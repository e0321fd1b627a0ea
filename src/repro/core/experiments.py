"""Declarative paper-experiment sweep runner.

One grid specification (:class:`ExperimentSpec`) describes a family of
runs — protocol × n × fanout k × scene × churn cadence × payload ×
view model × engine × seed batch — and the runner executes every cell
through the right engine, reduces seed-batched metrics into one
deterministic row, and persists results as resumable JSON.  This is the
subsystem behind ``benchmarks/paper_repro.py`` (every figure/table of
the paper regenerates from a spec) and consolidates the ad-hoc loops
that used to live in ``bench_protocols.py`` / ``bench_fanout_k.py``.

Engine routing (per cell)
-------------------------
* ``snow`` / ``coloring``:
    * ``engine="events"`` — the live discrete-event loop
      (:mod:`repro.core.scenarios`), full protocol semantics, n capped
      at ``events_max_n``;
    * otherwise (``"auto"`` / ``"vectorized"``) the closed forms:
      stable → :func:`repro.core.engine.stable_sweep`;
      churn/breakdown with ``view_model="oracle"`` →
      :func:`repro.core.engine.trace_sweep` (epoch-segmented);
      ``view_model="stale"`` →
      :func:`repro.core.engine.run_trace_stale_vectorized` (divergent
      views, shared precompiled epoch plans across seeds).
* ``gossip`` / ``plumtree``: events below ``events_max_n`` (or on
  request), else the closed forms
  :func:`repro.core.baselines.gossip_sweep` /
  :func:`repro.core.baselines.plumtree_sweep` (stable only —
  dynamic-membership baseline cells beyond the cap are recorded as
  skipped, not silently dropped).
* ``flooding``: events only (no closed form exists); cells beyond
  ``events_max_n`` are recorded as skipped.

Metrics populated per row: seed-averaged LDT (ms, with a ci95 column),
RMR and its payload/redundant split (bytes/node/message), worst-case
reliability over the seed batch, and — when ``spec.control`` is on —
the DESIGN.md §9 control-plane byte totals per category plus the
normalized overhead rates ``control_Bps_node`` / ``data_Bps_node`` /
``total_Bps_node`` (bytes per node per second over the run window; the
total is the §5 overhead axis: control + payload + redundant).

Determinism and resume
----------------------
Rows contain no wall-clock values: the same spec and seeds produce an
*identical* JSON document (``tests/test_experiments.py`` asserts this
byte-for-byte).  ``ExperimentRunner.run`` writes the document after
every completed cell and skips already-present rows on the next
invocation, so an interrupted sweep resumes where it stopped; a spec
whose parameters changed under an existing result file raises instead
of silently mixing grids.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from .baselines import gossip_sweep, plumtree_sweep
from .churn import ChurnTrace, paper_breakdown_trace, paper_churn_trace
from .control import ControlParams, gossip_control
from .scenarios import run_breakdown, run_churn, run_stable, summarize
from .spans import span
from .specs import NetworkSpec, RunSpec, WorkloadSpec

#: protocols with a closed-form route (any n) vs events-only baselines
CLOSED_FORM = ("snow", "coloring")
SCENES = ("stable", "churn", "breakdown")


@dataclass(frozen=True)
class Cell:
    """One grid point — everything an engine needs besides the seeds."""

    protocol: str
    scene: str
    n: int
    k: int
    payload: int
    view_model: str
    engine: str

    def key(self) -> str:
        """Stable row id inside the results JSON."""
        return (f"{self.protocol}/{self.scene}/n{self.n}/k{self.k}"
                f"/p{self.payload}/{self.view_model}/{self.engine}")


@dataclass(frozen=True)
class ExperimentSpec:
    """A declarative sweep: the cross product of the axis tuples,
    canonicalized (stable cells ignore ``view_model``; baselines have
    no stale closed form) and deduplicated, in deterministic order."""

    name: str
    protocols: Tuple[str, ...] = ("snow",)
    scenes: Tuple[str, ...] = ("stable",)
    ns: Tuple[int, ...] = (500,)
    ks: Tuple[int, ...] = (4,)
    payloads: Tuple[int, ...] = (64,)
    view_models: Tuple[str, ...] = ("oracle",)
    engines: Tuple[str, ...] = ("auto",)
    seeds: Tuple[int, ...] = (0, 1)
    n_messages: int = 20
    rate_s: float = 1.0
    churn_every: int = 10
    crash_every: int = 10
    #: victims of the breakdown trace are drawn with this fixed seed so
    #: every delay seed replays identical crashes
    trace_seed: int = 0
    #: account DESIGN.md §9 control-plane bytes and overhead rates
    control: bool = True
    #: hard cap for event-loop cells (per-node views are O(n²) memory)
    events_max_n: int = 2500
    #: optional network fabric (DESIGN.md §12) applied to every cell —
    #: None keeps the historical flat uniform fabric and keeps the spec
    #: fingerprint byte-identical to pre-§12 result files
    net: Optional[NetworkSpec] = None
    #: optional offered-traffic model (DESIGN.md §14): snow cells route
    #: through the workload engines (concurrent publishers, topic
    #: multicast, egress queueing) instead of the fixed-cadence
    #: broadcast schedule; None keeps the historical schedule and the
    #: pre-§14 spec fingerprint
    workload: Optional[WorkloadSpec] = None

    def cells(self) -> List[Cell]:
        seen = set()
        out: List[Cell] = []
        for proto, scene, n, k, payload, vm, eng in itertools.product(
                self.protocols, self.scenes, self.ns, self.ks,
                self.payloads, self.view_models, self.engines):
            if scene == "stable" or proto not in CLOSED_FORM:
                vm = "oracle"      # no stale axis outside the closed form
            cell = Cell(proto, scene, n, k, payload, vm, eng)
            if cell.key() in seen:
                continue
            seen.add(cell.key())
            out.append(cell)
        return out

    def asdict(self) -> dict:
        # round-trip through JSON so the fingerprint compares equal to
        # what a result file loads back (tuples become lists); ``net``
        # and ``workload`` are omitted entirely when None so result
        # files written before the fields existed still
        # fingerprint-match their specs
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self)
             if f.name not in ("net", "workload")}
        if self.net is not None:
            d["net"] = self.net.asdict()
        if self.workload is not None:
            d["workload"] = self.workload.asdict()
        return json.loads(json.dumps(d))


def _trace_for(spec: ExperimentSpec, cell: Cell) -> Optional[ChurnTrace]:
    if cell.scene == "churn":
        return paper_churn_trace(cell.n, spec.n_messages, spec.rate_s,
                                 spec.churn_every)
    if cell.scene == "breakdown":
        return paper_breakdown_trace(cell.n, spec.n_messages, spec.rate_s,
                                     spec.trace_seed, spec.crash_every)
    return None


def _duration_s(spec: ExperimentSpec, trace: Optional[ChurnTrace]) -> float:
    """The closed-form control/data integration window: the broadcast
    span (plus trailing trace events)."""
    if trace is not None:
        spans = trace.epoch_spans()
        return float(spans[-1][1] - spans[0][0]) if spans else 0.0
    return spec.n_messages * spec.rate_s


def _events_horizon_s(spec: ExperimentSpec, cell: Cell,
                      trace: Optional[ChurnTrace]) -> float:
    """How long the live event loop actually runs — mirrors the
    ``sim.run(until=...)`` expressions in :mod:`repro.core.scenarios`.
    Events-cell control frames accrue over THIS window (SWIM keeps
    probing through the 15 s drain), so their per-second rates must be
    normalized by it; the steady-rate categories then compare like for
    like against closed-form cells normalized by the message span."""
    if cell.scene == "stable":
        return spec.n_messages * spec.rate_s + 15.0
    last = trace.msg_times[-1] if trace.msg_times else 0.0
    if cell.scene == "churn":
        return last + spec.rate_s + 15.0
    return last + spec.rate_s - 0.02 + 15.0      # breakdown


def _mean(vals: List[float]) -> float:
    vals = [v for v in vals if not math.isnan(v)]
    return float(np.mean(vals)) if vals else float("nan")


def _ci95(vals: List[float]) -> float:
    vals = [v for v in vals if not math.isnan(v)]
    if len(vals) < 2:
        return 0.0
    return float(1.96 * np.std(vals, ddof=1) / np.sqrt(len(vals)))


def _reduce(cell: Cell, spec: ExperimentSpec, engine_used: str,
            per_seed: List[dict], control_totals: Optional[Dict[str, float]],
            data_window_s: float,
            control_window_s: Optional[float] = None) -> dict:
    """Collapse per-seed metric dicts into one deterministic row.

    Overhead normalization: data bytes all land inside the broadcast
    span (``data_window_s``), control traffic accrues over the window
    the engine actually modeled/ran (``control_window_s`` — the live
    loop keeps probing through its 15 s drain, the closed forms
    integrate over the span).  Each term is divided by its own window,
    so both engines report the same steady-state rates."""
    with span("snow.rows"):
        ldts = [s["ldt"] for s in per_seed]
        rmrs = [s["rmr"] for s in per_seed]
        reds = [s.get("rmr_redundant", 0.0) for s in per_seed]
        rels = [s["reliability"] for s in per_seed]
        row = {
            "cell": dataclasses.asdict(cell),
            "engine_used": engine_used,
            "seeds": list(spec.seeds),
            "n_messages": spec.n_messages,
            "ldt_ms": _mean(ldts) * 1000.0,
            "ldt_ms_ci95": _ci95([v * 1000.0 for v in ldts]),
            "rmr_B": _mean(rmrs),
            "redundant_B": _mean(reds),
            "payload_B": _mean(rmrs) - _mean(reds),
            "reliability": float(min(rels)) if rels else float("nan"),
        }
        if control_totals is not None:
            if control_window_s is None:
                control_window_s = data_window_s
            n = cell.n
            td = max(data_window_s, 1e-12)
            tc = max(control_window_s, 1e-12)
            control_b = float(sum(control_totals.values()))
            data_bps = _mean(rmrs) * spec.n_messages / td
            row["control_B"] = {k: float(v) for k, v in
                                sorted(control_totals.items())}
            row["data_window_s"] = data_window_s
            row["control_window_s"] = control_window_s
            row["control_Bps_node"] = control_b / (n * tc)
            row["data_Bps_node"] = data_bps
            row["total_Bps_node"] = data_bps + control_b / (n * tc)
        return row


def _events_cell(spec: ExperimentSpec, cell: Cell,
                 trace: Optional[ChurnTrace]) -> Tuple[List[dict],
                                                       Dict[str, float]]:
    """Run one cell through the live event loop, per seed; returns the
    per-seed summaries plus seed-averaged control category totals
    (accrued over :func:`_events_horizon_s`)."""
    params = ControlParams() if spec.control else None
    per_seed, ctl_acc = [], {}
    for seed in spec.seeds:
        kw = dict(n=cell.n, k=cell.k, n_messages=spec.n_messages,
                  rate_s=spec.rate_s, seed=seed, payload=cell.payload)
        if spec.net is None:
            kw.update(engine="events", control=params)
        else:
            kw.update(net=spec.net,
                      run=RunSpec(engine="events", control=params))
        if cell.scene == "stable":
            c = run_stable(cell.protocol, **kw)
        elif cell.scene == "churn":
            c = run_churn(cell.protocol, trace=trace, **kw)
        else:
            c = run_breakdown(cell.protocol, trace=trace, **kw)
        per_seed.append(summarize(c))
        for k_, v in c.metrics.control_bytes.items():
            ctl_acc[k_] = ctl_acc.get(k_, 0.0) + v / len(spec.seeds)
    if spec.control and cell.protocol in ("gossip", "flooding"):
        # the live GossipNode maintains no membership; charge the §9
        # modeled per-round full-view push over the SAME window the
        # live frames accrued in, so per-second rates stay consistent
        horizon = _events_horizon_s(spec, cell, trace)
        for k_, v in gossip_control(cell.n, horizon).items():
            ctl_acc[k_] = ctl_acc.get(k_, 0.0) + v
    return per_seed, (ctl_acc if spec.control else None)


def _closed_form_cell(spec: ExperimentSpec, cell: Cell,
                      trace: Optional[ChurnTrace]
                      ) -> Tuple[List[dict], Optional[Dict[str, float]],
                                 str]:
    """Run one snow/coloring cell through the closed-form engines.

    ``cell.engine="device"`` requests the device-resident fused sweep
    (:mod:`repro.core.device_sweep`): stable cells and oracle-view
    churn/breakdown cells run the whole seed batch in one device
    dispatch (``engine_used="device"``).  Stale-view cells have no
    device expression (the adoption sweep is inherently host-ordered),
    so they fall back to the host engine and report it honestly via
    ``engine_used="vectorized-stale"``.
    """
    params = ControlParams() if spec.control else None
    sweep_engine = "device" if cell.engine == "device" else "host"
    if cell.scene == "stable":
        rows = stable_sweep_rows(spec, cell, params, engine=sweep_engine)
        used = "device" if sweep_engine == "device" else "vectorized"
    elif cell.view_model == "stale":
        rows = _stale_rows(spec, cell, trace, params)
        used = "vectorized-stale"
    else:
        from .engine import trace_sweep

        if spec.net is None:
            rows = trace_sweep(cell.protocol, trace, cell.k, spec.seeds,
                               payload=cell.payload, control=params,
                               engine=sweep_engine)
        else:
            rows = trace_sweep(cell.protocol, trace, cell.k, spec.seeds,
                               payload=cell.payload, net=spec.net,
                               run=RunSpec(engine=sweep_engine,
                                           control=params))
        used = "device" if sweep_engine == "device" else "vectorized"
    ctl = None
    if spec.control:
        ctl_rows = [r["control_B"] for r in rows if "control_B" in r]
        ctl = {}
        for cr in ctl_rows:
            for k_, v in cr.items():
                ctl[k_] = ctl.get(k_, 0.0) + v / len(ctl_rows)
    return rows, ctl, used


def stable_sweep_rows(spec: ExperimentSpec, cell: Cell,
                      params: Optional[ControlParams],
                      engine: str = "host") -> List[dict]:
    from .engine import stable_sweep

    if spec.net is None:
        return stable_sweep(cell.protocol, cell.n, cell.k, spec.seeds,
                            n_messages=spec.n_messages, rate_s=spec.rate_s,
                            payload=cell.payload, control=params,
                            engine=engine)
    return stable_sweep(cell.protocol, cell.n, cell.k, spec.seeds,
                        n_messages=spec.n_messages, rate_s=spec.rate_s,
                        payload=cell.payload, net=spec.net,
                        run=RunSpec(engine=engine, control=params))


def _stale_rows(spec: ExperimentSpec, cell: Cell, trace: ChurnTrace,
                params: Optional[ControlParams]) -> List[dict]:
    from .engine import compile_trace, run_trace_stale_vectorized

    if spec.net is not None and (spec.net.hier is not None
                                 or spec.net.locality != "uniform"
                                 or spec.net.loss is not None):
        raise NotImplementedError(
            "stale-view cells model the flat uniform lossless fabric only")
    # epoch plans are delta-chained (epoch e+1 derives from epoch e —
    # bit-identical to full re-plans, see planner.plan_delta) and
    # compiled once across all seeds
    epochs = compile_trace(cell.protocol, trace, cell.k, trace.all_ids(),
                           cell.payload, replan="delta")
    fixed = set(range(cell.n))
    rows = []
    for seed in spec.seeds:
        c = run_trace_stale_vectorized(cell.protocol, trace, cell.k, seed,
                                       cell.payload, epochs=epochs,
                                       control=params)
        s = c.metrics.summary(fixed)
        if params is not None:
            s["control_B"] = {k_: float(v) for k_, v in
                              c.metrics.control_bytes.items()}
        rows.append(s)
    return rows


def route(spec: ExperimentSpec, cell: Cell) -> str:
    """The engine decision table, stated positively.

    * snow/coloring: the closed forms unless ``engine="events"``
      (which is capped at ``events_max_n`` like every events cell);
      ``engine="device"`` selects the device-resident fused sweep
      inside the closed-form path (``_closed_form_cell``);
    * gossip/plumtree: their closed forms exist for the stable scene
      only — used beyond the cap or on ``engine="vectorized"``; they
      have no device expression, so ``engine="device"`` is an explicit
      skip;
    * flooding (and dynamic-membership baselines): events only.

    Returns ``"closed-form" | "gossip-closed-form" |
    "plumtree-closed-form" | "events"``, or ``"skipped:<reason>"``
    when no engine can serve the cell.
    """
    if cell.protocol in CLOSED_FORM:
        if cell.engine != "events":
            return "closed-form"
    elif cell.engine == "device":
        return f"skipped:no device engine for {cell.protocol}"
    elif cell.protocol in ("gossip", "plumtree") and cell.scene == "stable":
        if cell.engine == "vectorized" or (cell.engine == "auto"
                                           and cell.n > spec.events_max_n):
            return f"{cell.protocol}-closed-form"
    elif cell.engine == "vectorized":
        return (f"skipped:no closed form for {cell.protocol}/"
                f"{cell.scene}")
    if cell.n > spec.events_max_n:
        return (f"skipped:event-loop cell at n={cell.n} exceeds "
                f"events_max_n={spec.events_max_n}")
    return "events"


def _workload_cell(spec: ExperimentSpec, cell: Cell) -> dict:
    """Route one cell through the workload engines (DESIGN.md §14).

    The workload model replaces the fixed-cadence broadcast schedule
    with generated traffic (concurrent publishers, topic multicast,
    optional egress caps), so it only exists for the snow protocol:
    ``engine="events"`` runs the queueing-aware event loop (capped at
    ``events_max_n``), anything else the vectorized level sweep with
    M/G/1 waiting folded in (``"device"`` selects the fused device
    sweep).  Tail quantiles and the delivered-within-deadline fraction
    ride along seed-averaged next to the usual LDT/RMR columns."""
    from .workload import workload_sweep

    wl = spec.workload
    if cell.protocol != "snow":
        return {"cell": dataclasses.asdict(cell),
                "skipped": f"no workload engine for {cell.protocol}"}
    if cell.engine == "events":
        if cell.n > spec.events_max_n:
            return {"cell": dataclasses.asdict(cell),
                    "skipped": f"event-loop cell at n={cell.n} exceeds "
                               f"events_max_n={spec.events_max_n}"}
        rows = workload_sweep(cell.n, cell.k, spec.seeds, wl,
                              engine="events")
        used = "events"
    else:
        rows = workload_sweep(cell.n, cell.k, spec.seeds, wl,
                              engine="vectorized",
                              device=(cell.engine == "device"))
        used = "device" if cell.engine == "device" else "vectorized"
    row = _reduce(cell, spec, used, rows, None, wl.horizon_s)
    row["n_messages"] = _mean([r["n_messages"] for r in rows])
    row["offered_hz"] = _mean([r["offered_hz"] for r in rows])
    for key in sorted(rows[0]):
        if key.endswith("_ldt") or key.endswith("_delivery"):
            row[key + "_ms"] = _mean([r[key] for r in rows]) * 1000.0
    if wl.deadline_s is not None:
        row["delivered_frac"] = _mean([r["delivered_frac"] for r in rows])
    return row


def run_cell(spec: ExperimentSpec, cell: Cell) -> dict:
    """Execute one grid cell end to end via :func:`route`; returns the
    reduced row, or a ``{"skipped": reason}`` row for cells no engine
    can serve — explicit, so reports show the hole.  The whole call is
    the ``snow.query`` span (:mod:`repro.core.spans`)."""
    with span("snow.query", scene=cell.scene, protocol=cell.protocol,
              n=cell.n, k=cell.k, seeds=len(spec.seeds)):
        return _run_cell(spec, cell)


def _run_cell(spec: ExperimentSpec, cell: Cell) -> dict:
    if spec.workload is not None:
        if spec.scenes != ("stable",):
            raise ValueError("workload specs drive their own (possibly "
                             "churn-coupled) traffic; use scenes="
                             "('stable',)")
        return _workload_cell(spec, cell)
    with span("snow.plan.trace") as sp:
        trace = _trace_for(spec, cell)
        r = route(spec, cell)
        sp.set(events=0 if trace is None else len(trace.events))
    with span("snow.trace.scan"):
        duration = _duration_s(spec, trace)
    if r.startswith("skipped:"):
        return {"cell": dataclasses.asdict(cell),
                "skipped": r.split(":", 1)[1]}
    if r == "events":
        per_seed, ctl = _events_cell(spec, cell, trace)
        return _reduce(cell, spec, "events", per_seed, ctl, duration,
                       _events_horizon_s(spec, cell, trace))
    if r in ("gossip-closed-form", "plumtree-closed-form"):
        params = ControlParams() if spec.control else None
        sweep = gossip_sweep if r == "gossip-closed-form" else plumtree_sweep
        rows = sweep(cell.n, cell.k, spec.seeds,
                     n_messages=spec.n_messages,
                     payload=cell.payload, rate_s=spec.rate_s,
                     control=params)
        ctl = rows[0].get("control_B") if spec.control else None
        return _reduce(cell, spec, r, rows, ctl, duration)
    per_seed, ctl, used = _closed_form_cell(spec, cell, trace)
    return _reduce(cell, spec, used, per_seed, ctl, duration)


class ExperimentRunner:
    """Executes specs into ``<out_dir>/<spec.name>.json``, resumably.

    The document layout is ``{"spec": {...}, "rows": {cell_key: row}}``
    serialized with sorted keys — rerunning a completed spec is a
    no-op that returns the identical document."""

    def __init__(self, out_dir) -> None:
        self.out_dir = Path(out_dir)

    def path(self, spec: ExperimentSpec) -> Path:
        return self.out_dir / f"{spec.name}.json"

    def load(self, spec: ExperimentSpec) -> Optional[dict]:
        p = self.path(spec)
        if not p.exists():
            return None
        return json.loads(p.read_text())

    def run(self, spec: ExperimentSpec,
            progress: Optional[Callable[[str], None]] = None,
            max_cells: Optional[int] = None) -> dict:
        """Run every grid cell not yet present in the result file.

        ``max_cells`` bounds how many *new* cells are executed (the
        resume tests interrupt with it); the partial document is still
        valid and a later ``run`` completes it.  Raises ``ValueError``
        if the file on disk was produced by a different spec."""
        doc = self.load(spec)
        if doc is None:
            doc = {"spec": spec.asdict(), "rows": {}}
        elif doc.get("spec") != spec.asdict():
            raise ValueError(
                f"{self.path(spec)} holds results of a different spec; "
                f"delete it (or rename the spec) to rerun")
        done = 0
        for cell in spec.cells():
            key = cell.key()
            if key in doc["rows"]:
                continue
            if max_cells is not None and done >= max_cells:
                break
            if progress:
                progress(f"[{spec.name}] {key}")
            doc["rows"][key] = run_cell(spec, cell)
            self._write(doc, spec)
            done += 1
        return doc

    def _write(self, doc: dict, spec: ExperimentSpec) -> None:
        self.out_dir.mkdir(parents=True, exist_ok=True)
        self.path(spec).write_text(
            json.dumps(doc, indent=2, sort_keys=True) + "\n")
