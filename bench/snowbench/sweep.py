"""Sweep traffic: a capacity planner's back-to-back queries.

One client submits a query, waits for its row and submits the next.  A
query is one ``ExperimentSpec`` cell run through
``experiments.run_cell`` with ``engines=("device",)``: the cell's
configuration (fleet size, fan-out, payload, messages) under the
traffic file's protocol and scene, with delay seeds drawn fresh from
``--seed`` and the query's index (and, where the traffic file says
``fresh_trace_seed``, a fresh breakdown trace seed, so new victims).

Work is counted as deliveries: seeds × messages × n per query.

The host's allocator is held to one policy for the whole run
(:func:`keep_freed_memory`): a query's planning allocates and frees
hundreds of megabytes of numpy arrays, and glibc's default policy hands
some of it back to the kernel, to fault it in again page by page, in
some queries and not in others, by the heap's history.
"""
from __future__ import annotations

import contextlib
import ctypes
import ctypes.util
import math
from typing import Dict, List, Tuple

import numpy as np

from . import reference

#: row keys that the reference reproduces exactly
EXACT_KEYS = ("reliability", "rmr_B", "redundant_B", "payload_B")


def query(seed: int, index: int, count: int, traffic: dict) -> dict:
    """Delay seeds (and trace seed) of query ``index`` (-1: the set-up
    query): a pure function of ``--seed`` and the index, each below
    2**31."""
    state = np.random.SeedSequence(
        [int(seed) % 2**64, int(index) + 1]).generate_state(count + 1)
    q = {"index": index,
         "seeds": tuple(int(s) & 0x7FFFFFFF for s in state[:count])}
    if traffic.get("fresh_trace_seed"):
        q["trace_seed"] = int(state[count]) & 0x7FFFFFFF
    return q


#: glibc's ``mallopt`` parameters (malloc.h)
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3


def keep_freed_memory() -> dict:
    """Fix glibc's two thresholds for handing freed memory back: blocks
    under 1 GiB come from the heap, not from a mapping of their own
    (by default the cut-off moves with the sizes freed so far), and the
    heap's free top is kept up to 2 GiB.  A query then reuses the pages
    of the last query's arrays instead of faulting them in anew.
    Returns what ``mallopt`` answered (1: taken), or nothing where the C
    library has no ``mallopt``."""
    name = ctypes.util.find_library("c")
    libc = ctypes.CDLL(name) if name else None
    if libc is None or not hasattr(libc, "mallopt"):
        return {}
    return {"M_MMAP_THRESHOLD": libc.mallopt(M_MMAP_THRESHOLD, 1 << 30),
            "M_TRIM_THRESHOLD": libc.mallopt(M_TRIM_THRESHOLD,
                                             2**31 - 1)}


class Generator:
    """Drives ``run_cell`` for one cell, keeps every row, and checks one
    query drawn from the seed against :mod:`snowbench.reference`."""

    unit = "query"
    e2e_name = "sweep_rate"

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.done: List[Tuple[dict, dict]] = []
        self.failed: List[str] = []
        print(f"allocator: mallopt {keep_freed_memory()}", flush=True)

    def _spec(self, q: dict):
        from repro.core.experiments import ExperimentSpec

        c, t = self.cfg, self.traffic
        kw = dict(name="bench", protocols=(t["protocol"],),
                  scenes=(t["scene"],), ns=(c["n"],), ks=(c["k"],),
                  payloads=(c["payload_B"],), engines=("device",),
                  seeds=q["seeds"], n_messages=c["n_messages"],
                  rate_s=c["rate_s"])
        if t["scene"] != "stable":
            kw["crash_every"] = t["trace"]["crash_every"]
            kw["trace_seed"] = q["trace_seed"]
        return ExperimentSpec(**kw)

    def _run(self, index: int) -> dict:
        from repro.core.experiments import run_cell

        q = query(self.seed, index, self.cfg["seeds_per_query"],
                  self.traffic)
        spec = self._spec(q)
        return q, run_cell(spec, spec.cells()[0])

    def setup(self) -> None:
        """One query of the cell's own shapes: compiles or loads every
        program the window runs."""
        self._run(-1)

    def step(self, index: int) -> float:
        q, row = self._run(index)
        self.done.append((q, row))
        why = self._row_fault(row)
        if why:
            self.failed.append(f"query {index}: {why}")
        c = self.cfg
        return float(len(q["seeds"]) * c["n_messages"] * c["n"])

    def e2e(self, work: float, seconds: float) -> Dict[str, float]:
        return {self.e2e_name: work / seconds}

    def _row_fault(self, row: dict) -> str:
        """The exact checks every row gets: the device engine served it,
        its LDT is a positive number, and a stable row has reliability 1
        and the closed-form bytes (one frame per member per tree)."""
        if "skipped" in row:
            return f"skipped: {row['skipped']}"
        if row.get("engine_used") != "device":
            return f"served by {row.get('engine_used')!r}"
        if not (math.isfinite(row["ldt_ms"]) and row["ldt_ms"] > 0):
            return f"LDT {row['ldt_ms']!r}"
        if self.traffic["scene"] == "stable":
            trees = 2 if self.traffic["protocol"] == "coloring" else 1
            frame = self.cfg["frame_header_B"] + self.cfg["payload_B"]
            want = {"reliability": 1.0, "rmr_B": float(frame * trees),
                    "redundant_B": float(frame * (trees - 1))}
            for key, val in want.items():
                if row[key] != val:
                    return f"{key} {row[key]!r} != closed form {val!r}"
        return ""

    @contextlib.contextmanager
    def spans(self):
        """Host spans around the program's planning and device entry
        points, for a traced run only."""
        import jax
        from repro.core import device_sweep, engine, experiments

        def wrap(mod, name, span):
            fn = getattr(mod, name)

            def traced(*a, **kw):
                with jax.profiler.TraceAnnotation(span):
                    return fn(*a, **kw)

            setattr(mod, name, traced)
            return mod, name, fn

        saved = [wrap(engine, "stable_plans", "bench.plan.stable_plans"),
                 wrap(engine, "snow_stable_control", "bench.host.control"),
                 wrap(engine, "snow_trace_control", "bench.host.control"),
                 wrap(engine, "compile_trace", "bench.plan.compile_trace"),
                 wrap(experiments, "paper_breakdown_trace",
                      "bench.plan.breakdown_trace"),
                 wrap(device_sweep, "stable_stats_device",
                      "bench.device.stable_stats"),
                 wrap(device_sweep, "trace_ldt_device",
                      "bench.device.trace_ldt")]
        try:
            yield
        finally:
            for mod, name, fn in saved:
                setattr(mod, name, fn)

    def release(self) -> None:
        """Nothing of the program's state outlives a query."""

    def check(self) -> Tuple[Dict[str, float], int]:
        """Numbers compared on one query drawn from the seed, and the
        count of rows that failed the exact checks."""
        if not self.done:
            return {}, len(self.failed)
        pick = int(np.random.default_rng([int(self.seed), 1]).integers(
            len(self.done)))
        q, row = self.done[pick]
        return (compare(row, reference.answer(q, self.cfg, self.traffic)),
                len(self.failed))


def compare(row: dict, ref: dict) -> Dict[str, float]:
    """``ldt_gap``: the relative gap of the mean LDT; ``ci95_gap``: the
    gap of its 95% half-interval, relative to the reference's mean LDT;
    ``rows_off``: how many of the exact keys differ at all."""
    return {
        "ldt_gap": abs(row["ldt_ms"] - ref["ldt_ms"]) / ref["ldt_ms"],
        "ci95_gap": abs(row["ldt_ms_ci95"] - ref["ldt_ms_ci95"])
        / ref["ldt_ms"],
        "rows_off": float(sum(row[k] != ref[k] for k in EXACT_KEYS)),
    }
