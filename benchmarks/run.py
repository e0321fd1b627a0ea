"""Benchmark runner — one section per paper table/figure plus the
framework benches.  Prints ``name,us_per_call,derived`` CSV lines at the
end for machine consumption; full tables above them.

``--smoke`` runs a reduced-size pass of the sections that support it
(CI's post-test sanity run); ``--only a,b`` restricts to named sections.
"""
from __future__ import annotations

import argparse
import inspect
import os
import time
from pathlib import Path

try:
    import _bootstrap  # noqa: F401  (direct execution)
except ImportError:
    from benchmarks import _bootstrap  # noqa: F401  (package import)


# single source of truth: section name -> benchmark module (imported
# lazily so `--only` runs don't pay for jax-heavy modules)
SECTION_MODULES = {
    "protocols_table2": "bench_protocols",
    "scale_n_fig6a": "bench_scale_n",
    "device_scale": "bench_device",
    "fanout_k_fig6b": "bench_fanout_k",
    "paper_repro": "paper_repro",
    "locality_scale": "bench_locality",
    "replan_scale": "bench_replan",
    "workload_scale": "bench_workload",
    "children_micro": "bench_children_micro",
    "collectives": "bench_collectives",
    "kernels": "bench_kernels",
    "roofline": "bench_roofline",
}
SECTIONS = tuple(SECTION_MODULES)


BASELINE = Path(__file__).parent / "results" / "smoke_baseline.json"

# --check tolerance bands (compared against the committed baseline)
WALL_RATIO = 2.0          # fail a section on > 2× wall-time regression
WALL_HEADROOM_S = 1.0     # ... with absolute headroom for tiny sections
LDT_REL_TOL = 0.35        # seeded smoke LDT may drift only this much
MIN_VEC_SPEEDUP = 5.0     # closed-form engine must stay clearly ahead
MIN_CHURN_VEC_SPEEDUP = 3.0   # epoch-segmented churn engine floor (the
                              # smoke n is small; full bench shows 20x+)
MIN_REPLAN_SPEEDUP = 10.0     # delta vs full re-plan per 1-event epoch
                              # at n=1M (DESIGN.md §13; measured ~17x)
# §5.4 redundancy bands: snow must never send a redundant byte in the
# stable scenario (structural disjointness), gossip must keep its
# duplicate floor (k-1 of every k forwards are redundant: ~3 x 108 B)
MAX_SNOW_REDUNDANT_B = 1e-9
MIN_GOSSIP_REDUNDANT_B = 50.0
# §5 overhead bands (paper_repro smoke): snow's TOTAL overhead
# (control + payload + redundant, B per node per second) must stay
# strictly below the gossip baseline, and its control plane must stay
# well below gossip's per-round view push (DESIGN.md §9: SWIM probes +
# delta member-updates + 15 s anti-entropy vs a 1 s full-view round)
MAX_OVERHEAD_RATIO = 1.0
MAX_CONTROL_RATIO = 0.5
# §11 fault-injection bands (scale_n smoke): the pull-repair engine
# must close the loss/crash reliability dip to exactly 1.0 at loss
# ≤ 5%, and its closed-form byte bill (digest cadence + fetches) must
# stay strictly under the reliable-epoch rebroadcast comparator
MIN_REPAIR_RELIABILITY = 1.0
MAX_REPAIR_REBROADCAST_RATIO = 1.0
# device-engine bands (device_scale smoke): the counter-RNG device path
# is statistically pinned, not bit-exact — its seeded mean-LDT drift vs
# the host DelayBank oracle may not exceed this, and the committed
# device_scale trajectory (speedup at 1M, completed 10M row) must hold.
# The locality_scale smoke's drift vs its committed 50k row rides the
# same *ldt_drift band.
MAX_DEVICE_LDT_DRIFT = 0.10
# §14 workload bands (workload_scale smoke): the saturation knee —
# the largest offered utilization ρ whose within-deadline delivered
# fraction still holds ≥ 0.99 — may never creep below this floor
MIN_SATURATION_RHO = 0.7


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR``
    (JAX reads the variable itself) or else at ``<repo>/.jax_cache``: a
    fixed path, so the next run of this checkout hits the cache.  For
    entry points only; importing the library sets no cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _calibrate() -> float:
    """Machine-speed probe: min-of-3 wall time of a fixed planner
    workload.  Stored in the baseline and re-measured at check time so
    the >2× wall band compares *this* machine against itself-at-baseline
    scaled by relative speed — heterogeneous CI runners don't flake the
    gate on hardware alone."""
    import numpy as np

    from repro.core.planner import plan_broadcast

    members = np.arange(20_000)
    plan_broadcast(members, 0, 4)            # warm caches / imports
    best = min(_timed(lambda: plan_broadcast(members, 0, 4))
               for _ in range(3))
    return best


def _timed(fn) -> float:
    t0 = time.time()
    fn()
    return time.time() - t0


def _check(sections, metrics) -> list:
    """Compare a smoke pass against the committed baseline; returns a
    list of human-readable violations (empty = pass)."""
    import json

    if not BASELINE.exists():
        return [f"missing baseline {BASELINE}; run --smoke --write-baseline"]
    doc = json.loads(BASELINE.read_text())
    base = doc["sections"]
    # hardware normalization: >1 means this machine is slower than the
    # one that wrote the baseline (clamped — calibration is a probe, not
    # an excuse for an order-of-magnitude regression)
    factor = 1.0
    if doc.get("calibration_s"):
        factor = min(max(_calibrate() / doc["calibration_s"], 0.5), 8.0)
    problems = []
    for name, us, derived in sections:
        if derived.startswith("fail"):
            problems.append(f"{name}: {derived}")
            continue
        b = base.get(name)
        if b is None:
            continue          # new section, no baseline yet
        wall_s = us / 1e6
        scaled = b["wall_s"] * factor
        limit = max(WALL_RATIO * scaled, scaled + WALL_HEADROOM_S)
        if wall_s > limit:
            problems.append(
                f"{name}: wall {wall_s:.2f}s > {limit:.2f}s (baseline "
                f"{b['wall_s']:.2f}s x machine factor {factor:.2f}, "
                f"band {WALL_RATIO}x)")
        m, bm = metrics.get(name, {}), b.get("metrics", {})
        # banded metric families, matched by key suffix so the stable
        # and churn variants (ldt_ms / churn_ldt_ms, ...) share rules:
        # *ldt_ms   — seeded drift band vs the committed baseline
        # *reliability — may never drop below the baseline
        # *speedup  — closed-form engines must stay clearly ahead
        for key in sorted(set(m) | set(bm)):
            mval, bval = m.get(key), bm.get(key)
            if mval is None:
                continue
            if key.endswith("ldt_ms") and bval:
                rel = abs(mval - bval) / bval
                if rel > LDT_REL_TOL:
                    problems.append(f"{name}: {key} {mval:.0f} vs "
                                    f"baseline {bval:.0f} ({rel:.0%})")
            elif key.endswith("repair_reliability"):
                # absolute band: repair must close the dip completely
                if mval < MIN_REPAIR_RELIABILITY - 1e-9:
                    problems.append(
                        f"{name}: {key} {mval} — pull repair left a "
                        f"reliability dip open at loss ≤ 5%")
            elif key.endswith("reliability"):
                if mval < (bval or 0.0) - 1e-9:
                    problems.append(f"{name}: {key} dropped to {mval}")
            elif key.endswith("speedup"):
                # absolute floor — fires even when the baseline predates
                # the metric, so a collapsed engine can't hide behind a
                # stale smoke_baseline.json
                floor = (MIN_REPLAN_SPEEDUP if "replan" in key
                         else MIN_CHURN_VEC_SPEEDUP if "churn" in key
                         else MIN_VEC_SPEEDUP)
                if mval < floor:
                    problems.append(f"{name}: {key} "
                                    f"{mval:.1f}x < {floor}x")
            elif key.endswith("overhead_ratio"):
                # absolute band: total overhead strictly below the
                # gossip baseline (the paper's §5 headline comparison;
                # applies to snow and to the plumtree closed form)
                if mval >= MAX_OVERHEAD_RATIO:
                    problems.append(
                        f"{name}: {key} {mval:.3f} — total overhead "
                        f"is not below the gossip baseline")
            elif key.endswith("rebroadcast_ratio"):
                # absolute band: repair bytes < rebroadcast comparator
                if mval >= MAX_REPAIR_REBROADCAST_RATIO:
                    problems.append(
                        f"{name}: {key} {mval:.3f} — pull repair costs "
                        f"as much as rebroadcasting every dipped message")
            elif key.endswith("control_ratio"):
                if mval >= MAX_CONTROL_RATIO:
                    problems.append(
                        f"{name}: {key} {mval:.3f} ≥ {MAX_CONTROL_RATIO} "
                        f"— snow control plane is not ≪ gossip's")
            elif key.endswith("ldt_drift"):
                # absolute band: device-vs-host statistical pin
                if mval > MAX_DEVICE_LDT_DRIFT:
                    problems.append(
                        f"{name}: {key} {mval:.1%} > "
                        f"{MAX_DEVICE_LDT_DRIFT:.0%} — device engine "
                        f"diverged from the host oracle")
            elif key.endswith("saturation_rho"):
                # absolute floor: egress queueing may shape tails but
                # must not pull the saturation knee into the band
                if mval < MIN_SATURATION_RHO - 1e-9:
                    problems.append(
                        f"{name}: {key} {mval} < {MIN_SATURATION_RHO} "
                        f"— the offered-vs-delivered knee crept below "
                        f"the floor")
            elif key.endswith("committed_ok"):
                if mval < 1.0:
                    problems.append(
                        f"{name}: {key} {mval} — the committed results "
                        f"for this section are missing their acceptance "
                        f"rows (run `run.py --only {name}` to refresh)")
            elif key.endswith("cross_region_B"):
                # §12.3 band: the locality ring must strictly beat the
                # uniform ring on the expensive tier (same smoke run, so
                # the comparison is baseline-independent)
                if key.startswith("locality"):
                    uni = m.get("uniform_cross_region_B")
                    if uni is not None and mval >= uni:
                        problems.append(
                            f"{name}: locality_cross_region_B {mval:.3e} "
                            f">= uniform {uni:.3e} — the locality ring "
                            f"stopped reducing cross-region traffic")
            elif key.endswith("redundant_B"):
                # absolute redundancy bands (baseline-independent):
                # snow's stable redundant bytes are structurally zero,
                # gossip's duplicate floor must not collapse
                if "snow" in key and mval > MAX_SNOW_REDUNDANT_B:
                    problems.append(
                        f"{name}: {key} {mval!r} — snow sent redundant "
                        f"bytes in the stable scenario")
                elif "gossip" in key and mval < MIN_GOSSIP_REDUNDANT_B:
                    problems.append(
                        f"{name}: {key} {mval:.1f} B < "
                        f"{MIN_GOSSIP_REDUNDANT_B} B gossip floor")
    return problems


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes; skip the heavy kernel sections")
    ap.add_argument("--only", default="",
                    help="comma-separated section names to run")
    ap.add_argument("--check", action="store_true",
                    help="compare the smoke pass against the committed "
                         "baseline (results/smoke_baseline.json); exit 1 "
                         "on >2x wall-time regression or metric drift")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write results/smoke_baseline.json from this "
                         "smoke pass")
    args = ap.parse_args(argv)
    if args.check or args.write_baseline:
        args.smoke = True
    use_compile_cache()

    import importlib
    import json

    only = [s.strip() for s in args.only.split(",") if s.strip()]
    if only:
        unknown = [s for s in only if s not in SECTIONS]
        if unknown:
            ap.error(f"unknown section(s) {unknown}; choose from {SECTIONS}")
        names = [s for s in SECTIONS if s in only]
    elif args.smoke:
        # protocol-layer sections only; the jax kernel/roofline benches
        # have their own timings and dominate smoke wall-time
        names = ["scale_n_fig6a", "device_scale", "paper_repro",
                 "locality_scale", "replan_scale", "workload_scale",
                 "children_micro"]
    else:
        names = list(SECTIONS)

    sections = []
    metrics = {}
    for name in names:
        mod = importlib.import_module(f"benchmarks.{SECTION_MODULES[name]}")
        t0 = time.time()
        print(f"\n=== {name} " + "=" * max(1, 60 - len(name)))
        try:
            kwargs = {}
            if args.smoke and "smoke" in inspect.signature(mod.main).parameters:
                kwargs["smoke"] = True
            for line in mod.main(**kwargs):
                print(line)
            sections.append((name, (time.time() - t0) * 1e6, "ok"))
            metrics[name] = dict(getattr(mod, "LAST_SMOKE", {}))
        except Exception as e:  # noqa: BLE001
            print(f"FAILED: {e!r}")
            sections.append((name, (time.time() - t0) * 1e6, f"fail:{e!r}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in sections:
        print(f"{name},{us:.0f},{derived}")

    if args.write_baseline:
        BASELINE.parent.mkdir(parents=True, exist_ok=True)
        BASELINE.write_text(json.dumps({
            "calibration_s": _calibrate(),
            "sections": {
                name: {"wall_s": us / 1e6, "metrics": metrics.get(name, {})}
                for name, us, derived in sections if derived == "ok"
            }}, indent=2) + "\n")
        print(f"baseline written: {BASELINE}")

    if args.check:
        problems = _check(sections, metrics)
        if problems:
            print("\nCHECK FAILED:")
            for p in problems:
                print(f"  - {p}")
            raise SystemExit(1)
        print("\ncheck ok: within tolerance of committed baseline")

    if any(d.startswith("fail") for _, _, d in sections):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
