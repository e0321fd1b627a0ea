"""The reduction from a profiler trace to the per-layer metrics: on a
hand-made trace whose answers are known, and on small traces recorded on
a TPU v5e (one query each of ``breakdown-1m`` and ``stable-coloring-1m``)
kept under ``bench/tests/data``."""
import json
from pathlib import Path

import pytest

import tinybench
from snowbench.manifest import Bench
from snowbench.trace import TraceView, union

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HAND = {
    "host": [["bench.window", 0, 1000], ["bench.query", 0, 500],
             ["bench.query", 500, 500], ["bench.plan.stable_plans", 10, 100],
             ["bench.plan.compile_trace", 600, 200]],
    "devices": {DEV: {
        "ops": [["fusion.1", 200, 100], ["fusion.2", 250, 100],
                ["collective-permute-start.3", 800, 50],
                ["fusion.9", 990, 40]],
        "modules": [["jit__stable_stats", 200, 150],
                    ["jit_other", 800, 50]]}},
}


def _read(metric, view):
    return Bench.load(tinybench.REPO).reader(metric)(view)


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_hand_made_trace():
    v = TraceView(HAND, units=2)
    assert v.window_s == pytest.approx(1e-6)
    # busy: [200, 350] + [800, 850] + [990, 1000] (clipped to the window)
    assert v.busy_s(DEV) == pytest.approx(210e-9)
    assert v.idle_share() == pytest.approx(0.79)
    assert _read("device_idle_share.sweep", v) == pytest.approx(79.0)
    assert _read("host_plan_ms", v) == pytest.approx(300e-9 / 2 * 1e3)
    assert _read("sweep_program_ms", v) == pytest.approx(150e-9 / 2 * 1e3)
    assert _read("collective_ms_per_rollout", v) == pytest.approx(
        50e-9 / 2 * 1e3)
    gaps = v.idle_gaps()
    assert [g[0] for g in gaps] == [
        "bench.query (bench.plan.compile_trace 44%)",
        "bench.plan.stable_plans", "bench.query"]
    assert [g[1] for g in gaps] == pytest.approx([450e-9, 200e-9, 140e-9])
    assert v.top_ops()[0] == ["fusion.1", pytest.approx(100e-9)]


def test_readers_return_nothing_where_the_trace_has_nothing():
    empty = {"host": [["bench.window", 0, 1000]], "devices": {}}
    v = TraceView(empty, units=3)
    for m in ("host_plan_ms", "sweep_program_ms", "device_idle_share.sweep",
              "collective_ms_per_rollout", "device_idle_share.fanout"):
        assert _read(m, v) is None
    assert v.idle_gaps() == [] and v.mean_busy_s() == 0.0


def test_a_trace_without_a_window_span_is_refused():
    with pytest.raises(ValueError):
        TraceView({"host": [], "devices": {}}, units=1)


def _recorded(name):
    doc = json.loads((DATA / f"{name}.trace.json").read_text())
    return doc, TraceView(doc["events"], doc["units"])


def _busy_by_sweep(rows, lo, hi):
    """Busy seconds by a sweep over sorted start/end edges, written apart
    from ``union`` so the two check each other."""
    edges = []
    for _, s, d in rows:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, since = 0.0, 0, None
    for x, step in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth == 0 and step == 1:
            since = x
        depth += step
        if depth == 0:
            busy += x - since
    return busy * 1e-9


@pytest.mark.parametrize("name", ["breakdown-1m", "stable-coloring-1m"])
def test_recorded_tpu_trace(name):
    doc, v = _recorded(name)
    ops = doc["events"]["devices"][DEV]["ops"]
    assert v.busy_s(DEV) == pytest.approx(_busy_by_sweep(ops, *v.window))
    idle = _read("device_idle_share.sweep", v)
    assert 0.0 < idle < 100.0
    assert idle == pytest.approx(100.0 * (1 - v.busy_s(DEV) / v.window_s))
    window_ms = v.window_s * 1e3
    plan, program = _read("host_plan_ms", v), _read("sweep_program_ms", v)
    # host planning and the sweep program take turns within one query
    assert 0 < plan < window_ms and 0 < program < window_ms
    assert plan + program <= window_ms
    assert program >= v.busy_s(DEV) * 1e3 * 0.5
    assert _read("collective_ms_per_rollout", v) is None
    top = v.top_ops()
    assert top and all(" " not in op for op, _ in top)
    for label, _ in v.idle_gaps():
        assert label.split(" (")[0] in {
            "bench.query", "bench.plan.stable_plans",
            "bench.plan.compile_trace", "bench.plan.breakdown_trace",
            "bench.device.stable_stats", "bench.device.trace_ldt",
            "bench.host.control"}
