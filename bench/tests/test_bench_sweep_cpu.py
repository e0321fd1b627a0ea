"""Sweep cells at a tiny size on the CPU: the query builder, the plain
reference against the program, the control and the faults that the
correctness check must catch.  The measurement path (``run.py``)
refuses a CPU, so these drive ``harness.run`` directly."""
import json

import numpy as np
import pytest

import tinybench
from snowbench import harness, reference, sweep
from snowbench.manifest import Bench

SWEEPS = ["tiny-breakdown", "tiny-stable-coloring"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return Bench.load(tinybench.make(tmp_path_factory.mktemp("bench")))


def _cpu():
    import jax

    return jax.devices("cpu")[:1]


@pytest.mark.parametrize("n", [2, 3, 5, 8, 9, 17, 64, 101, 1000, 4097])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_reference_trees_are_the_planners(n, k):
    from repro.core.planner import PRIMARY, SECONDARY, plan_broadcast, \
        plan_colored

    members = np.arange(n)
    for root in sorted({0, n // 2, n - 1}):
        for color, plan in ((None, plan_broadcast(members, root, k)),
                            (0, plan_colored(members, root, k, PRIMARY)),
                            (1, plan_colored(members, root, k, SECONDARY))):
            parent, depth = reference.tree(n, root, k, color)
            assert np.array_equal(parent, np.asarray(plan.parent))
            assert np.array_equal(depth, np.asarray(plan.depth))


def test_query_seeds_are_a_function_of_seed_and_index():
    t = {"fresh_trace_seed": True}
    a = sweep.query(2**33 + 5, 3, 5, t)
    assert a == sweep.query(2**33 + 5, 3, 5, t)
    assert a != sweep.query(2**33 + 5, 4, 5, t)
    assert a != sweep.query(2**33 + 6, 3, 5, t)
    assert all(0 <= s < 2**31 for s in a["seeds"] + (a["trace_seed"],))


def test_the_allocator_policy_is_taken_where_glibc_runs():
    import platform

    if platform.libc_ver()[0] != "glibc":
        assert sweep.keep_freed_memory() == {}
        return
    assert sweep.keep_freed_memory() == {"M_MMAP_THRESHOLD": 1,
                                         "M_TRIM_THRESHOLD": 1}
    assert "trace_seed" not in sweep.query(7, 0, 5, {})


@pytest.mark.parametrize("cell", SWEEPS)
@pytest.mark.parametrize("trace", [False, True])
def test_tiny_run_is_correct(bench, cell, trace):
    out = harness.run(bench, cell, 2**32 + 11, 0.2, trace, _cpu())
    assert out["correct"], out
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"ldt_gap", "ci95_gap", "rows_off"}
    if trace:
        assert "breakdown" in out and "window_s" in out["device"]
    else:
        assert set(out["metrics"]) == {"sweep_rate", "setup_s"}
        assert out["metrics"]["sweep_rate"]["value"] > 0


@pytest.mark.parametrize("cell", SWEEPS)
def test_window_line_gives_the_seconds_of_each_unit(bench, cell, capsys):
    out = harness.run(bench, cell, 2**32 + 12, 0.2, False, _cpu())
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("window:")][0]
    seconds = json.loads(line[line.index("[", line.index("each unit")):])
    assert len(seconds) == out["attempted"]
    assert all(s > 0 for s in seconds)


@pytest.mark.parametrize("cell", SWEEPS)
def test_calibration_separates_program_and_bfloat16_control(bench, cell):
    """``calibrate.readings`` at a tiny size: the program's readings sit
    under every limit, the control's exceed at least one."""
    import calibrate

    got = calibrate.readings(bench, cell, [424242, 424243], 1, _cpu())
    limits = bench.limits(cell)
    assert got["failed"] == 0
    assert all(got["lower"][k] <= limits[k] for k in limits), got["lower"]
    assert any(got["upper"][k] > limits[k] for k in limits), got["upper"]


def _half_batch(fn):
    """Half of the seeds left out, the mean taken over the rest."""
    def broken(*a, **kw):
        args = list(a)
        i = 1 if fn.__name__ == "stable_stats_device" else 2
        seeds = list(args[i])
        args[i] = seeds[:max(1, len(seeds) // 2)]
        out = fn(*args, **kw)
        if isinstance(out, tuple):
            return tuple(np.resize(o, len(seeds)) for o in out)
        return np.resize(out, len(seeds))
    return broken


def _altered(fn):
    """One seed's LDT moved by 10 ms, the least forwarding delay,
    where it is produced."""
    def broken(*a, **kw):
        out = fn(*a, **kw)
        ldt = np.array(out[0] if isinstance(out, tuple) else out)
        ldt[0] += 1e-2
        return (ldt,) + tuple(out[1:]) if isinstance(out, tuple) else ldt
    return broken


@pytest.mark.parametrize("cell", SWEEPS)
@pytest.mark.parametrize("fault", [_half_batch, _altered])
def test_faults_make_the_run_incorrect(bench, cell, fault, monkeypatch):
    from repro.core import device_sweep

    for name in ("stable_stats_device", "trace_ldt_device"):
        monkeypatch.setattr(device_sweep, name,
                            fault(getattr(device_sweep, name)))
    out = harness.run(bench, cell, 99, 0.1, False, _cpu())
    assert out["correct"] is False, out
