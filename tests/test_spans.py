"""The program's host spans (``repro.core.spans``): the step durations
they keep, and the spans one query writes into a profiler trace."""
import time
from pathlib import Path

import pytest

from repro.core.experiments import ExperimentSpec, _trace_for, run_cell
from repro.core.spans import span

#: every span of a device query and the args it carries
ARGS = {
    "snow.query": {"scene", "protocol", "n", "k", "seeds"},
    "snow.plan.trace": {"events"},
    "snow.trace.scan": set(),
    "snow.plan.trees": {"epochs", "full"},
    "snow.control": set(),
    "snow.rows": set(),
    "snow.sweep": {"engine"},
    "snow.device.pack": set(),
    "snow.device.upload": {"bytes"},
    "snow.device.dispatch": {"program", "row_width", "row_fill"},
    "snow.device.pull": set(),
}
#: spans that the row accounting and the trace scans write more than once
REPEATED = {"snow.rows", "snow.trace.scan"}


def test_span_keeps_the_step_duration():
    with span("snow.test", a=1) as s:
        s.set(b=2)
        time.sleep(0.01)
    assert 0.01 <= s.seconds < 1.0


def _spec(scene):
    kw = dict(name="spans", scenes=(scene,), ns=(600,), ks=(4,),
              engines=("device",), seeds=(3, 4, 5), n_messages=6)
    if scene == "breakdown":
        kw.update(protocols=("snow",), crash_every=3, trace_seed=9)
    else:
        kw.update(protocols=("coloring",))
    return ExperimentSpec(**kw)


def _program_events(tmp_path, spec):
    import jax
    from jax.profiler import ProfileData

    run_cell(spec, spec.cells()[0])          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        row = run_cell(spec, spec.cells()[0])
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(Path(tmp_path).rglob("*.xplane.pb"))[0]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(str(xplane)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("snow.")]
    return row, events


@pytest.mark.parametrize("scene", ["breakdown", "stable"])
def test_a_query_writes_its_spans_inside_its_query_span(
        tmp_path, scene):
    spec = _spec(scene)
    row, events = _program_events(tmp_path, spec)
    assert row["engine_used"] == "device"
    names = [e[0] for e in events]
    for name in ARGS:
        want = names.count(name) >= 1 if name in REPEATED \
            else names.count(name) == 1
        assert want, (name, names)
    assert set(names) == set(ARGS)
    (_, q0, q1, qargs), = [e for e in events if e[0] == "snow.query"]
    assert qargs == {"scene": scene, "protocol": spec.protocols[0],
                     "n": 600, "k": 4, "seeds": 3}
    for name, start, end, args in events:
        assert q0 <= start <= end <= q1, name
        assert set(args) == ARGS[name], (name, args)
    by_name = {e[0]: e[3] for e in events}
    trace = _trace_for(spec, spec.cells()[0])
    events = 0 if trace is None else len(trace.events)
    assert by_name["snow.plan.trace"]["events"] == events
    assert (events > 0) is (scene == "breakdown")
    assert by_name["snow.plan.trees"]["full"] >= 1
    assert by_name["snow.plan.trees"]["epochs"] >= by_name[
        "snow.plan.trees"]["full"]
    assert by_name["snow.device.upload"]["bytes"] > 0
    dispatch = by_name["snow.device.dispatch"]
    assert dispatch["program"] == (
        "_trace_ldt" if scene == "breakdown" else "_stable_stats")
    # the node-major sweep's row: 3 seeds × the messages of an epoch
    # (all 6 in a stable sweep), padded to 128 lanes
    assert dispatch["row_width"] == 128
    cols = dispatch["row_fill"] * 128
    assert cols == round(cols) and round(cols) % 3 == 0
    assert (cols == 18) is (scene == "stable")
    assert by_name["snow.sweep"]["engine"] == "device"


@pytest.mark.parametrize("scene", ["breakdown", "stable"])
def test_row_times_come_from_the_spans(scene):
    """``plan_s`` is the planning span's duration on the first row and
    ``wall_s`` the sweep span's share of each seed."""
    from repro.core.churn import paper_breakdown_trace
    from repro.core.engine import stable_sweep, trace_sweep
    from repro.core.specs import RunSpec

    for engine in ("host", "device"):
        run = RunSpec(engine=engine)
        if scene == "stable":
            rows = stable_sweep("snow", 300, 4, [1, 2, 3], n_messages=2,
                                run=run)
        else:
            rows = trace_sweep("snow", paper_breakdown_trace(300, 6, 1.0,
                                                             9, 3),
                               4, [1, 2, 3], run=run)
        assert rows[0]["plan_s"] > 0
        assert [r["plan_s"] for r in rows[1:]] == [0.0, 0.0]
        assert len({r["wall_s"] for r in rows}) == 1
        assert rows[0]["wall_s"] > 0
