"""The program's spans and the device programs' named scopes as
:mod:`snowbench.program` reads them: on a hand-made trace whose answers
are known, on a hand-encoded serialized ``XSpace``, and on one query of
each sweep cell recorded on a TPU v5e with the spans and scopes in the
program (``bench/tests/data/*.program.trace.json``)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import tinybench
from snowbench import program
from snowbench.manifest import Bench
from snowbench.program import ProgramView, from_planes, merge, op_scopes
from snowbench.trace import TraceView

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
#: per query a plan, a control step and a device step; one control span
#: after the window's end
HAND = {
    "host": [["bench.window", 0, 1000], ["bench.query", 0, 500],
             ["bench.query", 500, 500], ["bench.plan.stable_plans", 10, 100],
             ["bench.plan.compile_trace", 600, 200]],
    "devices": {DEV: {
        "ops": [["fusion.1", 200, 100], ["fusion.2", 250, 100],
                ["collective-permute-start.3", 800, 50],
                ["fusion.9", 990, 40]],
        "modules": [["jit__stable_stats", 200, 150],
                    ["jit_other", 800, 50]],
        "scopes": [["level_sweep", 200, 100], ["level_sweep", 250, 100],
                   ["delay_planes", 800, 50], ["epoch_gather", 990, 40]]}},
    "program": [
        ["snow.query", 5, 490, {"scene": "breakdown"}],
        ["snow.plan.trees", 10, 100, {"epochs": 1}],
        ["snow.control", 120, 30, {}],
        ["snow.device.dispatch", 195, 160, {"program": "_trace_ldt"}],
        ["snow.query", 505, 490, {}],
        ["snow.plan.trees", 600, 200, {}],
        ["snow.control", 810, 40, {}],
        ["snow.rows", 840, 20, {}],
        ["snow.control", 1100, 50, {}]],
}
NUMBERS = ("level_sweep_ms", "delay_planes_ms", "epoch_gather_ms",
           "control_ms", "host_unnamed_ms")
SCOPES = r"^(level_sweep|delay_planes|epoch_gather|ldt_reduce)$"
SPANS = {"snow.query", "snow.plan.trace", "snow.trace.scan",
         "snow.plan.trees", "snow.control", "snow.rows", "snow.sweep",
         "snow.device.pack", "snow.device.upload", "snow.device.dispatch",
         "snow.device.pull"}


def _number(name, view):
    return getattr(program, name)(view)


def test_numbers_of_a_hand_made_trace():
    v = ProgramView(HAND, units=2)
    # union of the level_sweep ops [200, 350]; the epoch_gather op
    # clipped to the window's end at 1000
    assert _number("level_sweep_ms", v) == pytest.approx(150e-9 / 2 * 1e3)
    assert _number("delay_planes_ms", v) == pytest.approx(50e-9 / 2 * 1e3)
    assert _number("epoch_gather_ms", v) == pytest.approx(10e-9 / 2 * 1e3)
    # the control span past the window's end is left out
    assert _number("control_ms", v) == pytest.approx(70e-9 / 2 * 1e3)
    # named inside the queries: 100 + 30 + 160 + 200 + (810..860) 50
    assert _number("host_unnamed_ms", v) == pytest.approx(
        (1000 - 540) * 1e-9 / 2 * 1e3)
    assert v.scope_seconds(r"^(level_sweep|delay_planes)$") == [
        pytest.approx(200e-9)]
    assert v.program_seconds(r"^snow\.") == pytest.approx(980e-9)
    assert v.program_seconds(r"^snow\.plan\.", within=r"^bench\.plan\.") \
        == pytest.approx(300e-9)


@pytest.mark.parametrize("trace", ["empty", "harness spans only",
                                   "a query that names nothing"])
def test_numbers_are_none_where_the_trace_holds_nothing(trace):
    """A program without the spans and scopes (the parent's) gives no
    number and raises nothing."""
    if trace == "empty":
        events = {"host": [["bench.window", 0, 1000]], "devices": {}}
    elif trace == "harness spans only":
        events = {k: v for k, v in HAND.items() if k != "program"}
        events["devices"] = {DEV: {k: v for k, v in HAND["devices"][
            DEV].items() if k != "scopes"}}
    else:
        events = dict(HAND, program=[["snow.query", 5, 490, {}]])
        events["devices"] = {DEV: dict(HAND["devices"][DEV], scopes=[])}
    v = ProgramView(events, units=2)
    for name in NUMBERS[:4]:
        assert _number(name, v) is None, name
    unnamed = _number("host_unnamed_ms", v)
    if trace == "a query that names nothing":
        assert unnamed == pytest.approx(1000e-9 / 2 * 1e3)
    else:
        assert unnamed is None


def _event(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats.items()))


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def test_from_planes_keeps_program_spans_and_scoped_ops():
    planes = [
        _plane("/device:TPU:0", XLA_Ops=[
            _event("%while.10 = f32[5] while(%x)", 10, 50),
            _event("%fusion.3 = f32[5] fusion(%y)", 12, 5),
            _event("%copy.5 = f32[5] copy(%z)", 30, 2)],
            XLA_Modules=[_event("%fusion.3 = f32[5] fusion(%y)", 8, 60)]),
        _plane("/host:CPU", python3=[
            _event("bench.query", 0, 100),
            _event("snow.query", 1, 98, scene="breakdown", n=5),
            _event("snow.device.upload", 2, 3, bytes=40),
            _event("jax_profiler", 4, 1)]),
        _plane("/device:CPU:0", XLA_Ops=[_event("%fusion.1", 0, 1)]),
    ]
    scopes = {"%while.10 = f32[5] while(%x)": "level_sweep",
              "%fusion.3 = f32[5] fusion(%y)": "delay_planes",
              "%fusion.1": "ldt_reduce"}
    got = from_planes(planes, scopes)
    assert got == {
        "program": [["snow.query", 1, 98, {"scene": "breakdown", "n": 5}],
                    ["snow.device.upload", 2, 3, {"bytes": 40}]],
        "scopes": {"/device:TPU:0": [["level_sweep", 10, 50],
                                     ["delay_planes", 12, 5]]}}
    events = {"host": [["bench.window", 0, 100]],
              "devices": {"/device:TPU:0": {"ops": [], "modules": []}}}
    merged = merge(events, got)
    assert merged["program"] == got["program"]
    assert merged["devices"]["/device:TPU:0"]["scopes"] == got["scopes"][
        "/device:TPU:0"]
    assert "scopes" not in events["devices"]["/device:TPU:0"]
    json.dumps(merged)


def _varint(n):
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num, value):
    return _varint(num << 3) + _varint(value)


def _entry(num, key, value):
    return _msg(num, _int(1, key) + _msg(2, value))


def test_op_scopes_reads_the_tf_op_stat_of_the_event_metadata():
    """A serialized XSpace written by hand: the scope of each device op
    comes from its event metadata's ``tf_op`` stat, given as a string or
    as a reference to an interned one; other planes, stats and fields
    are passed over."""
    stack = "jit(_trace_ldt)/vmap()/while/body/closed_call"
    stats = (_entry(5, 7, _int(1, 7) + _msg(2, "tf_op"))
             + _entry(5, 8, _int(1, 8) + _msg(2, "source"))
             + _entry(5, 9, _int(1, 9)
                      + _msg(2, "jit(f)/vmap(delay_planes)/jit(_uniform)")))

    def op(i, name, *xstats):
        return _entry(4, i, _int(1, i) + _msg(2, name) + _msg(4, "short")
                      + b"".join(_msg(5, x) for x in xstats))

    device = (_int(1, 3) + _msg(2, "/device:TPU:0")
              + _msg(3, _int(1, 1) + _msg(2, "XLA Ops"))
              + stats
              + op(1, "%fusion.1 = f32[2] fusion()",
                   _int(1, 8) + _msg(5, "device_sweep.py:369"),
                   _int(1, 7) + _msg(5, f"{stack}/epoch_gather/gather:"))
              + op(2, "%fusion.2 = f32[2] fusion()",
                   _varint(2 << 3 | 1) + bytes(8),
                   _int(1, 7) + _int(7, 9))
              + op(3, "%fusion.3 = f32[2] fusion()",
                   _int(1, 7) + _msg(5, f"{stack}/copy:"))
              + op(4, "%while.4 = f32[2] while()",
                   _int(1, 7)
                   + _msg(5, f"{stack}/ldt_reduce/level_sweep/while:"))
              + op(5, "%fusion.5 = f32[2] fusion()")
              + _msg(6, _int(1, 8)))
    host = (_msg(2, "/host:CPU")
            + stats + op(1, "%fusion.9 = f32[2] fusion()",
                         _int(1, 7) + _msg(5, f"{stack}/level_sweep/x:")))
    buf = _msg(1, device) + _msg(1, host) + _msg(2, "hostname")
    assert op_scopes(buf) == {
        "%fusion.1 = f32[2] fusion()": "epoch_gather",
        "%fusion.2 = f32[2] fusion()": "delay_planes",
        "%while.4 = f32[2] while()": "level_sweep"}


@pytest.mark.parametrize("name", ["breakdown-1m", "stable-coloring-1m"])
def test_recorded_program_trace(name):
    """One query of each sweep cell recorded on a TPU v5e: each number
    reads where the cell has what it counts, the scopes cover the sweep
    program, the program's spans name all but a sliver of the query and
    agree with the harness's spans around the same calls, and the
    harness's own readers read the trace as before."""
    doc = json.loads((DATA / f"{name}.program.trace.json").read_text())
    v = ProgramView(doc["events"], doc["units"])
    bench = Bench.load(tinybench.REPO)
    sweep_program = bench.reader("sweep_program_ms")(v)
    for number in NUMBERS:
        value = _number(number, v)
        if number == "epoch_gather_ms" and name == "stable-coloring-1m":
            assert value is None     # the stable program has no epochs
        else:
            assert value is not None and value >= 0, number
    covered = max(v.scope_seconds(SCOPES)) * 1e3 / v.units
    assert 0.9 * sweep_program <= covered <= sweep_program
    assert _number("level_sweep_ms", v) > 0.5 * sweep_program
    assert _number("host_unnamed_ms", v) <= 0.05 * v.window_s * 1e3
    assert v.program_seconds(r"^snow\.plan\.") == pytest.approx(
        v.host_seconds(r"^bench\.plan\."), rel=0.05)
    if name == "breakdown-1m":
        assert _number("control_ms", v) == pytest.approx(
            v.host_seconds(r"^bench\.host\.control$") * 1e3, rel=0.02)
    assert {e[0] for e in doc["events"]["program"]} == SPANS
    harness = TraceView(doc["events"], doc["units"])
    for metric in ("host_plan_ms", "sweep_program_ms",
                   "device_idle_share.sweep"):
        assert bench.reader(metric)(harness) == bench.reader(metric)(v)
