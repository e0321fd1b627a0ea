"""The program's own spans and the device programs' named scopes, read
from a JAX profiler trace beside what :mod:`snowbench.trace` keeps.

``repro.core.spans`` writes host spans named ``snow.*`` (``snow.query``
around each ``run_cell``, ``snow.plan.*``, ``snow.control``,
``snow.device.*``, ...) and the sweep programs carry four named scopes
(``level_sweep``, ``delay_planes``, ``epoch_gather``, ``ldt_reduce``).
:func:`collect` gives :func:`snowbench.trace.collect`'s dict with two
additions: ``program``, the ``snow.*`` spans as ``[name, start_ns,
dur_ns, {args}]``, and each device's ``scopes``, ``[scope, start_ns,
dur_ns]`` for every op that carries a scope.  :class:`ProgramView`
reduces it, and the functions at the end give the per-query numbers.

The harness does not call this module: it reads traces through
``snowbench.trace.collect`` alone, so these numbers reach a result line
only once the benchmark's own files take them up.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import DEVICE_PLANE, Interval, TraceView, union

PROGRAM_PREFIX = "snow."
#: an op's name stack holds the scopes as path components, under a
#: transform as in ``vmap(delay_planes)``; the innermost one counts
SCOPE = re.compile(r"\b(level_sweep|delay_planes|epoch_gather|ldt_reduce)\b")
#: the stat of a device op's event metadata that holds its HLO
#: ``op_name``: the TPU profiler writes it there, not on each event
SCOPE_STAT = "tf_op"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, start: int, end: int):
    """The fields of one protobuf message in ``buf[start:end]``: (number,
    value), a length-delimited value as the (start, end) of its bytes."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def op_scopes(buf: bytes) -> Dict[str, str]:
    """{op event name: innermost named scope} of the device planes of a
    serialized ``XSpace``, from the ``tf_op`` stat of each op's event
    metadata.  ``jax.profiler.ProfileData`` shows an event's own stats
    only, so the few fields needed are read here: XSpace.planes (1);
    XPlane.name (2), event_metadata (4) and stat_metadata (5), maps
    whose entries hold the value in field 2; XEventMetadata.name (2) and
    stats (5); XStat.metadata_id (1), str_value (5) and ref_value (7);
    XStatMetadata.id (1) and name (2)."""
    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def value(entry):
        return next(v for f, v in _fields(buf, *entry) if f == 2)

    out: Dict[str, str] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((text(v) for g, v in fields if g == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                md = dict(_fields(buf, *value(entry)))
                stat_names[md.get(1, 0)] = text(md[2]) if 2 in md else ""
        for g, entry in fields:
            if g != 4:
                continue
            op, stack = None, None
            for h, v in _fields(buf, *value(entry)):
                if h == 2:
                    op = text(v)
                elif h == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == SCOPE_STAT:
                        stack = text(stat[5]) if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            found = SCOPE.findall(stack or "")
            if op is not None and found:
                out[op] = found[-1]
    return out


def from_planes(planes, scopes: Dict[str, str]) -> dict:
    """The additions to :func:`snowbench.trace.collect`'s dict, from
    planes already read (``ProfileData.planes``, or objects with the
    same attributes) and each op's scope by its event name:
    ``{"program": [...], "scopes": {device plane: [...]}}``."""
    out = {"program": [], "scopes": {}}
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            out["scopes"][plane.name] = [
                [scopes[e.name], e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events if e.name in scopes]
        elif plane.name.startswith("/host:"):
            out["program"].extend(
                [e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                for line in plane.lines for e in line.events
                if e.name.startswith(PROGRAM_PREFIX))
    return out


def merge(events: dict, extra: dict) -> dict:
    """``events`` (from :func:`snowbench.trace.collect`) with the
    program's spans and each device's ``scopes`` added."""
    out = dict(events, program=extra["program"])
    out["devices"] = {d: dict(dev, scopes=extra["scopes"].get(d, []))
                      for d, dev in events["devices"].items()}
    return out


def collect(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    from .trace import collect as collect_harness

    path = str(xplane_path)
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    extra = from_planes(ProfileData.from_file(path).planes, scopes)
    return merge(collect_harness(path), extra)


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """The parts common to two unions (sorted, disjoint intervals)."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class ProgramView(TraceView):
    """A :class:`~snowbench.trace.TraceView` that also reads the
    program's spans and the devices' scoped ops, where the events hold
    them."""

    def scope_seconds(self, pattern: str) -> List[float]:
        """Per device: seconds in which an op of a named scope matching
        ``pattern`` ran."""
        rx = re.compile(pattern)
        return [sum(b - a for a, b in union(self._clip(
            r for r in self.events["devices"][d].get("scopes", ())
            if rx.search(r[0])))) * 1e-9 for d in self.devices]

    def program_seconds(self, pattern: str,
                        within: Optional[str] = None) -> float:
        """Seconds inside the program's spans matching ``pattern``
        (overlaps counted once); with ``within``, only the part inside
        harness spans matching it."""
        rx = re.compile(pattern)
        spans = union(self._clip(
            s[:3] for s in self.events.get("program", ())
            if rx.search(s[0])))
        if within is not None:
            rw = re.compile(within)
            spans = intersect(spans, union(self._clip(
                s for s in self.events["host"] if rw.search(s[0]))))
        return sum(b - a for a, b in spans) * 1e-9


# -- per query, ms; None where the trace holds nothing for the number ----
def _scope_ms(view: ProgramView, scope: str) -> Optional[float]:
    per_chip = view.scope_seconds(f"^{scope}$")
    if not per_chip or max(per_chip) <= 0 or view.units == 0:
        return None
    return max(per_chip) / view.units * 1e3


def level_sweep_ms(view: ProgramView) -> Optional[float]:
    """Device time of the ``level_sweep`` scope (``level_sweep_xla``,
    ``fwd_at_parent``)."""
    return _scope_ms(view, "level_sweep")


def delay_planes_ms(view: ProgramView) -> Optional[float]:
    """Device time of the ``delay_planes`` scope (the threefry draws)."""
    return _scope_ms(view, "delay_planes")


def epoch_gather_ms(view: ProgramView) -> Optional[float]:
    """Device time of the ``epoch_gather`` scope (``_trace_ldt``'s
    selection of each epoch's window out of the delay planes)."""
    return _scope_ms(view, "epoch_gather")


def control_ms(view: ProgramView) -> Optional[float]:
    """Host time of the ``snow.control`` spans."""
    sec = view.program_seconds(r"^snow\.control$")
    if sec <= 0 or view.units == 0:
        return None
    return sec / view.units * 1e3


def host_unnamed_ms(view: ProgramView) -> Optional[float]:
    """Host time inside the harness's ``bench.query`` spans that none of
    the program's spans but ``snow.query`` covers."""
    if view.units == 0 or view.program_seconds(r"^snow\.query$") <= 0:
        return None
    named = view.program_seconds(r"^snow\.(?!query$)",
                                 within=r"^bench\.query$")
    return (view.host_seconds(r"^bench\.query$") - named) / view.units * 1e3
