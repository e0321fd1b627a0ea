"""From a JAX profiler trace to the numbers the per-layer readers take.

:func:`collect` keeps, from the ``.xplane.pb`` that
``jax.profiler.stop_trace`` writes, only what the readers use: every
device plane's ``XLA Ops`` and ``XLA Modules`` events, and the host
spans the harness writes (names that start with ``bench.``).  The result
is plain JSON, so a small recorded trace can be kept with the tests.

:class:`TraceView` reduces it.  Times are nanoseconds on the trace's own
clock, on which the profiler puts host and device events together.  The
window is the harness's ``bench.window`` span.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: spans that hold a whole window or a whole unit of work
OUTER_SPANS = (WINDOW_SPAN, "bench.query", "bench.rollout")

Interval = Tuple[float, float]


def short(name: str) -> str:
    """An op's name without the HLO text the TPU trace appends:
    ``%fusion.222 = f32[...] fusion(...)`` becomes ``%fusion.222``."""
    return name.split(" = ", 1)[0]


def collect(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    out = {"host": [], "devices": {}}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[short(e.name), e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if e.name.startswith(SPAN_PREFIX))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


class TraceView:
    """The collected trace of one traced window.

    ``units`` is the number of queries or rollouts the window ran, so a
    reader can give a per-unit number."""

    def __init__(self, events: dict, units: int):
        self.events = events
        self.units = units
        spans = [s for s in events["host"] if s[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError("the trace holds no bench.window span")
        _, start, dur = max(spans, key=lambda s: s[2])
        self.window: Interval = (float(start), float(start) + float(dur))
        self.devices = sorted(events["devices"])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, rows) -> List[Interval]:
        lo, hi = self.window
        out = []
        for _, s, d in rows:
            a, b = max(float(s), lo), min(float(s) + float(d), hi)
            if b > a:
                out.append((a, b))
        return out

    def busy_intervals(self, dev: str) -> List[Interval]:
        return union(self._clip(self.events["devices"][dev]["ops"]))

    def busy_s(self, dev: str) -> float:
        return sum(b - a for a, b in self.busy_intervals(dev)) * 1e-9

    def busiest(self) -> Optional[str]:
        busy = {d: self.busy_s(d) for d in self.devices}
        if not busy or max(busy.values()) <= 0:
            return None
        return max(busy, key=busy.get)

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self, dev: Optional[str] = None) -> Optional[float]:
        """1 − busy/window on ``dev`` (default: the busiest device), or
        None where no device ran an operation."""
        dev = dev or self.busiest()
        if dev is None:
            return None
        return 1.0 - self.busy_s(dev) / self.window_s

    def _seconds(self, kind: str, pattern: str, dev: str) -> float:
        rx = re.compile(pattern)
        return sum(b - a for a, b in union(self._clip(
            r for r in self.events["devices"][dev][kind]
            if rx.search(r[0])))) * 1e-9

    def op_seconds(self, pattern: str) -> List[float]:
        """Per device: seconds in which an op matching ``pattern`` ran."""
        return [self._seconds("ops", pattern, d) for d in self.devices]

    def module_seconds(self, pattern: str) -> List[float]:
        """Per device: seconds in which a program matching ``pattern``
        ran."""
        return [self._seconds("modules", pattern, d) for d in self.devices]

    def host_seconds(self, pattern: str) -> float:
        """Seconds inside host spans matching ``pattern`` (nested spans
        of one name counted once)."""
        rx = re.compile(pattern)
        return sum(b - a for a, b in union(self._clip(
            s for s in self.events["host"] if rx.search(s[0])))) * 1e-9

    # -- the breakdown the result line carries -------------------------
    def top_ops(self, limit: int = 10) -> List[List]:
        """Device ops with the most time, seconds averaged over the
        devices (an op that encloses others, such as a loop, counts
        its whole span)."""
        tot: Dict[str, float] = {}
        for dev in self.devices:
            lo, hi = self.window
            for name, s, d in self.events["devices"][dev]["ops"]:
                a, b = max(float(s), lo), min(float(s) + float(d), hi)
                if b > a:
                    tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
        n = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, sec / n] for name, sec in top]

    def idle_gaps(self, limit: int = 10) -> List[List]:
        """The longest gaps on the busiest device, each labelled by what the
        host was doing: the inner harness span (``bench.plan.*``,
        ``bench.device.*``, ...) that covers most of the gap where one
        covers half of it or more; else the unit span around it (host
        work outside the named entry points), with the inner span that
        covers most of the rest and its share."""
        dev = self.busiest()
        if dev is None:
            return []
        busy = self.busy_intervals(dev)
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = [(float(s), float(s) + float(d), name)
                 for name, s, d in self.events["host"]]
        out = []
        for a, b in gaps[:limit]:
            def cover(sp):
                return max(0.0, min(b, sp[1]) - max(a, sp[0]))

            inner = [sp for sp in spans if sp[2] not in OUTER_SPANS]
            best = max(inner, key=cover, default=None)
            share = cover(best) / (b - a) if best is not None else 0.0
            if share >= 0.5:
                label = best[2]
            else:
                mid = 0.5 * (a + b)
                around = [sp for sp in spans
                          if sp[2] in OUTER_SPANS and sp[0] <= mid <= sp[1]]
                label = min(around, key=lambda sp: sp[1] - sp[0])[2] \
                    if around else "outside bench spans"
                if share > 0:
                    label += f" ({best[2]} {share:.0%})"
            out.append([label, (b - a) * 1e-9])
        return out
