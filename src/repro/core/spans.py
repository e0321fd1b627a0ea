"""Named host spans on the profiler's clock.

``with span("snow.plan.trees", epochs=3) as s: ...`` writes one event
into the JAX profiler's trace while a trace is being taken, on the same
clock as the device's ``XLA Ops``, and leaves the step's host duration
in ``s.seconds`` after exit either way.  Args given to :func:`span` or
to :meth:`span.set` travel with the event.  Spans mark step
boundaries only, never the inside of a loop over nodes, messages or
seeds; with no trace running one costs about a microsecond.

JAX is never imported here: where no module has imported it, no
profiler can be running, so the span only keeps time and the host
engines stay free of JAX.
"""
from __future__ import annotations

import sys
import time


class span:
    """Context manager for one named host step; ``seconds`` after exit."""

    __slots__ = ("_mark", "_t0", "seconds")

    def __init__(self, name: str, **args):
        jax = sys.modules.get("jax")
        self._mark = None if jax is None \
            else jax.profiler.TraceAnnotation(name, **args)
        self.seconds = 0.0

    def set(self, **args) -> None:
        """Args known only inside the step, such as a count it made."""
        if self._mark is not None:
            self._mark.set_metadata(**args)

    def __enter__(self) -> "span":
        if self._mark is not None:
            self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._mark is not None:
            self._mark.__exit__(*exc)
