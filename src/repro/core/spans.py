"""Named host spans of the coadd engine.

``with span("execute.sync") as s: ...`` enters a
``jax.profiler.TraceAnnotation`` named ``"coadd.execute.sync"``: inside a
``jax.profiler`` trace it is a host event on the same clock as the
device's events, with its arguments as event stats.  Outside a trace it
costs about a microsecond.  Either way the span keeps its own
``perf_counter`` reading, ``s.seconds`` after exit, which is where
`JobStats`' timings come from.
"""

from __future__ import annotations

import time

import jax

PREFIX = "coadd."


class span:
    """A timed, traced region; ``set(**args)`` attaches arguments (such as
    byte counts known only once the work is done) after entry."""

    __slots__ = ("_trace", "_t0", "seconds")

    def __init__(self, name: str, **args):
        self._trace = jax.profiler.TraceAnnotation(PREFIX + name, **args)
        self.seconds = 0.0

    def set(self, **args) -> None:
        self._trace.set_metadata(**args)

    def __enter__(self) -> "span":
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
