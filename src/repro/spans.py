"""Program spans: one per protocol phase and gateway step (DESIGN.md §10.5).

`span(name)` is a context manager. It opens a
`jax.profiler.TraceAnnotation` (a TraceMe on the profiler's host plane,
on the same clock as the device events of a trace), reads
`time.perf_counter()` on entry and exit, and exposes the elapsed time as
`.seconds` — the number `SessionTimings` reports.

`wait=` makes the span end when the phase's device work ends rather than
when its dispatch returns: a zero-argument callable returning the
phase's outputs (any pytree of arrays), blocked on with
`jax.block_until_ready` before the span closes. It blocks whether or
not a profiler is running, so a traced run runs the program it measures.

Spans are siblings: none is opened inside another, so a gap in a trace
reads the one phase that covered it. Never open one inside a jitted
function; the TraceMe would fire once, at trace time.
"""
from __future__ import annotations

import time
from collections.abc import Callable
from typing import Any

import jax

__all__ = ["span"]


class span:
    """`with span(name[, wait=...]) as s:` — then `s.seconds`."""

    __slots__ = ("name", "seconds", "_wait", "_trace", "_t0")

    def __init__(self, name: str, wait: Callable[[], Any] | None = None):
        self.name = name
        self.seconds = 0.0
        self._wait = wait

    def __enter__(self) -> "span":
        self._trace = jax.profiler.TraceAnnotation(self.name)
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if exc_type is None and self._wait is not None:
                jax.block_until_ready(self._wait())
        finally:
            self.seconds = time.perf_counter() - self._t0
            self._trace.__exit__(exc_type, exc, tb)

