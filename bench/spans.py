"""Seconds of the program's named host spans (`repro.spans`) in a trace.

The program opens one span per protocol phase (`spdc.pmop`, `spdc.sweep`,
`spdc.verify`, `spdc.decipher`) and per gateway step
(`spdc.gateway.submit`, `.pack`, `.deliver`); they are TraceMe events on
the profiler's host plane, on the device trace's clock. A checkout whose
program has no such spans reads None, never 0.
"""
from __future__ import annotations

from bench.tracing import clip, length, union


def span_seconds(trace, names, *, merged: bool = False) -> float | None:
    """Seconds of the host spans named one of `names` inside the trace's
    window, each clipped to it: their lengths summed, or with `merged`
    the length of their union. None when no such span lies in the window."""
    names = {names} if isinstance(names, str) else set(names)
    spans = clip([(s, e) for s, e, name in trace.host if name in names],
                 *trace.window)
    if not spans:
        return None
    return length(union(spans) if merged else spans) / 1e9
