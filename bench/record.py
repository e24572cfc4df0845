"""What one run recorded: its requests, the gateway's flushes, compile
events and the trace. Metric readers (`bench/metrics/*.py`) read only this."""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

#: jax.monitoring events whose durations make up compile time
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclass
class Req:
    """One request: when it was due, sent and answered, and what it said.

    Open loop: `due` is its place on the schedule. Closed loop: the moment
    its client sent it. `refused` marks a typed rejection at submit."""

    idx: int
    n: int
    due: float
    sent: float | None = None
    done: float | None = None
    verified: bool = False
    sign: float | None = None
    logabs: float | None = None
    error: str | None = None
    refused: bool = False

    @property
    def answered(self) -> bool:
        return self.done is not None and self.error is None and not self.refused


class CompileLog:
    """Compile seconds reported by JAX, stamped with the host clock."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            with self._lock:
                self.events.append((time.monotonic(), duration))

    def seconds_between(self, t0: float, t1: float) -> float:
        with self._lock:
            return sum(d for t, d in self.events if t0 <= t <= t1)


@dataclass
class Run:
    """Everything a metric reader may look at, for one run of one cell."""

    workload: dict
    config: dict
    mix: dict
    seconds: float
    chips: int
    seed: int = 0
    t0: float = 0.0  # window start, host monotonic seconds
    t1: float = 0.0  # window end
    setup_s: float = 0.0
    requests: list[Req] = field(default_factory=list)
    #: (host time, metrics.FlushEvent) for every gateway flush in the window
    flushes: list = field(default_factory=list)
    compile_log: CompileLog | None = None
    trace: object = None  # tracing.Trace of the window, when traced
    #: when the profiler stopped (a traced run's per-layer readings end there)
    t_traced_end: float | None = None
    device_kind: str = ""

    @property
    def in_window(self) -> list[Req]:
        """Requests due inside the window (the run's attempted work)."""
        return [r for r in self.requests if self.t0 <= r.due < self.t1]

    @property
    def verified_in_window(self) -> list[Req]:
        """Verified answers completed inside the window."""
        return [r for r in self.requests
                if r.answered and r.verified and r.done <= self.t1]

    @property
    def t_end(self) -> float:
        """The end of what a reading covers: the traced part of a traced
        run, else the whole window."""
        return self.t_traced_end or self.t1

    @property
    def window_flushes(self) -> list:
        return [ev for t, ev in self.flushes if self.t0 <= t <= self.t_end]

    @property
    def compile_s(self) -> float:
        """JAX compile seconds from the window's start to `t_end`."""
        return self.compile_log.seconds_between(self.t0, self.t_end)

    def answers_until(self, t: float) -> list[Req]:
        """Verified answers completed from the window's start to `t`."""
        return [r for r in self.requests
                if r.answered and r.verified and self.t0 <= r.done <= t]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty sequence."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))]
