"""The profiler trace of a run's window, and its reduction to numbers.

`Session` records the window with JAX's profiler (the Python tracer off,
so the trace holds device events and host TraceMe spans only) and `load`
turns the `.xplane.pb` into a `Trace`: per device the intervals of its
XLA ops and XLA modules, and the host's spans, on one clock. A `Trace`
round-trips through JSON, which is how `bench/testdata/` keeps recorded
traces for the tests.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import shutil
import threading
from dataclasses import dataclass, field
from pathlib import Path

#: host spans that mark where the traced window opens and closes
OPEN_SPAN, CLOSE_SPAN = "bench.window.open", "bench.window.close"
#: op kinds that move data between chips
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather", "all-to-all",
               "reduce-scatter", "send", "recv")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w.\-]*?)(?:\.\d+)?(?: =|$)")
_MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_kind(event_name: str) -> str:
    """'%collective-permute-start.3 = (...)' -> 'collective-permute-start'."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def module_name(event_name: str) -> str:
    """'jit__lu_sweep(1675440604842785)' -> 'jit__lu_sweep'."""
    return _MODULE_NAME.match(event_name).group(1)


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """The parts of merged intervals `a` that merged intervals `b` miss."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Device:
    ops: list = field(default_factory=list)  # (start_ns, end_ns, name)
    async_ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    window: tuple[int, int]
    devices: list[Device]
    host: list = field(default_factory=list)  # (start_ns, end_ns, name)

    # -- the numbers ---------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, d: Device) -> list[tuple[int, int]]:
        return clip(union((s, e) for s, e, _ in d.ops), *self.window)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(length(self.busy(d)) for d in self.devices) / len(self.devices) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_s(self, name: str) -> float:
        """Device seconds of XLA module `name`, summed over the devices."""
        lo, hi = self.window
        return sum(length(clip([(s, e)], lo, hi)) for d in self.devices
                   for s, e, m in d.modules if module_name(m) == name) / 1e9

    def collective_s(self) -> tuple[float, float]:
        """(seconds of collective ops, the part of them during which no
        other op ran), each averaged over the devices."""
        total = exposed = 0
        for d in self.devices:
            coll, other = [], []
            for s, e, name in d.ops + d.async_ops:
                (coll if op_kind(name).startswith(COLLECTIVES) else other).append((s, e))
            coll = clip(union(coll), *self.window)
            other = clip(union(other), *self.window)
            total += length(coll)
            exposed += length(subtract(coll, other))
        n = len(self.devices)
        return total / n / 1e9, exposed / n / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by module/op kind, averaged
        over the devices) and the longest idle gaps, each named by the host
        span that covered most of it."""
        lo, hi = self.window
        per_op: dict[str, int] = {}
        for d in self.devices:
            mods = sorted((s, e, module_name(m)) for s, e, m in d.modules)
            starts = [m[0] for m in mods]
            for s, e, name in d.ops:
                c = clip([(s, e)], lo, hi)
                if not c:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
                key = f"{mod}/{op_kind(name)}"
                per_op[key] = per_op.get(key, 0) + length(c)
        n = len(self.devices)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        # the longest idle gaps, on the first chip
        busy = self.busy(self.devices[0])
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])[:top]
        host = sorted(self.host)
        idle = []
        for s, e in gaps:
            best, best_overlap = "idle", 0
            for hs, he, name in host:
                if hs >= e:
                    break
                ov = min(he, e) - max(hs, s)
                if ov > best_overlap:
                    best, best_overlap = name, ov
            idle.append([best, (e - s) / 1e9])
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops], "idle_gaps": idle}

    # -- storage ---------------------------------------------------------------

    def to_json(self) -> dict:
        return {"window": list(self.window), "host": self.host,
                "devices": [{"ops": d.ops, "async_ops": d.async_ops,
                             "modules": d.modules} for d in self.devices]}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        devs = [Device(*(list(map(tuple, d[k])) for k in ("ops", "async_ops", "modules")))
                for d in obj["devices"]]
        return cls(tuple(obj["window"]), devs, list(map(tuple, obj["host"])))

    def write(self, path: Path) -> None:
        path.parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def read(cls, path: Path) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def from_xplane(path: Path, chips: int) -> Trace:
    """Reduce an `.xplane.pb` to a Trace of the first `chips` TPU devices."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict[int, Device] = {}
    host = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                target = {"XLA Ops": d.ops, "Async XLA Ops": d.async_ops,
                          "XLA Modules": d.modules}.get(line.name)
                if target is not None:
                    target.extend((int(e.start_ns), int(e.end_ns), e.name)
                                  for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events)
    ids = sorted(devices)[:chips]
    opened = [s for s, _, name in host if name == OPEN_SPAN]
    closed = [e for _, e, name in host if name == CLOSE_SPAN]
    if not (opened and closed):
        raise ValueError(f"{path} lacks the {OPEN_SPAN!r} or {CLOSE_SPAN!r} span")
    host = [h for h in host if h[1] > h[0] and h[2] not in (OPEN_SPAN, CLOSE_SPAN)]
    return Trace((min(opened), max(closed)), [devices[i] for i in ids], host)


class Session:
    """The profiler, on from construction until `stop()` (idempotent)."""

    def __init__(self, log_dir: Path):
        import jax

        shutil.rmtree(log_dir, ignore_errors=True)
        self.log_dir = log_dir
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(log_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation(OPEN_SPAN):
            pass
        self._lock = threading.Lock()
        self._on = True

    def stop(self) -> None:
        import jax

        with self._lock:
            if self._on:
                self._on = False
                with jax.profiler.TraceAnnotation(CLOSE_SPAN):
                    pass
                jax.profiler.stop_trace()

    def load(self, chips: int) -> Trace:
        self.stop()
        found = sorted(self.log_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace under {self.log_dir}")
        return from_xplane(found[-1], chips)
