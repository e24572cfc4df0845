"""Runs one cell of `BENCHMARK.json` once and builds its result line.

Everything a cell needs is found by name: its configuration file (which
names a driver in `bench/drivers/`), its traffic mix in `bench/traffic/`,
its limits in `bench/limits/`, and one reader per metric in
`bench/metrics/<metric>.py`. A later cell or metric adds files and
entries; this module does not change.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

from bench import reference, tracing
from bench.record import CompileLog, Run
from bench.traffic import load as load_mix

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SRC = ROOT / "src"
#: where a traced run writes its profile (removed once it is read)
TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """(workload entry, configuration file, traffic mix, limits) of a cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    wl = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    return wl, cfg, load_mix(wl["traffic"]), reference.limits(workload)


def metric_module(name: str):
    """The reader `bench/metrics/<name>.py` (names may hold dots)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def metrics_for(spec: dict, workload: str, traced: bool) -> list[dict]:
    """The metric entries a run of `workload` reports: its end-to-end
    metrics untraced, its per-layer metrics traced."""
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def driver_class(cfg: dict):
    return importlib.import_module(f"bench.drivers.{cfg['driver']}").Driver


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Window:
    """The measured window as the drivers see it: `open()` stamps set-up
    time and starts the profiler on a traced run; the profiler stops at
    `trace_deadline` (drivers call `stop_trace()` once they pass it) or at
    `close()`, whichever comes first."""

    def __init__(self, run: Run, t_process: float, trace_dir: Path | None,
                 trace_seconds: float):
        self.run, self.t_process = run, t_process
        self.trace_dir, self.trace_seconds = trace_dir, trace_seconds
        self.session = None
        self.trace_deadline: float | None = None
        self.t_traced_end: float | None = None

    def open(self) -> float:
        if self.trace_dir is not None:
            self.session = tracing.Session(self.trace_dir)
        t0 = time.monotonic()
        self.run.setup_s = t0 - self.t_process
        if self.session is not None:
            self.trace_deadline = t0 + self.trace_seconds
        return t0

    def stop_trace(self) -> None:
        if self.session is not None and self.t_traced_end is None:
            self.t_traced_end = time.monotonic()
            self.session.stop()

    def close(self) -> None:
        self.stop_trace()


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_process: float, spec: dict | None = None,
             require_tpu: bool = True, cell: tuple | None = None,
             keep_trace: Path | None = None) -> dict:
    """Run one cell once; returns the result line as a dict (with the
    compared numbers under "checks") plus the Run under "_run".

    `cell` overrides (workload entry, configuration, mix, limits), for
    tests that drive a small cell that BENCHMARK.json does not list. `keep_trace`
    writes a traced run's reduced Trace there (gzipped JSON).
    """
    spec = spec or load_spec()
    wl, cfg, mix, limits = cell or resolve(spec, workload)
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.runtime import init_process

    init_process(x64=False)
    # keep every program in the persistent cache, the sub-second ones too,
    # so that only a checkout's first run of a cell compiles its warm-up
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = _devices(int(wl["chips"]), require_tpu)
    used = devices[: int(wl["chips"])]
    compile_log = CompileLog()
    run = Run(workload=wl, config=cfg, mix=mix, seconds=seconds,
              chips=int(wl["chips"]), seed=seed,
              device_kind=devices[0].device_kind)
    driver = driver_class(cfg)(cfg, mix, seed, seconds, run.chips)
    driver.setup()

    window = Window(run, t_process, TRACE_DIR if traced else None,
                    float(mix.get("trace_seconds", seconds)))
    run.t0, run.t1 = driver.measure(window)
    window.close()
    run.t_traced_end = window.t_traced_end
    run.requests, run.flushes = driver.requests, driver.flushes
    run.compile_log = compile_log
    memory_peak = _memory_peak(used)
    driver.close()
    if window.session is not None:
        run.trace = window.session.load(len(used))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if not run.trace.devices:
            if require_tpu:
                raise RuntimeError("the trace holds no TPU device events")
            run.trace = None  # a rehearsal: the CPU has no device plane
        elif keep_trace is not None:
            run.trace.write(keep_trace)

    numbers = reference.compare(run, driver.input, limits,
                                mix.get("check", {}).get("sample"))
    metrics = {}
    for entry in metrics_for(spec, wl["name"], traced):
        value = metric_module(entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    attempted = len(run.in_window)
    failed = sum(1 for r in run.in_window
                 if r.refused or not r.answered or not r.verified)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": reference.is_correct(numbers), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    line["_run"] = run
    return line


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = time.monotonic() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="write a traced run's reduced trace here (.json.gz)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU; prints counts and checks only, "
                         "never a metric, and exits 3")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"no program to measure: {SRC / 'repro'} is missing", file=sys.stderr)
        return 2
    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                        t_process=t_process, require_tpu=not args.rehearse,
                        keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    run = line.pop("_run")
    checks = line["checks"]
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    if args.rehearse:
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "device": {k: line["device"][k] for k in ("platform", "kind", "count")},
                "counts": {"answers": len(run.verified_in_window),
                           "flushes": len(run.window_flushes)},
                "checks": checks}
        print(json.dumps(line), flush=True)
        return 3
    print(json.dumps(line), flush=True)
    return 0
