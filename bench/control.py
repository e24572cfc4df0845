"""Readings that set a cell's limits: the program's on many seeds, then the
control's on a few, in one process (the programs compile once).

    python3 bench/control.py --workload <name> --seeds 1,2,... \
        --control-seeds 101,102,103 --seconds <s> [--out readings.json]

The control is the program with its own precision switched one step
down: every product on the LU, relay and verify paths goes from
Precision.HIGHEST to Precision.HIGH (`repro.core.lu.PRECISION`, the one
constant that sets them). Each reading is the run's compared numbers
(`bench/reference.py`); the limits in `bench/limits/<cell>.json` are set
between the program's largest and the control's smallest. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from bench import harness  # noqa: E402


def precision_probe() -> dict:
    """Relative error of one float32 product on this device at each
    precision, against float64 on the host: what the switch changes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a = rng.standard_normal((8, 512, 512), dtype=np.float32)
    b = rng.standard_normal((8, 512, 512), dtype=np.float32)
    ref = np.einsum("bij,bjk->bik", a.astype(np.float64), b.astype(np.float64))
    out = {}
    for p in ("DEFAULT", "HIGH", "HIGHEST"):
        prec = getattr(jax.lax.Precision, p)
        got = np.asarray(jnp.matmul(jnp.asarray(a), jnp.asarray(b), precision=prec),
                         dtype=np.float64)
        out[p] = float(np.abs(got - ref).max() / np.abs(ref).max())
    return out


def readings(workload: str, seeds, seconds: float) -> list[dict]:
    out = []
    for seed in seeds:
        t = time.monotonic()
        line = harness.run_cell(workload, seed, seconds, False, t_process=t)
        run = line.pop("_run")
        out.append({"seed": seed, "correct": line["correct"],
                    "answers": len(run.verified_in_window),
                    **{k: v["value"] for k, v in line["checks"].items()}})
        print(json.dumps(out[-1]), flush=True)
    return out


def use_lower_precision() -> None:
    """Switch the program's products from HIGHEST to HIGH and drop every
    program compiled at HIGHEST."""
    import jax

    import repro.core.lu as lu
    from repro.distrib import spdc_pipeline

    lu.PRECISION = jax.lax.Precision.HIGH
    spdc_pipeline._compiled_pipeline.cache_clear()
    jax.clear_caches()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    result = {"workload": args.workload,
              "program": readings(args.workload, seeds, args.seconds)}
    result["precision_probe"] = precision_probe()
    use_lower_precision()
    result["control"] = readings(args.workload, control_seeds, args.seconds)
    print(json.dumps({"precision_probe": result["precision_probe"]}), flush=True)
    if args.out is not None:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
