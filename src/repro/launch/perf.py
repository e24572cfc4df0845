import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# §Perf hillclimb driver: lower one cell with config overrides, report the
# three roofline terms + deltas vs the recorded baseline. (Same first-lines
# rule as dryrun.py.)
#
#   PYTHONPATH=src python -m repro.launch.perf --arch nemotron-4-340b \
#       --shape train_4k --set attn_probs_bf16=true --set grad_accum=8 \
#       --tag nemotron_bf16probs
#
#   PYTHONPATH=src python -m repro.launch.perf --spdc --exact-relay --tag spdc_exact

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "perf_results"
BASE = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results"


def _coerce(v: str):
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        return v


def run_variant(arch, shape_name, mesh_name, overrides, tag):
    import repro.launch.dryrun as dr
    from repro.configs import get_config
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import analyze

    # monkeypatch the config the dryrun cell will resolve
    base_cfg = get_config(arch)
    cfg = replace(base_cfg, **overrides)
    orig = dr.get_config
    dr.get_config = lambda name: cfg if name == arch else orig(name)
    try:
        rec = dr.run_cell(arch, shape_name, mesh_name, RESULTS / tag)
    finally:
        dr.get_config = orig

    base_file = BASE / f"{arch}__{shape_name}__{mesh_name}.json"
    if base_file.exists():
        base = json.loads(base_file.read_text())
        print(f"[perf:{tag}] vs baseline:")
        for k in ("compute_s", "memory_s", "collective_s", "roofline_fraction"):
            b, v = base[k], rec[k]
            delta = (v - b) / b * 100 if b else float("nan")
            print(f"   {k:20s} {b:12.4f} -> {v:12.4f}  ({delta:+.1f}%)")
    return rec


def run_spdc_variant(mesh_name, relay, n, tag):
    from functools import partial

    from repro.distrib.spdc_pipeline import _PROGRAMS
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import analyze

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    N = mesh.shape["model"]
    prog = _PROGRAMS[relay if isinstance(relay, str) else
                     ("exact" if relay else "baseline")]
    fn = jax.shard_map(
        partial(prog, n=n, b=n // N, num_servers=N, axis="model"),
        mesh=mesh, in_specs=P("model", None),
        out_specs=(P("model", None), P("model", None)),
    )
    x_sds = jax.ShapeDtypeStruct(
        (n, n), jnp.float32, sharding=NamedSharding(mesh, P("model", None))
    )
    t0 = time.time()
    compiled = jax.jit(fn).lower(x_sds).compile()
    hc = analyze_hlo(compiled.as_text())
    rl = analyze(
        arch="spdc-lu", shape=f"n{n}-{relay}",
        mesh_name=mesh_name, chips=mesh.devices.size, cost={},
        hlo_text="", memory_stats={}, active_params=0.0, tokens=1.0,
        training=False, hlo_cost=hc,
    )
    rec = rl.to_dict()
    rec["compile_s"] = time.time() - t0
    RESULTS.mkdir(parents=True, exist_ok=True)
    with open(RESULTS / f"{tag}.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[perf:{tag}] compute={rl.compute_s*1e3:.2f}ms "
          f"memory={rl.memory_s*1e3:.2f}ms "
          f"collective={rl.collective_s*1e3:.2f}ms "
          f"permutes={hc.coll_counts.get('collective-permute', 0)} "
          f"coll_wire={hc.total_coll_wire/1e9:.3f}GB")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (repeatable)")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--spdc", action="store_true")
    ap.add_argument("--exact-relay", action="store_true")
    ap.add_argument("--relay", choices=["baseline", "exact", "stream"])
    ap.add_argument("--n", type=int, default=8192)
    args = ap.parse_args()
    if args.spdc:
        relay = args.relay or ("exact" if args.exact_relay else "baseline")
        run_spdc_variant(args.mesh, relay, args.n, args.tag)
    else:
        overrides = {}
        for kv in args.set:
            k, v = kv.split("=", 1)
            overrides[k] = _coerce(v)
        run_variant(args.arch, args.shape, args.mesh, overrides, args.tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())
