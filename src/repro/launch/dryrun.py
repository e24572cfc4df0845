import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count at first init.

import argparse
import json
import sys
import time
import traceback
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import SHAPES, CONFIGS, cell_status, get_config
from repro.distrib.sharding import ShardingRules, make_rules, use_rules
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze
from repro.models.common import split_tree
from repro.models.lm import init_lm
from repro.serve.kvcache import cache_logical_specs, init_caches
from repro.serve.steps import build_decode_step, build_prefill_step
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.steps import build_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results"


def _sds(tree, rules: ShardingRules, spec_tree):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    def attach(x, spec):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(rules.mesh, rules.resolve(*spec))
        )
    return jax.tree.map(attach, tree, spec_tree)


def _batch_specs(cfg, shape, rules):
    b, s = shape.global_batch, shape.seq_len
    seq = 1 if shape.kind == "decode" else s
    batch_sh = NamedSharding(rules.mesh, rules.resolve("batch", None))
    out = {}
    if cfg.frontend is None:
        out["tokens"] = jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=batch_sh)
    else:
        out["embeds"] = jax.ShapeDtypeStruct(
            (b, seq, cfg.d_model), jnp.float32,
            sharding=NamedSharding(rules.mesh, rules.resolve("batch", None, None)),
        )
    if shape.kind == "train":
        out["labels"] = jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=batch_sh)
    return out


def rules_for(cfg, shape, mesh) -> ShardingRules:
    rules = make_rules(mesh, num_heads=cfg.num_heads or None,
                       num_kv_heads=cfg.num_kv_heads or None,
                       use_fsdp=cfg.use_fsdp)
    if cfg.dp_over_model:
        # pure-DP strategy: batch (and FSDP) over every mesh axis, no TP
        all_axes = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.axis_names)
        rules = replace(rules, batch_axes=all_axes, model_axis=None,
                        fsdp_axes=all_axes if cfg.use_fsdp else (),
                        shard_heads=False, shard_kv=False)
    dsize = 1
    for a in rules.batch_axes:
        dsize *= mesh.shape[a]
    if dsize and shape.global_batch % dsize != 0:
        rules = replace(rules, batch_axes=())
    return rules


def effective_cfg(cfg, shape, mesh, rules) -> object:
    """Clamp grad_accum so each microbatch still shards evenly over the
    data axes (global_batch / accum must be a multiple of the data size)."""
    if shape.kind != "train" or cfg.grad_accum == 1:
        return cfg
    dsize = 1
    for a in rules.batch_axes:
        dsize *= mesh.shape[a]
    accum = cfg.grad_accum
    while accum > 1 and (shape.global_batch % accum or
                         (shape.global_batch // accum) % max(dsize, 1)):
        accum //= 2
    return replace(cfg, grad_accum=max(accum, 1))


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str):
    """Lower + compile one (arch × shape) cell; returns (compiled, rules, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rules = rules_for(cfg, shape, mesh)
    cfg = effective_cfg(cfg, shape, mesh, rules)

    with use_rules(rules):
        px = jax.eval_shape(lambda: init_lm(cfg, jax.random.key(0)))
        params_sds, specs = split_tree(px)
        params_sds = _sds(params_sds, rules, specs)
        batch_sds = _batch_specs(cfg, shape, rules)

        def shardings_of(tree):
            return jax.tree.map(lambda x: x.sharding, tree)
        if shape.kind == "train":
            opt_cfg = AdamWConfig(state_dtype=cfg.opt_dtype)
            opt_sds = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params_sds)
            opt_specs = {"mu": specs, "nu": specs, "step": ()}
            opt_sds = _sds(opt_sds, rules, opt_specs)
            step_fn = build_train_step(cfg, opt_cfg)
            # out_shardings pinned to the input layouts: stops GSPMD from
            # re-sharding (= all-gathering) optimizer math or gradients
            lowered = jax.jit(
                step_fn, donate_argnums=(0, 1),
                out_shardings=(shardings_of(params_sds), shardings_of(opt_sds),
                               None),
            ).lower(params_sds, opt_sds, batch_sds, jax.random.key(0))
        elif shape.kind == "prefill":
            step_fn = build_prefill_step(cfg)
            lowered = jax.jit(step_fn).lower(params_sds, batch_sds)
        else:  # decode
            caches_sds = jax.eval_shape(
                lambda: init_caches(cfg, shape.global_batch, shape.seq_len)
            )
            cache_specs = cache_logical_specs(cfg, caches_sds)
            caches_sds = _sds(caches_sds, rules, cache_specs)
            pos_sds = jax.ShapeDtypeStruct(
                (shape.global_batch,), jnp.int32,
                sharding=NamedSharding(rules.mesh, rules.resolve("batch")),
            )
            step_fn = build_decode_step(cfg)
            lowered = jax.jit(
                step_fn, donate_argnums=(1,),
                out_shardings=(None, shardings_of(caches_sds)),
            ).lower(params_sds, caches_sds, batch_sds, pos_sds)
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
    meta = {"compile_s": compile_s, "cfg": cfg, "shape": shape,
            "params_sds": params_sds,
            "opt_sds": locals().get("opt_sds"),
            "caches_sds": locals().get("caches_sds")}
    return compiled, rules, meta


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: Path) -> dict:
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.devices.size
    compiled, rules, meta = lower_cell(arch, shape_name, mesh, mesh_name)
    cfg, shape = meta["cfg"], meta["shape"]

    mem = compiled.memory_analysis()
    memory_stats = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_est_bytes": int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
        ),
    }
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hc = analyze_hlo(hlo)  # trip-count-corrected (see hlo_cost.py docstring)

    training = shape.kind == "train"
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    rl = analyze(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        cost={k: cost.get(k, 0.0) for k in ("flops", "bytes accessed")},
        hlo_text=hlo, memory_stats=memory_stats,
        active_params=cfg.active_param_count(), tokens=tokens,
        training=training, hlo_cost=hc,
    )
    rec = rl.to_dict()
    rec["xla_cost_analysis_raw"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "note": "XLA visits while bodies once; see hlo_cost.py",
    }
    rec["compile_s"] = meta["compile_s"]
    rec["sharding"] = {
        "shard_heads": rules.shard_heads, "shard_kv": rules.shard_kv,
        "batch_axes": list(rules.batch_axes),
    }
    # analytic state accounting (exact; the memory_analysis temp numbers
    # additionally carry XLA:CPU f32-promotion artifacts — see EXPERIMENTS.md)
    def _tree_bytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    state = {"params_total_bytes": _tree_bytes(meta["params_sds"])}
    if meta.get("opt_sds") is not None:
        state["opt_total_bytes"] = _tree_bytes(meta["opt_sds"])
    if meta.get("caches_sds") is not None:
        state["caches_total_bytes"] = _tree_bytes(meta["caches_sds"])
    state["state_per_device_gib"] = sum(
        v for k, v in state.items() if k.endswith("_bytes")
    ) / chips / 2**30
    rec["state_analysis"] = state
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{arch}__{shape_name}__{mesh_name}.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(
        f"[dryrun] {arch} × {shape_name} × {mesh_name}: OK "
        f"compile={meta['compile_s']:.1f}s "
        f"compute={rl.compute_s*1e3:.2f}ms memory={rl.memory_s*1e3:.2f}ms "
        f"collective={rl.collective_s*1e3:.2f}ms dominant={rl.dominant} "
        f"frac={rl.roofline_fraction:.3f} peak_mem={memory_stats['peak_est_bytes']/2**30:.2f}GiB"
    )
    print(f"  memory_analysis: {mem}")
    return rec


def run_spdc_cell(mesh_name: str, out_dir: Path, n: int = 8192) -> dict:
    """The paper's own workload on the production mesh: 16-server one-way
    pipelined LU over the model axis (f32 lowering; f64 validated in tests)."""
    from repro.distrib.spdc_pipeline import lu_nserver_shardmap
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    x_sds = jax.ShapeDtypeStruct(
        (n, n), jnp.float32,
        sharding=NamedSharding(mesh, jax.sharding.PartitionSpec("model", None)),
    )
    from functools import partial
    from repro.distrib.spdc_pipeline import _server_program
    from jax.sharding import PartitionSpec as P
    N = mesh.shape["model"]
    fn = jax.shard_map(
        partial(_server_program, n=n, b=n // N, num_servers=N, axis="model"),
        mesh=mesh, in_specs=P("model", None),
        out_specs=(P("model", None), P("model", None)),
    )
    t0 = time.time()
    lowered = jax.jit(fn).lower(x_sds)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hc = analyze_hlo(compiled.as_text())
    rl = analyze(
        arch="spdc-lu", shape=f"n{n}", mesh_name=mesh_name,
        chips=mesh.devices.size,
        cost={k: cost.get(k, 0.0) for k in ("flops", "bytes accessed")},
        hlo_text=compiled.as_text(),
        memory_stats={"temp_bytes": int(mem.temp_size_in_bytes)},
        active_params=0.0, tokens=1.0, training=False, hlo_cost=hc,
    )
    rec = rl.to_dict()
    rec["compile_s"] = compile_s
    rec["lu_flops"] = 2 * n**3 / 3
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"spdc-lu__n{n}__{mesh_name}.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] spdc-lu n={n} × {mesh_name}: OK compile={compile_s:.1f}s "
          f"collective-permutes={rl.collectives['counts'].get('collective-permute', 0)}")
    return rec


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in CONFIGS:
        for shape_name in SHAPES:
            ok, _ = cell_status(CONFIGS[arch], shape_name)
            if ok:
                cells.append((arch, shape_name))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--spdc", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)
    if args.list:
        for a, s in all_cells():
            print(f"{a} {s}")
        return 0
    try:
        if args.spdc:
            run_spdc_cell(args.mesh, out_dir)
        else:
            run_cell(args.arch, args.shape, args.mesh, out_dir)
        return 0
    except Exception:
        traceback.print_exc()
        print(f"[dryrun] {args.arch} × {args.shape} × {args.mesh}: FAILED")
        return 1


if __name__ == "__main__":
    sys.exit(main())
