"""Launch-layer tests: hlo_cost trip-count correction, roofline parsing,
perf variants (pure-DP strategy, relay programs), and one real dry-run cell
via subprocess (512 fake devices need a fresh process)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_hlo_cost_counts_scan_trip_counts():
    """The raison d'être of launch/hlo_cost.py: XLA counts while bodies
    once; we must multiply by the trip count."""
    from repro.launch.hlo_cost import analyze_hlo

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jnp.zeros((64, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    raw = compiled.cost_analysis().get("flops", 0.0)
    ours = analyze_hlo(compiled.as_text()).flops
    dot_flops = 2 * 64 * 128 * 128
    assert raw < 2 * dot_flops  # XLA: body counted once
    assert ours > 9 * dot_flops  # ours: ~10x
    assert ours < 12 * dot_flops


def test_init_process_x64_follows_platform_and_cache_rule(monkeypatch,
                                                         tmp_path):
    """x64 on only on the CPU; the compile cache at the checkout's fixed
    .jax_cache/ unless JAX_COMPILATION_CACHE_DIR names one, in which case
    nothing is configured."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro import runtime

    x64, cache_dir = (jax.config.jax_enable_x64,
                      jax.config.jax_compilation_cache_dir)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert runtime.init_process() is True
        assert jax.config.jax_enable_x64
        assert runtime.CACHE_DIR == Path(__file__).resolve().parents[1] \
            / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(runtime.CACHE_DIR)
        assert runtime.init_process(x64=False) is False
        assert not jax.config.jax_enable_x64

        monkeypatch.setattr(runtime, "on_cpu", lambda: False)
        assert runtime.init_process() is False  # an accelerator: never x64
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        runtime.init_process()
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_enable_x64", x64)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()


def test_init_process_creates_no_backend_when_platform_named():
    """With JAX_PLATFORMS=cpu, init_process decides x64 from the config
    alone, so a later `import repro.linalg` still switches off XLA:CPU
    async dispatch (it only takes effect before the CPU backend exists)."""
    code = (
        "import warnings; warnings.simplefilter('error')\n"
        "import jax\n"
        "from jax._src import xla_bridge as xb\n"
        "from repro.runtime import init_process\n"
        "assert init_process() is True\n"
        "assert not xb._backends, 'init_process created a backend'\n"
        "import repro.linalg\n"
        "assert not xb._CPU_ENABLE_ASYNC_DISPATCH.value\n"
        "assert jax.numpy.zeros(2).dtype == 'float64'\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src")}
    env.pop("JAX_CPU_ENABLE_ASYNC_DISPATCH", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_hlo_cost_collectives_in_loops():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.hlo_cost import analyze_hlo

    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((2, 4))
    xs = jax.ShapeDtypeStruct((16, 64), jnp.float32,
                              sharding=NamedSharding(mesh, P("data", None)))
    ws = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, "model")))

    def g(x, w):
        def body(c, _):
            h = jnp.tanh(c @ w)
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P("data", None)))
            return h, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    c = analyze_hlo(jax.jit(g).lower(xs, ws).compile().as_text())
    assert c.coll_counts.get("all-gather", 0) == 5  # multiplied by trips


def test_roofline_analyze_terms():
    from repro.launch.hlo_cost import Cost
    from repro.launch.roofline import analyze

    hc = Cost(flops=197e12, hbm_bytes=819e9 / 2)
    hc.coll_wire = {"all-reduce": 100e9}
    hc.coll_counts = {"all-reduce": 1}
    hc.coll_bytes = {"all-reduce": 50e9}
    rl = analyze(arch="x", shape="y", mesh_name="single", chips=256,
                 cost={}, hlo_text="", memory_stats={},
                 active_params=1e9, tokens=1e6, training=True, hlo_cost=hc)
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(0.5)
    assert rl.collective_s == pytest.approx(1.0)  # 100e9/(2*50e9)
    assert rl.dominant in ("compute", "collective")
    assert rl.model_flops == pytest.approx(6e15)


def test_relay_programs_equivalent():
    """baseline / exact / stream relay programs produce identical LU."""
    from repro.core.lu import lu_nserver
    from repro.distrib.spdc_pipeline import lu_nserver_shardmap

    rng = np.random.default_rng(11)
    n, N = 32, 8
    x = jnp.asarray(rng.standard_normal((n, n)) + n * np.eye(n))
    ref_l, ref_u, _ = lu_nserver(x, N)
    for program in ("baseline", "exact", "stream"):
        l, u = lu_nserver_shardmap(x, N, program=program)
        np.testing.assert_allclose(np.asarray(l), np.asarray(ref_l),
                                   atol=1e-9, err_msg=program)
        np.testing.assert_allclose(np.asarray(u), np.asarray(ref_u),
                                   atol=1e-9, err_msg=program)


def test_dp_over_model_rules():
    """The pure-DP strategy (§Perf B) folds every axis into batch/fsdp."""
    from dataclasses import replace

    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import rules_for
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((2, 4), ("data", "model"))
    cfg = replace(get_config("mamba2-370m"), dp_over_model=True)
    rules = rules_for(cfg, SHAPES["train_4k"], mesh)
    assert rules.model_axis is None
    assert rules.batch_axes == ("data", "model")
    assert rules.fsdp_axes == ("data", "model")


def test_effective_grad_accum_clamp():
    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import effective_cfg, rules_for
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((8, 1), ("data", "model"))
    cfg = get_config("nemotron-4-340b")  # grad_accum=32
    rules = rules_for(cfg, SHAPES["train_4k"], mesh)
    eff = effective_cfg(cfg, SHAPES["train_4k"], mesh, rules)
    # 256 batch / 8 data shards => accum can stay 32 (256/32=8 divisible by 8)
    assert (256 // eff.grad_accum) % 8 == 0


@pytest.mark.slow
def test_dryrun_one_cell_subprocess(tmp_path):
    """End-to-end dry-run of a small cell on the real 16x16 mesh (fresh
    process: 512 fake devices must be set before JAX init)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma3-1b",
         "--shape", "decode_32k", "--mesh", "single", "--out", str(tmp_path)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=400,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.load(open(tmp_path / "gemma3-1b__decode_32k__single.json"))
    assert rec["chips"] == 256
    assert rec["compute_s"] > 0 and rec["memory_s"] > 0
    assert rec["dominant"] in ("compute", "memory", "collective")
