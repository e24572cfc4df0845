"""Distributed SPDC pipeline (shard_map) + sharding rules + SDC checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import freivalds_residual, outsource_determinant, sdc_flag
from repro.core.lu import lu_nserver
from repro.distrib.sharding import make_rules, use_rules
from repro.distrib.spdc_pipeline import (
    lu_nserver_shardmap, pipeline_collective_bytes,
)


def _wellcond(n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((n, n)) + n * np.eye(n))


PROGRAMS = ["baseline", "exact", "stream"]
#: (n, N): one server per device
ONE_PER_DEVICE = [(16, 4), (24, 8), (32, 2), (40, 5)]
#: (n, N, chips): k = N / chips servers share each chip of the mesh
SHARED_CHIPS = [(64, 16, 4), (48, 8, 4), (40, 10, 2), (96, 16, 8)]


def _mesh(chips):
    from repro.launch.mesh import make_smoke_mesh

    return make_smoke_mesh((chips,), ("servers",))


@pytest.mark.parametrize("n,servers,chips,program,batch", [
    *(pytest.param(n, s, None, p, None, id=f"{n}-{s}-{p}")
      for p in PROGRAMS for n, s in ONE_PER_DEVICE),
    *(pytest.param(n, s, c, "baseline", None, id=f"{n}-{s}-chips{c}-baseline")
      for n, s, c in SHARED_CHIPS),
    pytest.param(64, 16, 4, "baseline", 3, id="64-16-chips4-baseline-stack3"),
])
def test_shardmap_matches_reference(n, servers, chips, program, batch):
    x = _wellcond(n, seed=servers)
    if batch is not None:
        x = jnp.stack([_wellcond(n, seed=servers + i) for i in range(batch)])
    mesh = None if chips is None else _mesh(chips)
    l, u = lu_nserver_shardmap(x, servers, program=program, mesh=mesh)
    l2, u2, _ = lu_nserver(x, servers)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l2), atol=1e-9)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u2), atol=1e-9)


def test_relay_chips_is_the_largest_divisor_within_the_devices():
    from repro.distrib.spdc_pipeline import relay_chips

    assert [relay_chips(16, d) for d in (1, 2, 3, 4, 8, 16, 32)] == \
        [1, 2, 2, 4, 8, 16, 16]
    assert relay_chips(10, 8) == 5 and relay_chips(7, 4) == 1
    assert relay_chips(4, 8, "exact") == 4


#: (n, N, chips) of the relay against lu_nserver: one server per chip, and
#: 16 servers at k = 4, 2, 8 and 16 per chip (16 on one chip: no hop)
RELAY_LAYOUTS = [(16, 4, 4), (64, 16, 4), (64, 16, 8), (64, 16, 2), (64, 16, 1)]


@pytest.mark.parametrize("n,servers,chips,batch", [
    pytest.param(64, 16, None, None, id="default-devices"),
    *(pytest.param(n, s, c, None, id=f"{n}-{s}-chips{c}")
      for n, s, c in RELAY_LAYOUTS),
    pytest.param(64, 16, 4, 3, id="64-16-chips4-stack3"),
])
def test_sixteen_servers_on_the_default_devices_match_the_reference(
        n, servers, chips, batch):
    """The relay's factors are lu_nserver's to rounding, in float64. With
    no mesh, N=16 over this process's 8 devices: two servers per chip,
    chosen from the device count alone. A (B, n, n) stack takes one
    wavefront."""
    x = _wellcond(n, seed=16)
    if batch is not None:
        x = jnp.stack([_wellcond(n, seed=16 + i) for i in range(batch)])
    mesh = None if chips is None else _mesh(chips)
    l, u = lu_nserver_shardmap(x, servers, mesh=mesh)
    assert len(l.sharding.device_set) == (8 if chips is None else chips)
    l2, u2, _ = lu_nserver(x, servers)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l2), atol=1e-9)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u2), atol=1e-9)


@pytest.mark.parametrize("program", ["exact", "stream"])
@pytest.mark.parametrize("chips", [None, 4])
def test_exact_and_stream_refuse_more_servers_than_chips(program, chips):
    mesh = None if chips is None else _mesh(chips)
    with pytest.raises(ValueError, match="one device per server"):
        lu_nserver_shardmap(_wellcond(64), 16, program=program, mesh=mesh)


def test_a_mesh_that_does_not_divide_the_servers_is_refused():
    with pytest.raises(ValueError, match="does not divide"):
        lu_nserver_shardmap(_wellcond(48), 8, mesh=_mesh(3))


def test_shardmap_exact_relay_shim_removed():
    """The exact_relay deprecation cycle is finished: the parameter is
    gone, so passing it is a TypeError — not a silent bool reinterpret."""
    x = _wellcond(16, seed=1)
    with pytest.raises(TypeError, match="exact_relay"):
        lu_nserver_shardmap(x, 4, exact_relay=True)
    ref_l, ref_u = lu_nserver_shardmap(x, 4, program="exact")
    np.testing.assert_allclose(np.asarray(ref_l @ ref_u), np.asarray(x),
                               atol=1e-9)


def test_shardmap_rejects_unknown_program():
    with pytest.raises(ValueError, match="unknown program"):
        lu_nserver_shardmap(_wellcond(16), 4, program="telepathy")


def _assert_one_way(n, servers, chips):
    """The compiled relay's only collectives are collective-permutes down
    the chain: each carries at most 2b rows (never the (n, n) U buffer)
    and no edge goes from the last chip back to the first."""
    import re
    from functools import partial

    from repro.distrib.spdc_pipeline import _server_program
    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(
        partial(_server_program, n=n, b=n // servers, num_servers=servers,
                axis="servers"),
        mesh=_mesh(chips), in_specs=P("servers", None),
        out_specs=(P("servers", None), P("servers", None)),
    )
    txt = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float64)
    ).compile().as_text()
    collectives = re.findall(
        r"= (.*?) (collective-permute(?:-start)?|all-gather\S*|all-reduce\S*|"
        r"all-to-all|reduce-scatter|send|recv)\((.*)", txt)
    assert collectives
    assert "all-gather" not in txt
    assert "all-reduce" not in txt
    assert {op for _, op, _ in collectives} <= {"collective-permute",
                                                  "collective-permute-start"}
    for shape, _, rest in collectives:
        for dims in re.findall(r"\w+\[([\d,]+)\]", shape):
            rows = [int(d) for d in dims.split(",")]
            assert len(rows) < 2 or rows[-2] <= 2 * (n // servers), shape
        pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", rest).group(1)
        edges = [tuple(map(int, e)) for e in re.findall(r"(\d+),(\d+)", pairs)]
        assert edges and all(t == s + 1 for s, t in edges), edges


def test_shardmap_hlo_is_one_way():
    """The distributed pipeline must contain collective-permutes (the
    one-way relay) and no all-gather/all-reduce (no broadcast pattern)."""
    _assert_one_way(16, 4, 4)


def test_shardmap_hlo_is_one_way_with_four_servers_per_chip():
    """Servers that share a chip hand off by a local update of its slab;
    between chips the relay is still collective-permutes only."""
    _assert_one_way(64, 16, 4)


def test_distributed_protocol_end_to_end():
    m = np.asarray(_wellcond(24, seed=3))
    res = outsource_determinant(m, 4, distributed=True)
    want_s, want_la = np.linalg.slogdet(m)
    assert res.verified and res.det.sign == want_s
    np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-9)


def test_sixteen_servers_through_the_shardmap_transport():
    """outsource_determinant(M, 16, transport="shardmap") on 8 devices:
    verified by Q3, the ciphertext placed on the relay's chips first."""
    m = np.asarray(_wellcond(96, seed=5))
    res = outsource_determinant(m, 16, transport="shardmap", method="q3")
    want_s, want_la = np.linalg.slogdet(m)
    assert res.verified and res.det.sign == want_s
    np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-9)


def test_comm_model_overcount_bounded():
    info = pipeline_collective_bytes(1024, 8)
    # each U row, (b, n) with b = 128, crosses every edge below its server:
    # 7 + 6 + ... + 1 = 28 rows, float64
    assert info["relay_bytes"] == 28 * 128 * 1024 * 8
    assert info["paper_exact_bytes"] < info["relay_bytes"]
    # the paper sends only a row's blocks from the diagonal on: relay
    # N(N-1)/2 rows of n columns vs ~N³/3 blocks → factor < 1.5 for any N
    assert info["overcount_factor"] < 1.5


# ----------------------------------------------------------- sharding rules
def test_rules_head_fallback():
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((2, 4))
    r1 = make_rules(mesh, num_heads=8, num_kv_heads=4)
    assert r1.shard_heads and r1.shard_kv
    r2 = make_rules(mesh, num_heads=6, num_kv_heads=1)  # 6 % 4 != 0
    assert not r2.shard_heads and not r2.shard_kv
    assert r2.resolve("batch", "qseq", "heads", None) == jax.sharding.PartitionSpec(
        ("data",), "model", None, None
    )


def test_constrain_noop_without_rules():
    from repro.distrib.sharding import constrain

    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


def test_sharded_train_step_runs():
    """Integration: tiny model, real mesh, sharded params, one train step."""
    from repro.configs import smoke_config
    from repro.models.common import split_tree
    from repro.models.lm import init_lm
    from repro.train.data import SyntheticLM
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.steps import build_train_step
    from jax.sharding import NamedSharding

    cfg = smoke_config("tinyllama-1.1b")
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((2, 4))
    rules = make_rules(mesh, num_heads=cfg.num_heads,
                       num_kv_heads=cfg.num_kv_heads)
    with use_rules(rules):
        px = init_lm(cfg, jax.random.key(0))
        params, specs = split_tree(px)
        params = jax.tree.map(
            lambda v, s: jax.device_put(
                v, NamedSharding(mesh, rules.resolve(*s))
            ),
            params, specs,
        )
        opt_cfg = AdamWConfig(lr=1e-3)
        opt = init_opt_state(params, opt_cfg)
        step = jax.jit(build_train_step(cfg, opt_cfg))
        batch = SyntheticLM(cfg).batch(0, 8, 32)
        p2, o2, metrics = step(params, opt, batch, jax.random.key(1))
        assert np.isfinite(float(metrics["loss"]))
        # params actually sharded
        emb = p2["embed"]
        assert len(emb.sharding.device_set) == 8


# ------------------------------------------------------------------ SDC
def test_freivalds_accepts_correct_and_rejects_corrupt():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 32)), dtype=jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 48)), dtype=jnp.float32)
    c = a @ b
    key = jax.random.key(0)
    r_ok = freivalds_residual(a, b, c, key)
    assert not bool(sdc_flag(r_ok))
    c_bad = c.at[5, 7].add(1.0)  # one corrupted element
    r_bad = freivalds_residual(a, b, c_bad, key)
    assert bool(sdc_flag(r_bad))


def test_sdc_in_train_step():
    from repro.configs import smoke_config
    from repro.models.common import split_tree
    from repro.models.lm import init_lm
    from repro.train.data import SyntheticLM
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.steps import build_train_step

    cfg = smoke_config("tinyllama-1.1b")
    params, _ = split_tree(init_lm(cfg, jax.random.key(0)))
    opt_cfg = AdamWConfig()
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(build_train_step(cfg, opt_cfg, sdc_check=True))
    batch = SyntheticLM(cfg).batch(0, 4, 128)
    _, _, metrics = step(params, opt, batch, jax.random.key(1))
    assert float(metrics["sdc_residual"]) < 1e-3
