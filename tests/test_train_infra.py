"""Training infrastructure: optimizer math, checkpoint atomicity/integrity/
elasticity, deterministic data, fault-tolerant loop behavior."""
import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.common import split_tree
from repro.models.lm import init_lm
from repro.train.checkpoint import CheckpointManager
from repro.train.data import SyntheticLM
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import (
    AdamWConfig, adamw_update, global_norm, init_opt_state, schedule,
)
from repro.train.steps import build_train_step


# ------------------------------------------------------------------ optimizer
def test_adamw_matches_reference_impl():
    """Our AdamW == a straightforward numpy AdamW on a toy problem."""
    cfg = AdamWConfig(lr=0.1, beta1=0.9, beta2=0.999, eps=1e-8,
                      weight_decay=0.0, clip_norm=1e9, warmup_steps=0,
                      total_steps=10**9)
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    st = init_opt_state(p, cfg)
    p1, st1, _ = adamw_update(p, g, st, cfg)
    # reference
    m = 0.1 * np.asarray(g["w"])
    v = 0.001 * np.asarray(g["w"]) ** 2
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    # schedule at step 1: cosine progress ~0 => lr ≈ cfg.lr
    lr = float(schedule(cfg, jnp.asarray(1.0)))
    want = np.asarray(p["w"]) - lr * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(p1["w"]), want, rtol=1e-5)


def test_grad_clipping():
    cfg = AdamWConfig(clip_norm=0.5)
    g = {"w": jnp.asarray([30.0, 40.0])}  # norm 50
    assert np.isclose(float(global_norm(g)), 50.0)
    p = {"w": jnp.zeros(2)}
    st = init_opt_state(p, cfg)
    _, _, metrics = adamw_update(p, g, st, cfg)
    assert np.isclose(float(metrics["grad_norm"]), 50.0)


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110)
    assert float(schedule(cfg, jnp.asarray(5.0))) == pytest.approx(0.5)
    assert float(schedule(cfg, jnp.asarray(10.0))) == pytest.approx(1.0)
    late = float(schedule(cfg, jnp.asarray(110.0)))
    assert late == pytest.approx(0.1, rel=1e-3)  # cosine floor = 0.1 lr


def test_bf16_state_dtype():
    cfg = AdamWConfig(state_dtype=jnp.bfloat16)
    p = {"w": jnp.ones(4, jnp.bfloat16)}
    st = init_opt_state(p, cfg)
    assert st["mu"]["w"].dtype == jnp.bfloat16
    p2, st2, _ = adamw_update(p, {"w": jnp.ones(4, jnp.bfloat16)}, st, cfg)
    assert p2["w"].dtype == jnp.bfloat16
    assert st2["nu"]["w"].dtype == jnp.bfloat16


# ----------------------------------------------------------------- checkpoint
def _tiny_state():
    return {
        "params": {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(4)},
        "opt": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_checkpoint_roundtrip_and_gc():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep_last=2)
        st = _tiny_state()
        for step in (10, 20, 30):
            mgr.save(step, st, blocking=True)
        assert mgr.all_steps() == [20, 30]  # oldest pruned
        restored, at = mgr.restore(st)
        assert at == 30
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["a"]), np.asarray(st["params"]["a"])
        )


def test_checkpoint_integrity_detection():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        st = _tiny_state()
        mgr.save(5, st, blocking=True)
        # corrupt a leaf on disk
        leaf = next(Path(d).glob("step_*/leaf_000000.npy"))
        arr = np.load(leaf)
        arr.flat[0] += 1
        np.save(leaf, arr)
        with pytest.raises(IOError, match="corruption"):
            mgr.restore(st)


def test_checkpoint_atomicity_no_partial_dirs():
    """A tmp dir left by a 'crashed' writer is never listed as a checkpoint."""
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, _tiny_state(), blocking=True)
        fake = Path(d) / "step_000000099.tmp-1234"
        fake.mkdir()
        assert mgr.all_steps() == [1]


def test_elastic_restore_onto_different_mesh():
    """Save unsharded, restore onto a 4-device sharded layout (and back)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        st = {"w": jnp.arange(16.0).reshape(4, 4)}
        mgr.save(1, st, blocking=True)
        from repro.launch.mesh import make_smoke_mesh

        mesh = make_smoke_mesh((4,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        placed, _ = mgr.restore_sharded(st, sh)
        assert len(placed["w"].sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(placed["w"]), np.asarray(st["w"]))


# ----------------------------------------------------------------------- data
def test_data_determinism_and_sharding():
    cfg = smoke_config("tinyllama-1.1b")
    d1 = SyntheticLM(cfg, seed=1)
    d2 = SyntheticLM(cfg, seed=1)
    b1 = d1.batch(5, 8, 16)
    b2 = d2.batch(5, 8, 16)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert not np.array_equal(
        np.asarray(d1.batch(6, 8, 16)["tokens"]), np.asarray(b1["tokens"])
    )
    # shard slices tile the global batch
    shards = [d1.shard_batch(5, 8, 16, i, 4)["tokens"] for i in range(4)]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s) for s in shards]), np.asarray(b1["tokens"])
    )
    # markov structure: every transition comes from the table
    toks = np.asarray(b1["tokens"])
    nexts = np.asarray(d1.nexts)
    for row in toks:
        for t in range(len(row) - 1):
            assert row[t + 1] in nexts[row[t]]


# ----------------------------------------------------------------------- loop
def _loop_fixture(tmp, total=30, **kw):
    cfg = smoke_config("tinyllama-1.1b")
    params, _ = split_tree(init_lm(cfg, jax.random.key(0)))
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = jax.tree.map(lambda x: x, init_opt_state(params, opt_cfg))
    step = jax.jit(build_train_step(cfg, opt_cfg))
    data = SyntheticLM(cfg, seed=0)
    mgr = CheckpointManager(tmp, keep_last=3)
    lc = LoopConfig(total_steps=total, checkpoint_every=10, **kw)
    return step, params, opt, (lambda s: data.batch(s, 4, 32)), mgr, lc


def test_loop_resumes_after_crash():
    with tempfile.TemporaryDirectory() as d:
        step, p, o, data_fn, mgr, lc = _loop_fixture(d)

        calls = {"n": 0}

        def bomb(s):
            if s == 15 and calls["n"] == 0:
                calls["n"] = 1
                raise RuntimeError("node failure")

        _, _, rep = run_training(step, p, o, data_fn, mgr, lc,
                                 fault_injector=bomb)
        assert rep.restarts == 1
        # replayed steps 10..15 after resume => more steps run than total
        assert rep.steps_run > lc.total_steps - 1
        assert mgr.latest_step() == lc.total_steps


def test_loop_straggler_detection():
    import time

    with tempfile.TemporaryDirectory() as d:
        step, p, o, data_fn, mgr, lc = _loop_fixture(
            d, total=12, straggler_factor=5.0
        )

        def slow_data(s):
            if s == 9:
                time.sleep(1.0)  # slow data fetch — inside the timed region
            return data_fn(s)

        _, _, rep = run_training(step, p, o, slow_data, mgr, lc)
        assert any(s == 9 for s, _, _ in rep.straggler_events)


@pytest.mark.slow
def test_loop_fresh_vs_resumed_equivalence():
    """Crash/resume must land on the same params as an uninterrupted run
    (determinism of data + replay from checkpoint)."""
    with tempfile.TemporaryDirectory() as d1, tempfile.TemporaryDirectory() as d2:
        step, p, o, data_fn, mgr1, lc = _loop_fixture(d1, total=20)
        pa, _, _ = run_training(step, p, o, data_fn, mgr1, lc)

        step2, p2, o2, data_fn2, mgr2, lc2 = _loop_fixture(d2, total=20)

        fired = {"n": 0}

        def bomb(s):
            if s == 13 and fired["n"] == 0:
                fired["n"] = 1
                raise RuntimeError("boom")

        pb, _, _ = run_training(step2, p2, o2, data_fn2, mgr2, lc2,
                                fault_injector=bomb)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-6)


# ----------------------------------------------------------------------- eval
def test_eval_step_deterministic_finite_loss():
    """build_eval_step returns a pure loss: finite scalar, bit-identical
    across calls, and jit-compatible."""
    from repro.train.steps import build_eval_step

    cfg = smoke_config("tinyllama-1.1b")
    params, _ = split_tree(init_lm(cfg, jax.random.key(0)))
    batch = SyntheticLM(cfg, seed=0).batch(0, 4, 32)
    ev = jax.jit(build_eval_step(cfg, ce_chunk=16))
    l1 = float(ev(params, batch))
    l2 = float(ev(params, batch))
    assert np.isfinite(l1)
    assert l1 == l2
    # an untrained model should sit near uniform cross-entropy
    assert 0.0 < l1 < 2.0 * np.log(cfg.vocab_size)
