"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _rand(shape, dtype=jnp.float64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


# ------------------------------------------------------------------- CED
@pytest.mark.parametrize("n,block", [(8, 4), (16, 8), (12, 4), (256, 128), (20, 1)])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("mode", ["ewd", "ewm"])
def test_ced_kernel(n, block, k, mode):
    m = _rand((n, n), seed=n + k)
    v = jnp.asarray(np.random.default_rng(1).uniform(0.5, 2.0, n))
    got = ops.ced(m, v, k, mode=mode, block=block)
    want = ref.ced_ref(m, v, k, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


@pytest.mark.parametrize("shape", [(100, 100), (128, 128), (2, 100, 100)])
@pytest.mark.parametrize("growth_safe", [False, True])
@pytest.mark.parametrize("k", [0, 1, 2, 3])
def test_ced_every_rotation_and_padded_tile(shape, growth_safe, k):
    """Every quarter-turn, with and without the growth-safe flip, at the
    TPU tile (128) and at an n it does not divide (zero-padded to the
    tile grid, then cropped): exactly the jnp cipher's data movement."""
    from repro.core.cipher import _flip_rotated, ewo
    from repro.core.prt import rot90_cw

    m = _rand(shape, seed=k)
    v = jnp.asarray(np.random.default_rng(1).uniform(0.5, 2.0, shape[:-1]))
    got = ops.ced(m, v, k, growth_safe=growth_safe)
    if len(shape) == 3:
        want = jnp.stack([rot90_cw(ewo(m[i], v[i], "ewd"), k)
                          for i in range(shape[0])])
    else:
        want = rot90_cw(ewo(m, v, "ewd"), k)
    if growth_safe:
        want = _flip_rotated(want, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_ced_dtypes(dtype):
    m = _rand((16, 16), dtype=dtype)
    v = jnp.asarray(np.random.default_rng(1).uniform(0.5, 2.0, 16), dtype=dtype)
    got = ops.ced(m, v, 2, block=8)
    want = ref.ced_ref(m, v, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# --------------------------------------------------------------- LU panel
@pytest.mark.parametrize("n", [4, 8, 32, 64, 128])
def test_lu_panel_kernel(n):
    a = _rand((n, n), seed=n) + n * jnp.eye(n)
    l, u = ops.lu_panel(a)
    want = ref.lu_panel_ref(a)
    got = jnp.tril(l, -1) + u
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-9)
    np.testing.assert_allclose(np.asarray(l @ u), np.asarray(a), atol=1e-9)


# ------------------------------------------------------------------- TRSM
@pytest.mark.parametrize("n,m", [(4, 8), (16, 16), (32, 128), (64, 32)])
def test_trsm_kernels(n, m):
    l = jnp.tril(_rand((n, n), seed=n), -1) + jnp.eye(n)
    b = _rand((n, m), seed=m)
    np.testing.assert_allclose(
        np.asarray(ops.trsm_lower(l, b)),
        np.asarray(ref.trsm_lower_ref(l, b)), atol=1e-9,
    )
    u = jnp.triu(_rand((n, n), seed=n + 1)) + n * jnp.eye(n)
    b2 = _rand((m, n), seed=m + 1)
    np.testing.assert_allclose(
        np.asarray(ops.trsm_upper_right(u, b2)),
        np.asarray(ref.trsm_upper_right_ref(u, b2)), atol=1e-9,
    )


# ------------------------------------------------------------------- Schur
@settings(max_examples=10, deadline=None)
@given(mi=st.sampled_from([32, 64]), ni=st.sampled_from([32, 96]),
       ki=st.sampled_from([16, 64]))
def test_schur_kernel_property(mi, ni, ki):
    c = _rand((mi, ni), seed=1)
    a = _rand((mi, ki), seed=2)
    b = _rand((ki, ni), seed=3)
    got = ops.schur_update(c, a, b, bm=32, bn=32, bk=16)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.schur_update_ref(c, a, b)), atol=1e-9
    )


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-4), (jnp.bfloat16, 0.15)])
def test_schur_low_precision(dtype, atol):
    c = _rand((64, 64), dtype=dtype)
    a = _rand((64, 64), dtype=dtype, seed=1)
    b = _rand((64, 64), dtype=dtype, seed=2)
    got = ops.schur_update(c, a, b, bm=32, bn=32, bk=32)
    want = ref.schur_update_ref(
        c.astype(jnp.float32), a.astype(jnp.float32), b.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want), atol=atol, rtol=0.05
    )


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa(hq, hkv, causal):
    q = _rand((2, hq, 64, 16), dtype=jnp.float32, seed=1)
    k = _rand((2, hkv, 64, 16), dtype=jnp.float32, seed=2)
    v = _rand((2, hkv, 64, 16), dtype=jnp.float32, seed=3)
    got = ops.flash_attention(q, k, v, causal=causal, bq=16, bk=16)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("window", [8, 24, 64])
def test_flash_attention_sliding(window):
    q = _rand((1, 2, 64, 16), dtype=jnp.float32, seed=1)
    k = _rand((1, 2, 64, 16), dtype=jnp.float32, seed=2)
    v = _rand((1, 2, 64, 16), dtype=jnp.float32, seed=3)
    got = ops.flash_attention(q, k, v, causal=True, window=window, bq=16, bk=16)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_flash_attention_decode_right_aligned():
    """sq < sk: queries are the LAST sq positions (decode semantics)."""
    q = _rand((2, 4, 4, 16), dtype=jnp.float32, seed=1)
    k = _rand((2, 4, 64, 16), dtype=jnp.float32, seed=2)
    v = _rand((2, 4, 64, 16), dtype=jnp.float32, seed=3)
    got = ops.flash_attention(q, k, v, causal=True, bq=4, bk=16)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5), (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    q = _rand((1, 2, 32, 8), dtype=dtype, seed=1)
    k = _rand((1, 2, 32, 8), dtype=dtype, seed=2)
    v = _rand((1, 2, 32, 8), dtype=dtype, seed=3)
    got = ops.flash_attention(q, k, v, causal=True, bq=8, bk=8)
    want = ref.flash_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want), atol=atol
    )


# -------------------------------------------- kernels inside blocked LU
def test_blocked_lu_with_kernels_end_to_end():
    from repro.core.lu import lu_blocked

    a = _rand((64, 64), seed=11) + 64 * jnp.eye(64)
    l, u = lu_blocked(a, 16, use_kernels=True)
    l2, u2 = lu_blocked(a, 16, use_kernels=False)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l2), atol=1e-9)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u2), atol=1e-9)
