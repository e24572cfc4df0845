"""Flash attention kernel — blockwise online-softmax, the memory hot spot of
every attention arch at 32k–500k context.

Materializing S = QKᵀ at 32k is 4 GiB/head (f32); blockwise online softmax
(Rabe & Staats / FlashAttention) keeps the working set at
(bq×d + 2·bk×d + bq×bk) ≈ 300 KiB in VMEM. Grid (batch, q_head, q_blk,
kv_blk), kv innermost so the accumulator + running (m, ℓ) stats stay
resident in VMEM scratch across the contraction. GQA is handled in the
K/V index maps (kv head = q head // group), so K/V tiles are never
replicated in HBM. Causal and sliding-window masks are applied per-tile
with right-aligned query positions (decode: sq < sk works unchanged).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import on_cpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: int | None, sk_total: int, bq: int, bk: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, ...]  # (bq, d)
    k = k_ref[0, 0, ...]  # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    # right-aligned absolute positions
    sq_total = pl.num_programs(2) * bq
    qpos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk_total - sq_total)
    kpos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (bq, bk)
    correction = jnp.exp(m_prev - m_new)  # (bq, 1)
    l_new = correction * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * correction + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0, 0, ...], preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        # fully-masked rows (can happen with windows) -> zero output
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)


@partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret", "scale"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D) with Hq % Hkv == 0."""
    if interpret is None:
        interpret = on_cpu()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    bq = min(bq, sq)
    while sq % bq != 0:
        bq //= 2
    bk = min(bk, sk)
    while sk % bk != 0:
        bk //= 2
    if scale is None:
        scale = 1.0 / (d**0.5)

    # fold batch into a leading grid axis; heads are their own axis so the
    # GQA index map can divide by the group size
    grid = (b, hq, sq // bq, sk // bk)
    kernel = partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, sk_total=sk, bq=bq, bk=bk,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, h, qi, ki: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, h, qi, ki: (bi, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
