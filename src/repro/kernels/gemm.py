"""Schur-complement GEMM kernel: C ← C − A·B, the O(n³) hot spot of
blocked LU (≥ ~90% of Parallelize flops for nb ≥ 4).

Classic three-loop Pallas matmul: grid (i, j, k) with the (i, j) output
tile revisited across the contraction index k (k innermost ⇒ the out tile
stays resident in VMEM; Mosaic keeps the accumulator on-chip between grid
steps). MXU-aligned 128× tiles; accumulation in the output dtype's widened
form (f32 for bf16 inputs) via preferred_element_type.

Batch (DESIGN.md §3): (B, m, k)·(B, k, n) stacks prepend a batch grid axis
— grid (B, i, j, k), one independent accumulator walk per matrix. The
contraction index stays innermost so the VMEM-residency argument is
unchanged.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import on_cpu


def _schur_kernel(c_ref, a_ref, b_ref, o_ref, *, acc_dtype):
    # contraction index is the innermost grid axis: 2 for (i,j,k) grids,
    # 3 for batched (b,i,j,k) grids — equal to the block rank
    k = pl.program_id(c_ref.ndim)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = c_ref[...]

    o_ref[...] -= jnp.matmul(
        a_ref[...], b_ref[...], preferred_element_type=acc_dtype
    ).astype(o_ref.dtype)


def _fit_block(n: int, want: int) -> int:
    b = min(want, n)
    while n % b != 0:
        b //= 2
    return max(b, 1)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "acc_dtype"))
def schur_update(
    c: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    acc_dtype=None,
) -> jnp.ndarray:
    """C − A @ B with (M,K)@(K,N) tiling; batched over a leading stack dim.

    acc_dtype: accumulation dtype override. Default (None) widens bf16/f16
    inputs to f32 and keeps f32/f64 inputs at their own dtype; passing
    jnp.float64 on f32 inputs selects the "mixed" variant (DESIGN.md §6.4)
    — each tile's contraction accumulates wide, the output stores narrow.
    f64 accumulation needs a backend with f64 support (CPU/GPU, or
    interpret mode); TPU Mosaic callers should stay ≤ f32.
    """
    if interpret is None:
        interpret = on_cpu()
    m, kdim = a.shape[-2:]
    n = b.shape[-1]
    bm = _fit_block(m, bm)
    bn = _fit_block(n, bn)
    bk = _fit_block(kdim, bk)
    if acc_dtype is None:
        acc_dtype = (jnp.float32 if c.dtype in (jnp.bfloat16, jnp.float16)
                     else c.dtype)
    batched = c.ndim == 3
    if batched:
        B = c.shape[0]
        grid = (B, m // bm, n // bn, kdim // bk)
        in_specs = [
            pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, i, j)),
            pl.BlockSpec((1, bm, bk), lambda p, i, j, k: (p, i, k)),
            pl.BlockSpec((1, bk, bn), lambda p, i, j, k: (p, k, j)),
        ]
        out_specs = pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, i, j))
        out_shape = jax.ShapeDtypeStruct((B, m, n), c.dtype)
    else:
        grid = (m // bm, n // bn, kdim // bk)
        in_specs = [
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ]
        out_specs = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((m, n), c.dtype)
    return pl.pallas_call(
        partial(_schur_kernel, acc_dtype=acc_dtype),
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
    )(c, a, b)
