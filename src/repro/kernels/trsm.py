"""Block triangular-solve kernels.

trsm_lower:        X = L^{-1} B   (L unit-lower b×b; B b×m, tiled over cols)
trsm_upper_right:  Z = B U^{-1}   (U upper b×b;      B m×b, tiled over rows)

The triangular factor stays resident in VMEM across the grid; each grid
step solves one column (row) tile of B by masked forward (backward)
elimination — the same gather-free masking idiom as lu_panel. Elimination
steps are rank-1 updates (VPU) over a tile; the O(b²·m) work is dominated
by the rank-1 broadcasts, which vectorize over the m-tile lane dimension.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.runtime import on_cpu


def _trsm_lower_kernel(l_ref, b_ref, o_ref, *, acc_dtype=None):
    l = l_ref[...]
    x = b_ref[...]
    if acc_dtype is not None:  # mixed variant: solve wide, store narrow
        l, x = l.astype(acc_dtype), x.astype(acc_dtype)
    squeeze = l.ndim == 3  # batched launch: (1, n, n) / (1, n, cb) blocks
    if squeeze:
        l, x = l[0], x[0]
    b = l.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = lax.broadcasted_iota(jnp.int32, (b, b), 1)

    def body(k, x):
        # row_k of the current solution; eliminate it from rows > k
        xrows = lax.broadcasted_iota(jnp.int32, x.shape, 0)
        row_k = jnp.sum(jnp.where(xrows == k, x, 0.0), axis=0)  # (m,)
        lcol = jnp.sum(jnp.where(cols == k, l, 0.0), axis=1)  # (b,)
        lcol = jnp.where(jnp.arange(b) > k, lcol, 0.0)
        return x - lcol[:, None] * row_k[None, :]

    out = lax.fori_loop(0, b, body, x).astype(o_ref.dtype)
    o_ref[...] = out[None] if squeeze else out


def _trsm_upper_right_kernel(u_ref, b_ref, o_ref, *, acc_dtype=None):
    u = u_ref[...]
    x = b_ref[...]
    if acc_dtype is not None:  # mixed variant: solve wide, store narrow
        u, x = u.astype(acc_dtype), x.astype(acc_dtype)
    squeeze = u.ndim == 3  # batched launch: (1, n, n) / (1, rb, n) blocks
    if squeeze:
        u, x = u[0], x[0]
    b = u.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = lax.broadcasted_iota(jnp.int32, (b, b), 1)

    def body(k, x):
        # scale column k by 1/U_kk, then eliminate from columns > k
        ukk = jnp.sum(jnp.where((rows == k) & (cols == k), u, 0.0))
        col_k = jnp.sum(jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 1) == k, x, 0.0), axis=1) / ukk
        urow = jnp.sum(jnp.where(rows == k, u, 0.0), axis=0)  # (b,)
        urow = jnp.where(jnp.arange(b) > k, urow, 0.0)
        x = x - col_k[:, None] * urow[None, :]
        # write the scaled column back into position k
        iscol = lax.broadcasted_iota(jnp.int32, x.shape, 1) == k
        return jnp.where(iscol, col_k[:, None], x)

    out = lax.fori_loop(0, b, body, x).astype(o_ref.dtype)
    o_ref[...] = out[None] if squeeze else out


@partial(jax.jit, static_argnames=("col_block", "interpret", "acc_dtype"))
def trsm_lower(
    l: jnp.ndarray, b: jnp.ndarray, *, col_block: int = 256,
    interpret: bool | None = None, acc_dtype=None,
) -> jnp.ndarray:
    """Solve L X = B for X; grid over column tiles of B. A (B, n, n) /
    (B, n, m) stack adds a leading batch grid axis (DESIGN.md §3).
    acc_dtype selects the mixed variant: the elimination runs in the wider
    dtype in VMEM, the output tile stores at b.dtype (DESIGN.md §6.4)."""
    if interpret is None:
        interpret = on_cpu()
    n, m = b.shape[-2:]
    cb = min(col_block, m)
    while m % cb != 0:
        cb //= 2
    kern = partial(_trsm_lower_kernel, acc_dtype=acc_dtype)
    if b.ndim == 3:
        batch = b.shape[0]
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((batch, n, m), b.dtype),
            grid=(batch, m // cb),
            in_specs=[
                pl.BlockSpec((1, n, n), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, n, cb), lambda i, j: (i, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, n, cb), lambda i, j: (i, 0, j)),
            interpret=interpret,
        )(l, b)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n, m), b.dtype),
        grid=(m // cb,),
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),
            pl.BlockSpec((n, cb), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n, cb), lambda j: (0, j)),
        interpret=interpret,
    )(l, b)


@partial(jax.jit, static_argnames=("row_block", "interpret", "acc_dtype"))
def trsm_upper_right(
    u: jnp.ndarray, b: jnp.ndarray, *, row_block: int = 256,
    interpret: bool | None = None, acc_dtype=None,
) -> jnp.ndarray:
    """Solve Z U = B for Z; grid over row tiles of B. A (B, n, n) /
    (B, m, n) stack adds a leading batch grid axis (DESIGN.md §3).
    acc_dtype: mixed variant, as trsm_lower."""
    if interpret is None:
        interpret = on_cpu()
    m, n = b.shape[-2:]
    rb = min(row_block, m)
    while m % rb != 0:
        rb //= 2
    kern = partial(_trsm_upper_right_kernel, acc_dtype=acc_dtype)
    if b.ndim == 3:
        batch = b.shape[0]
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((batch, m, n), b.dtype),
            grid=(batch, m // rb),
            in_specs=[
                pl.BlockSpec((1, n, n), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, rb, n), lambda i, j: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, rb, n), lambda i, j: (i, j, 0)),
            interpret=interpret,
        )(u, b)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((m, n), b.dtype),
        grid=(m // rb,),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((rb, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, n), lambda i: (i, 0)),
        interpret=interpret,
    )(u, b)
