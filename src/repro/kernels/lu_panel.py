"""Panel LU kernel — unblocked no-pivot factorization of one b×b tile in VMEM.

This is the sequential bottleneck of blocked LU: everything else (TRSM,
Schur GEMM) is MXU-bound, but the panel is a b-step dependent elimination.
Keeping the whole panel resident in VMEM (b ≤ 256 ⇒ ≤ 512 KiB f64) and
expressing each elimination step as masked row/column reductions keeps the
inner loop on the VPU without dynamic gathers (TPU-unfriendly).

Output is the compact form (strict-lower multipliers + U), matching
ref.lu_panel_ref; callers split with tril/triu.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.runtime import on_cpu


def _lu_panel_kernel(x_ref, o_ref, *, acc_dtype=None):
    a = x_ref[...]
    if acc_dtype is not None:  # mixed variant: eliminate wide, store narrow
        a = a.astype(acc_dtype)
    squeeze = a.ndim == 3  # batched launch: one (1, b, b) tile per program
    if squeeze:
        a = a[0]
    b = a.shape[0]
    # 2D iota (TPU requires >= 2D); rows[i,j] = i, cols[i,j] = j
    rows = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = lax.broadcasted_iota(jnp.int32, (b, b), 1)

    def body(k, a):
        # pivot = a[k, k]; urow = a[k, :] masked to cols > k;
        # lcol = a[:, k] / pivot masked to rows > k — all as masked sums,
        # no dynamic slicing.
        pivot = jnp.sum(jnp.where((rows == k) & (cols == k), a, 0.0))
        urow = jnp.sum(jnp.where(rows == k, a, 0.0), axis=0)  # (b,)
        acol = jnp.sum(jnp.where(cols == k, a, 0.0), axis=1)  # (b,)
        lcol = jnp.where(jnp.arange(b) > k, acol / pivot, 0.0)
        urow_right = jnp.where(jnp.arange(b) > k, urow, 0.0)
        a = a - lcol[:, None] * urow_right[None, :]
        # store multipliers into column k (rows > k)
        return jnp.where((cols == k) & (rows > k), lcol[:, None], a)

    out = lax.fori_loop(0, b, body, a).astype(o_ref.dtype)
    o_ref[...] = out[None] if squeeze else out


@partial(jax.jit, static_argnames=("interpret", "acc_dtype"))
def lu_panel_compact(x: jnp.ndarray, *, interpret: bool | None = None,
                     acc_dtype=None) -> jnp.ndarray:
    """Compact LU of one panel, or of a (B, b, b) stack via a batch grid
    axis (one panel per program instance — DESIGN.md §3). acc_dtype
    selects the mixed variant: the b-step elimination runs in the wider
    dtype in VMEM and the compact form stores at x.dtype (DESIGN.md §6.4;
    f64 accumulation needs a f64-capable backend or interpret mode)."""
    if interpret is None:
        interpret = on_cpu()
    b = x.shape[-1]
    kern = partial(_lu_panel_kernel, acc_dtype=acc_dtype)
    if x.ndim == 3:
        B = x.shape[0]
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((B, b, b), x.dtype),
            grid=(B,),
            in_specs=[pl.BlockSpec((1, b, b), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, b, b), lambda i: (i, 0, 0)),
            interpret=interpret,
        )(x)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b, b), x.dtype),
        in_specs=[pl.BlockSpec((b, b), lambda: (0, 0))],
        out_specs=pl.BlockSpec((b, b), lambda: (0, 0)),
        interpret=interpret,
    )(x)
