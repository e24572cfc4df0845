"""Fused CED cipher kernel — blind + rotate in one HBM pass.

The paper's Cipher (§IV.C) runs EWO and PRT "simultaneously". On TPU that
means: read each input tile HBM→VMEM once, scale rows by the blinding
vector in VMEM (VPU elementwise), and write the tile to its *rotated*
destination — the rotation is carried by the output BlockSpec index map, so
it costs zero extra bandwidth (vs. a naive scale-pass + rotate-pass at 2×
traffic). Arithmetic intensity is 1 flop / 8 bytes (f64) — purely
memory-bound, so halving traffic halves cipher latency.

Tiles are square (b×b, b a multiple of the 128-lane for the TPU target).
The in-tile quarter-turn is composed from transposes and row reversals:
rot_cw(T) = flipud(T)ᵀ, rot_ccw(T) = flipud(Tᵀ), rot180(T) =
flipud(flipud(Tᵀ)ᵀ). Mosaic has no lowering for `rev`, so a row reversal
is b single-row copies between VMEM buffers — pure data movement, exact.

n not a multiple of the tile is zero-padded to one (blinding entries of
the pad are 1) and the rotated window cropped back out of the result.

Batch (DESIGN.md §3): a (B, n, n) stack adds a leading batch grid axis —
grid (B, nb, nb), each program ciphers one tile of one matrix; the
rotation index map acts on the tile coordinates only, the batch coordinate
passes through. All matrices in one call share the rotation degree k (the
index map is static in k); core.cipher.cipher_batch groups a mixed-k batch
into ≤ 3 launches.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import on_cpu


def _flip_rows(x, buf_ref, out_ref):
    """out_ref[...] = x[::-1] (x a (b, b) value; buf_ref scratch)."""
    b = x.shape[0]
    buf_ref[...] = x

    def copy_row(r, carry):
        out_ref[pl.ds(r, 1), :] = buf_ref[pl.ds(b - 1 - r, 1), :]
        return carry

    lax.fori_loop(0, b, copy_row, 0)


def _ced_kernel(m_ref, v_ref, o_ref, buf_ref, rev_ref, *, k: int, mode: str,
                growth_safe: bool):
    tile = m_ref[...]
    vcol = v_ref[...]  # (b, 1) slice of the blinding vector for these rows
    scaled = tile / vcol if mode == "ewd" else tile * vcol
    k = k % 4
    if k == 0:
        o_ref[...] = scaled
    elif growth_safe and k % 2 == 1:
        # odd rotation ∘ exchange flip = transpose, in-tile and in the
        # index map alike (core.cipher growth-safe relayout)
        o_ref[...] = scaled.T
    elif k == 1:
        _flip_rows(scaled, buf_ref, rev_ref)
        o_ref[...] = rev_ref[...].T
    elif k == 3:
        _flip_rows(scaled.T, buf_ref, o_ref)
    else:
        _flip_rows(scaled.T, buf_ref, rev_ref)
        _flip_rows(rev_ref[...].T, buf_ref, o_ref)


def _out_index_map(k: int, nb: int, *, batched: bool, growth_safe: bool):
    k = k % 4
    if growth_safe and k % 2 == 1:  # transpose: block (i,j) -> (j,i)
        def rot(i, j):
            return (j, i)
    elif k == 1:  # block (i,j) -> (j, nb-1-i)
        def rot(i, j):
            return (j, nb - 1 - i)
    elif k == 2:  # -> (nb-1-i, nb-1-j)
        def rot(i, j):
            return (nb - 1 - i, nb - 1 - j)
    elif k == 3:  # -> (nb-1-j, i)
        def rot(i, j):
            return (nb - 1 - j, i)
    else:
        def rot(i, j):
            return (i, j)
    if batched:
        return lambda b, i, j: (b, *rot(i, j))
    return rot


def _crop(x, n: int, k: int, growth_safe: bool):
    """The n×n window of a ciphered zero-padded tile grid that holds the
    rotated matrix (the pad sat bottom/right before the rotation)."""
    p = x.shape[-1]
    k = k % 4
    if k == 0 or (growth_safe and k % 2 == 1):
        rows, cols = slice(0, n), slice(0, n)
    elif k == 1:
        rows, cols = slice(0, n), slice(p - n, p)
    elif k == 2:
        rows, cols = slice(p - n, p), slice(p - n, p)
    else:
        rows, cols = slice(p - n, p), slice(0, n)
    return x[..., rows, cols]


@partial(jax.jit,
         static_argnames=("k", "mode", "block", "interpret", "growth_safe"))
def ced(
    m: jnp.ndarray,
    v: jnp.ndarray,
    k: int,
    *,
    mode: str = "ewd",
    block: int = 128,
    interpret: bool | None = None,
    growth_safe: bool = False,
) -> jnp.ndarray:
    """Fused Cipher: rot90_cw^k(EWO(m, v)) for (n, n) or (B, n, n).

    n not divisible by block is zero-padded to the tile grid and cropped
    back (module docstring). growth_safe composes odd rotations with the
    exchange flip (the composite is a transpose — still a single fused
    HBM pass, the index map just changes; core.cipher semantics,
    DESIGN.md §6.1). interpret=None runs the kernel through the Pallas
    interpreter on the CPU backend and compiled (Mosaic) everywhere else.
    """
    if interpret is None:
        interpret = on_cpu()
    n = m.shape[-1]
    batched = m.ndim == 3
    v = v.reshape(*m.shape[:-1], 1).astype(m.dtype)
    pad = -n % block
    if pad:
        lead = [(0, 0)] if batched else []
        m = jnp.pad(m, lead + [(0, pad), (0, pad)])
        v = jnp.pad(v, lead + [(0, pad), (0, 0)], constant_values=1)
    p = n + pad
    nb = p // block
    if batched:
        grid = (m.shape[0], nb, nb)
        tile_spec = pl.BlockSpec((pl.squeezed, block, block),
                                 lambda b, i, j: (b, i, j))
        v_spec = pl.BlockSpec((pl.squeezed, block, 1),
                              lambda b, i, j: (b, i, 0))
    else:
        grid = (nb, nb)
        tile_spec = pl.BlockSpec((block, block), lambda i, j: (i, j))
        v_spec = pl.BlockSpec((block, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        partial(_ced_kernel, k=k, mode=mode, growth_safe=growth_safe),
        out_shape=jax.ShapeDtypeStruct(m.shape, m.dtype),
        grid=grid,
        in_specs=[tile_spec, v_spec],
        out_specs=pl.BlockSpec(
            tile_spec.block_shape,
            _out_index_map(k, nb, batched=batched, growth_safe=growth_safe),
        ),
        scratch_shapes=[pltpu.VMEM((block, block), m.dtype)] * 2,
        interpret=interpret,
    )(m, v)
    return _crop(out, n, k, growth_safe) if pad else out
