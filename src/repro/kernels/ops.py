"""Jit'd public wrappers for every Pallas kernel (the API the rest of the
framework calls). The backend picks the execution mode: the Pallas
interpreter on the CPU, the compiled Mosaic kernel on a TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from .ced import ced as _ced
from .flash_attn import flash_attention as _flash
from .gemm import schur_update as _schur
from .lu_panel import lu_panel_compact as _lu_panel_compact
from .trsm import trsm_lower as _trsm_lower
from .trsm import trsm_upper_right as _trsm_upper_right


def ced(m, v, k, *, mode="ewd", block=128, growth_safe=False):
    """Fused CED cipher: rot90_cw^k(EWO(m, v)); growth_safe composes odd
    rotations with the exchange flip (DESIGN.md §6.1)."""
    return _ced(m, v, k, mode=mode, block=block, growth_safe=growth_safe)


def lu_panel(x, *, acc_dtype=None):
    """Panel LU -> (L unit-lower, U upper); batched over a leading dim.
    acc_dtype selects the mixed (wide-accumulate) variant."""
    compact = _lu_panel_compact(x, acc_dtype=acc_dtype)
    n = x.shape[-1]
    l = jnp.tril(compact, -1) + jnp.eye(n, dtype=x.dtype)
    u = jnp.triu(compact)
    return l, u


def trsm_lower(l, b, *, acc_dtype=None):
    """X = L^{-1} B (L unit lower)."""
    return _trsm_lower(l, b, acc_dtype=acc_dtype)


def trsm_upper_right(u, b, *, acc_dtype=None):
    """Z = B U^{-1} (U upper)."""
    return _trsm_upper_right(u, b, acc_dtype=acc_dtype)


def schur_update(c, a, b, *, acc_dtype=None, **tiles):
    """C - A @ B; acc_dtype overrides the accumulation dtype."""
    return _schur(c, a, b, acc_dtype=acc_dtype, **tiles)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    bq=128, bk=128):
    """Blockwise online-softmax attention (GQA-aware)."""
    return _flash(
        q, k, v, causal=causal, window=window, scale=scale, bq=bq, bk=bk,
    )
