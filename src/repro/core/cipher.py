"""Cipher — Composite Element Distortion (CED), paper §IV.C.

CED = EWO ∘ PRT:

  * EWO (element-wise obfuscation): row i is divided (EWD) or multiplied
    (EWM) by blinding entry v_i.
  * PRT obfuscation: the scaled matrix is rotated by k ∈ {1,2,3} clockwise
    quarter-turns, k = Rotate(Ψ) = (⌊Ψ⌋ mod 3) + 1.

Both are applied in a single pass ("run simultaneously", §IV.C): the fused
Pallas kernel (kernels/ced.py) reads each input tile once, scales it in
VMEM, and writes it to the rotated destination via the BlockSpec index map —
the rotation costs nothing beyond addressing. This module is the public API;
it dispatches to the fused kernel or a pure-jnp path.

Determinant bookkeeping (used by Decipher):

    EWD:  det(X) = det(M) / Ψ · s      EWM:  det(X) = det(M) · Ψ · s

with s = rotation_sign(n, k) (growth_safe_sign(n, k) when the growth-safe
relayout is on).

Growth control (DESIGN.md §6) — two composable, det-tracked devices that
keep the no-pivot LU's element growth fp32-survivable:

  * growth_safe relayout: odd rotations (k ∈ {1, 3}) map the main diagonal
    onto the anti-diagonal, turning a diagonally dominant input into an
    anti-diagonally dominant ciphertext whose leading principal minors are
    structurally tiny — the no-pivot schedule then grows elements by ~n
    regardless of any scaling. Composing the odd rotation with an exchange
    flip (rot¹(A)·J = J·rot³(A) = Aᵀ) keeps the dominance structure on the
    diagonal; the flip's det sign is folded into Decipher exactly.
  * equilibrate(): two-sided power-of-two row/col scaling of the
    ciphertext. Scales are exact in any binary float format, so the
    transform is lossless; the log-det correction Σ log r_i + Σ log c_j is
    replayable bookkeeping the client folds into Decipher, like the
    padding draw.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .keygen import Key
from .prt import rot90_cw, rotate_degree
from .seed import Seed

Mode = Literal["ewd", "ewm"]


@dataclass(frozen=True)
class CipherMeta:
    """Public-side record of how M was ciphered (client keeps this)."""

    mode: Mode
    rotate_k: int  # quarter-turns applied
    n: int
    #: growth-safe relayout: odd rotations composed with an exchange flip
    #: (the ciphertext is the transposed, not rotated, scaled matrix);
    #: Decipher must use growth_safe_sign instead of rotation_sign
    flipped: bool = False


def ewo(m: jnp.ndarray, v: jnp.ndarray, mode: Mode) -> jnp.ndarray:
    """Element-wise obfuscation: row-scale by the blinding vector."""
    v = v.reshape(-1, 1).astype(m.dtype)
    if mode == "ewd":
        return m / v
    if mode == "ewm":
        return m * v
    raise ValueError(f"unknown EWO mode: {mode!r}")


def _flip_rotated(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exchange-flip that undoes an odd rotation's diagonal→anti-diagonal
    map: column flip after k=1, row flip before-equivalent after k=3. Both
    compositions equal the transpose of the unrotated input; implemented
    as the flip so kernel-produced rotations compose identically."""
    if k % 2 == 0:
        return x
    if k % 4 == 1:
        return x[..., :, ::-1]
    return x[..., ::-1, :]


def cipher(
    m: jnp.ndarray,
    key: Key,
    seed: Seed,
    *,
    mode: Mode = "ewd",
    growth_safe: bool = False,
    use_kernel: bool = False,
) -> tuple[jnp.ndarray, CipherMeta]:
    """Cipher(K, M) → X. Returns the ciphertext and the (client-held) meta.

    use_kernel selects the fused Pallas CED kernel (compiled on a TPU, the
    Pallas interpreter on the CPU). The jnp path is the oracle.

    growth_safe composes odd rotations with a det-tracked exchange flip
    (module docstring / DESIGN.md §6.1) so the no-pivot LU's element
    growth stays fp32-survivable; meta.flipped records it for Decipher.
    """
    n = int(m.shape[0])
    if key.v.shape[0] != n:
        raise ValueError(f"blinding vector length {key.v.shape[0]} != n {n}")
    k = rotate_degree(seed.psi)
    if use_kernel:
        from repro.kernels import ops as kops

        x = kops.ced(m, jnp.asarray(key.v), k, mode=mode,
                     growth_safe=growth_safe)
    else:
        x = rot90_cw(ewo(m, jnp.asarray(key.v), mode), k)
        if growth_safe:
            x = _flip_rotated(x, k)
    return x, CipherMeta(mode=mode, rotate_k=k, n=n,
                         flipped=growth_safe and k % 2 == 1)


@partial(jax.jit, static_argnames=("mode", "growth_safe"))
def _cipher_batch_jnp(m: jnp.ndarray, v: jnp.ndarray, ks: jnp.ndarray,
                      *, mode: Mode, growth_safe: bool = False) -> jnp.ndarray:
    """Batched CED, pure jnp: per-matrix blinding vector AND rotation degree.

    The per-example quarter-turn count is data (each matrix has its own
    seed), so the rotation is a vmapped lax.switch over the four turn
    counts — XLA lowers it to selects over cheap relayouts; still zero
    flops beyond the blinding scale. growth_safe swaps the odd-rotation
    branches for their flip compositions (= transpose; see cipher()).
    """

    if growth_safe:
        branches = [
            lambda a: a,
            lambda a: a.T,  # rot¹ then column flip
            lambda a: jnp.rot90(a, k=-2, axes=(0, 1)),
            lambda a: a.T,  # rot³ then row flip
        ]
    else:
        branches = [
            lambda a: a,
            lambda a: jnp.rot90(a, k=-1, axes=(0, 1)),
            lambda a: jnp.rot90(a, k=-2, axes=(0, 1)),
            lambda a: jnp.rot90(a, k=-3, axes=(0, 1)),
        ]

    def one(mi, vi, ki):
        return lax.switch(ki % 4, branches, ewo(mi, vi, mode))

    return jax.vmap(one)(m, v, ks)


def cipher_batch(
    m: jnp.ndarray,
    key_vs: np.ndarray | jnp.ndarray,
    seeds: list[Seed],
    *,
    mode: Mode = "ewd",
    growth_safe: bool = False,
    use_kernel: bool = False,
) -> tuple[jnp.ndarray, list[CipherMeta]]:
    """Batched Cipher: (B, n, n) stack + (B, n) stacked blinding vectors.

    Pure-jnp path is one jitted vmapped program. The Pallas path groups the
    batch by rotation degree (the kernel's output index map is static in k)
    and launches one batched-grid kernel per group — at most 3 launches for
    any B.
    """
    B, n = int(m.shape[0]), int(m.shape[-1])
    if len(seeds) != B:
        raise ValueError(f"{len(seeds)} seeds for batch of {B}")
    v = jnp.asarray(key_vs, dtype=m.dtype)
    if v.shape != (B, n):
        raise ValueError(f"blinding stack shape {v.shape} != {(B, n)}")
    ks = np.array([rotate_degree(s.psi) for s in seeds], dtype=np.int32)
    metas = [
        CipherMeta(mode=mode, rotate_k=int(k), n=n,
                   flipped=growth_safe and int(k) % 2 == 1)
        for k in ks
    ]
    if use_kernel:
        from repro.kernels import ops as kops

        x = jnp.zeros_like(m)
        for k in sorted(set(ks.tolist())):
            idx = np.nonzero(ks == k)[0]
            xk = kops.ced(m[idx], v[idx], int(k), mode=mode,
                          growth_safe=growth_safe)
            x = x.at[idx].set(xk)
    else:
        x = _cipher_batch_jnp(m, v, jnp.asarray(ks), mode=mode,
                              growth_safe=growth_safe)
    return x, metas


def equilibrate(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two-sided power-of-two equilibration of a ciphertext (DESIGN.md §6.2).

    Scales row i by r_i = 2^{-round(log2 max_j |x_ij|)} and then column j
    by c_j = 2^{-round(log2 max_i |(r x)_ij|)}, driving every row/col max
    magnitude into [2^{-1/2}, 2^{1/2}]. Powers of two make the scaling
    EXACT in any binary float format — the transform is lossless and fully
    replayable from the ciphertext itself (no extra secret state).

    Returns (x_eq, log2_scale) with log2_scale the INTEGER
    Σ log2 r_i + Σ log2 c_j (int32 — exact for any n, where a float32 sum
    of n log terms would round), so

        log|det x| = log|det x_eq| − log2_scale · ln 2

    — the correction Decipher folds in (`decipher(..., log2_scale=…)`,
    with the ln 2 multiply done in float64 on the host). Batch-aware:
    (..., n, n) input gives (...,)-shaped log2_scale. All-zero rows /
    columns scale by 1 (their max is clamped), leaving det = 0 alone.
    """
    info = jnp.finfo(x.dtype)
    bias = info.maxexp - 1
    bits = jnp.dtype(f"int{info.bits}")

    def pow2_exp(maxabs):
        # integer exponent of the power of two nearest the magnitude;
        # clamp 0 → exponent 0 (scale 1), and stay in the normal range
        safe = jnp.where(maxabs > 0, maxabs, 1.0)
        e = jnp.round(jnp.log2(safe)).astype(jnp.int32)
        return jnp.clip(e, 1 - bias, bias - 1)

    def pow2(e):
        # 2**e from its exponent bits: XLA's exp2 is not exact at every
        # integer (off by up to 3e-6 on a TPU, 1e-6 on the CPU)
        return lax.bitcast_convert_type(
            (e.astype(bits) + bias) << info.nmant, x.dtype)

    e_r = pow2_exp(jnp.max(jnp.abs(x), axis=-1))
    x = x * pow2(-e_r)[..., :, None]
    e_c = pow2_exp(jnp.max(jnp.abs(x), axis=-2))
    x = x * pow2(-e_c)[..., None, :]
    log2_scale = -(jnp.sum(e_r, axis=-1) + jnp.sum(e_c, axis=-1))
    return x, log2_scale


def cipher_flops(n: int) -> int:
    """Cipher cost model — paper Table I claims n² flops for our protocol.

    One multiply (or divide) per element; the rotation is pure data
    movement (0 flops).
    """
    return n * n
