"""Decipher — paper §IV.F: recover det(M) from the LU of the ciphertext.

    det(X) = Π_i L_ii U_ii                      (from the servers' LU)
    EWD:  det(M) = det(X) · sign · Ψ
    EWM:  det(M) = det(X) · sign / Ψ

The correct rotation sign is ((-1)^{⌊n/2⌋})^k (PRT); the paper's literal
formula uses (-1)^k, valid only for n ≡ 2,3 (mod 4) — both are provided
(faithful=True reproduces the paper, default applies the theorem's own
case split). When the cipher used the growth-safe relayout
(meta.flipped — DESIGN.md §6.1) the sign law is growth_safe_sign instead.

All arithmetic is done in (sign, log|·|) space to survive large n; the
log-sum over the factor diagonals is taken on the host in float64
(core.lu.slogdet_from_lu) — a single float32 cannot represent
log|det| ≈ 1000 to the 1e-4 absolute accuracy float32 protocol runs
target. See DESIGN.md §1.1, §6.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from .cipher import CipherMeta
from .lu import slogdet_from_lu
from .prt import growth_safe_sign, rotation_sign, rotation_sign_paper
from .seed import Seed

_LN2 = float(np.log(2.0))

#: largest log|det| whose exp still fits a float64 — beyond it .value
#: would silently return inf (the satellite bug this guards against)
_MAX_VALUE_LOGABS = float(np.log(np.finfo(np.float64).max))

#: dtype-aware default relative det tolerance for allclose(): the
#: float64 figure matches the protocol's historic rtol; the float32
#: figure is the acceptance bar of the f32 protocol path (DESIGN.md §6)
_DEFAULT_RTOL = {"float64": 1e-8, "float32": 1e-4, "float16": 1e-2,
                 "bfloat16": 1e-1}


@dataclass(frozen=True)
class Determinant:
    """Determinant in overflow-safe (sign, log|det|) form.

    `dtype` records the compute dtype of the factorization that produced
    this determinant — it selects allclose()'s default tolerance. `logabs`
    itself is always a host float64 (summed on the host from the factor
    diagonals), so the log-space value is meaningful beyond the compute
    dtype's own resolution.
    """

    sign: float
    logabs: float
    dtype: str = "float64"

    @property
    def value(self) -> float:
        """det as a plain float — raises OverflowError when it does not fit.

        log|det| > ~709.78 means the determinant exceeds the float64
        range; silently returning inf (the pre-fix behavior) corrupted
        every downstream comparison. Work in (sign, logabs) space instead:
        this property is for small matrices and display only.
        """
        if self.logabs > _MAX_VALUE_LOGABS:
            raise OverflowError(
                f"|det| = exp({self.logabs:.1f}) overflows float64; compare "
                "in (sign, logabs) space instead of .value"
            )
        return float(self.sign * np.exp(self.logabs))

    def is_zero(self, atol_logabs: float = -np.inf) -> bool:
        """True when this determinant is (numerically) zero: an exact zero
        sign, a -inf logabs, or logabs at/below `atol_logabs`."""
        return self.sign == 0 or self.logabs == float("-inf") \
            or self.logabs <= atol_logabs

    def allclose(
        self,
        other: "Determinant",
        rtol: float | None = None,
        atol: float = 0.0,
        zero_logabs: float = -np.inf,
    ) -> bool:
        """Relative-determinant comparison, done correctly in log space.

        Two determinants agree to relative error rtol iff
        |Δ logabs| ≤ log1p(rtol); `atol` adds extra log-space slack. The
        pre-fix implementation applied rtol to logabs ITSELF
        (np.isclose(logabs, …, rtol)), so the tolerated relative det
        error grew with |log det| — wildly loose at n = 1024 and
        needlessly tight near |det| ≈ 1.

        rtol=None selects the dtype-aware default (1e-8 for float64
        computes, 1e-4 for float32) from the coarser of the two operands.

        Zero handling: determinants that are zero (sign 0, logabs -inf,
        or logabs ≤ zero_logabs) compare equal to each other regardless
        of sign — ±0 must not be a sign mismatch; a zero never equals a
        nonzero. Otherwise differing signs are a mismatch.
        """
        if rtol is None:
            rtols = [_DEFAULT_RTOL.get(d, 1e-8) for d in (self.dtype,
                                                          other.dtype)]
            rtol = max(rtols)
        a_zero = self.is_zero(zero_logabs)
        b_zero = other.is_zero(zero_logabs)
        if a_zero or b_zero:
            return a_zero and b_zero
        if self.sign != other.sign:
            return False
        return bool(
            abs(self.logabs - other.logabs) <= float(np.log1p(rtol)) + atol
        )

    def to_bytes(self) -> bytes:
        """Serialize with the role-split wire codec (repro.api.wire) —
        (sign, logabs) round-trip bit-exactly, ±inf included."""
        from repro.api import wire

        return wire.encode(
            "Determinant",
            {"sign": float(self.sign), "logabs": float(self.logabs),
             "dtype": self.dtype},
            {},
        )

    @classmethod
    def _from_wire(cls, scalars, arrays):
        return cls(sign=scalars["sign"], logabs=scalars["logabs"],
                   dtype=scalars["dtype"])

    @classmethod
    def from_bytes(cls, data: bytes) -> "Determinant":
        from repro.api import wire

        kind, scalars, arrays = wire.decode(data)
        if kind != "Determinant":
            raise wire.WireError(f"expected Determinant frame, got {kind!r}")
        return cls._from_wire(scalars, arrays)


def _assemble(
    sign_x: float,
    logabs_x: float,
    seed: Seed,
    meta: CipherMeta,
    *,
    faithful: bool,
    log2_scale: float,
    dtype: str,
) -> Determinant:
    """Shared Decipher bookkeeping: relayout sign, equilibration
    correction, Ψ factor — all in host float64."""
    if faithful:
        s = rotation_sign_paper(meta.rotate_k)
    elif meta.flipped:
        s = growth_safe_sign(meta.n, meta.rotate_k)
    else:
        s = rotation_sign(meta.n, meta.rotate_k)
    log_psi = float(np.log(seed.psi))
    logabs = logabs_x - float(log2_scale) * _LN2
    if meta.mode == "ewd":
        return Determinant(sign=sign_x * s, logabs=logabs + log_psi,
                           dtype=dtype)
    if meta.mode == "ewm":
        return Determinant(sign=sign_x * s, logabs=logabs - log_psi,
                           dtype=dtype)
    raise ValueError(f"unknown mode {meta.mode!r}")


def decipher(
    seed: Seed,
    meta: CipherMeta,
    l: jnp.ndarray,
    u: jnp.ndarray,
    *,
    faithful: bool = False,
    log2_scale: float = 0.0,
) -> Determinant:
    """Decipher(Ψ, L, U) → det(M).

    log2_scale: the equilibration exponent sum returned by
    core.cipher.equilibrate (0 when the ciphertext was not equilibrated).
    """
    sign_x, logabs_x = slogdet_from_lu(l, u)
    return _assemble(
        float(sign_x), float(logabs_x), seed, meta,
        faithful=faithful, log2_scale=log2_scale, dtype=str(l.dtype),
    )


def decipher_batch(
    seeds: list[Seed],
    metas: list[CipherMeta],
    l: jnp.ndarray,
    u: jnp.ndarray,
    *,
    faithful: bool = False,
    log2_scale: np.ndarray | None = None,
) -> list[Determinant]:
    """Batched Decipher: (B, n, n) LU factors → one Determinant per matrix.

    The (B, n) diagonals come to the host once; their float64 log-sum
    and the per-matrix Ψ/rotation-sign bookkeeping run there.
    log2_scale: per-matrix equilibration exponents, shape (B,).
    """
    sign_x, logabs_x = slogdet_from_lu(l, u)
    dtype = str(l.dtype)
    if log2_scale is None:
        log2_scale = np.zeros(len(seeds))
    log2_scale = np.asarray(log2_scale)
    return [
        _assemble(
            float(sign_x[i]), float(logabs_x[i]), seed, meta,
            faithful=faithful, log2_scale=float(log2_scale[i]), dtype=dtype,
        )
        for i, (seed, meta) in enumerate(zip(seeds, metas, strict=True))
    ]


def decipher_flops(n: int) -> int:
    """Paper Table I Decipher cost: 2n (n diagonal products + n-ish for the
    running product/log accumulation)."""
    return 2 * n
