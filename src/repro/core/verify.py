"""Result authentication — paper §IV.E: Q1 (prior work), Q2, Q3, ε(N) —
plus per-server tamper LOCALIZATION (DESIGN.md §4).

Q1 (Gao & Yu):  vector residual   L(U r) − X r
Q2 (paper):     scalar residual   (Lᵀr)ᵀ(U r) − (rᵀ X) r
Q3 (paper):     deterministic     Σ_i |Σ_{j≤i} L_ij U_ji − x_ii|

All avoid matrix–matrix products: Q1/Q2 are matrix–vector (O(n²)), Q3 reads
only the diagonal band terms it needs (O(n²) for the inner products over
j ≤ i, or O(n) if L/U rows are streamed during integration).

Every check is batch-aware (DESIGN.md §3): with (..., n, n) factors and
(..., n) probes the residuals come back per-matrix — a tampered matrix
inside a batch is flagged individually, never averaged away.

ε(N): multi-server block pipelining + no-pivot elimination accumulate
rounding; the paper validates |Q| ≤ ε(N) with ε growing in N. We model
ε(N) = c · (1 + N) · n · u · scale(X) with u the unit roundoff of the
compute dtype and scale(X) = ‖X‖_F / √n (RMS magnitude) — first-order error
analysis of an n-step elimination distributed over N pipeline stages.
`authenticate` additionally widens ε by the *observed element growth*
max|U| / max|X| (clamped ≥ 1): the no-pivot schedule's rounding is
proportional to the largest intermediate the elimination produced, which
the returned factors expose. The growth term is what makes the threshold
dtype-portable — an equilibrated float32 ciphertext whose factorization
grew by g carries residual ~g·n·u, and a scale-only model either
false-alarms on it (scale clamps to 1) or needs a dtype-tuned fudge
(DESIGN.md §6.3).

How much widening a server may claim depends on whether the residual can
SEE the factors the growth is measured from. For the secret-probed Q1/Q2
residuals inflation is self-defeating: huge planted entries in U blow up
U·r with probability 1 over the client-held probe, so a result that
passes the widened check has small backward error relative to its own
factors — an exact factorization of a nearby matrix, whose determinant
is the right answer anyway. The diagonal-only Q3 residual has no such
property: a pair of huge strictly-upper entries U[j,i], U[j',i] chosen so
L[i,j]·U[j,i] + L[i,j']·U[j',i] = 0 cancels out of every diagonal term,
inflating max|U| (and hence ε) by an arbitrary factor G while leaving the
residual untouched — the server could then bias diagonal entries by
~ε·G and still verify. Q3/Q3-literal therefore clamp the widening at
`q3_growth_cap(n)` = c·n: the acceptance tolerance stays a client-chosen
bound, and honest runs keep ≥ 25× margin under it in every supported
configuration (the only config that needs widening at all — equilibrated
scale ≈ 1 with the growth-safe relayout disabled — needs ~10× at
n ≤ 256; see tests/test_precision.py and DESIGN.md §6.3).

Localization: Algorithm 3 gives server i ownership of block row i of both
factors, so a verification failure is *attributable*. Blocking the Q1
residual vector by server — rows [i·b, (i+1)·b) — names the culprit: a
corruption anywhere in server k's strips perturbs residual rows of block k
(L strip: directly; U strip: through (Ur)_k, which L's lower-triangular
support propagates only to rows ≥ k·b). The FIRST block with residual
above ε(N) is therefore the faulty server, and blocks above it are clean —
exactly the invariant the recovery scheduler (distrib/recovery.py) needs
to recompute a single strip from verified upstream rows. Q3's diagonal
terms attribute to the *diagonal owner* instead (an off-diagonal U tamper
in row k surfaces at column c's diagonal, implicating server ⌊c/b⌋), so
localization always uses the Q1 form regardless of the accept/reject
method; `per_server_residuals(..., method="q3")` stays available for
diagnostics.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .lu import precise_einsum as _einsum


def q1(l: jnp.ndarray, u: jnp.ndarray, x: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Gao & Yu's vector check: L(Ur) − Xr. Zero vector iff LU consistent."""
    ur = _einsum("...ij,...j->...i", u, r)
    return (
        _einsum("...ij,...j->...i", l, ur)
        - _einsum("...ij,...j->...i", x, r)
    )


def q2(l: jnp.ndarray, u: jnp.ndarray, x: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Paper's scalar probabilistic check: (Lᵀr)ᵀ(Ur) − (rᵀX)r."""
    lt_r = _einsum("...ij,...i->...j", l, r)
    u_r = _einsum("...ij,...j->...i", u, r)
    rx = _einsum("...i,...ij->...j", r, x)
    return jnp.sum(lt_r * u_r, axis=-1) - jnp.sum(rx * r, axis=-1)


def q3(l: jnp.ndarray, u: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Deterministic diagonal check, per-element abs (the form the paper's
    own correctness proof §V.C.2 uses): Σ_i |(L·U)_ii − x_ii|."""
    lu_diag = _einsum("...ij,...ji->...i", jnp.tril(l), jnp.triu(u))
    return jnp.sum(
        jnp.abs(lu_diag - jnp.diagonal(x, axis1=-2, axis2=-1)), axis=-1
    )


def q3_paper_literal(l: jnp.ndarray, u: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Q3 exactly as §IV.E.2 writes it: |Σ_i (Σ_{j≤i} L_ij U_ji − x_ii)|.

    Weaker than q3: opposite-sign per-row errors cancel (see
    tests/test_core_protocol.py::test_q3_literal_cancellation_weakness).
    """
    lu_diag = _einsum("...ij,...ji->...i", jnp.tril(l), jnp.triu(u))
    return jnp.abs(
        jnp.sum(lu_diag - jnp.diagonal(x, axis1=-2, axis2=-1), axis=-1)
    )


def epsilon(
    num_servers: int,
    n: int,
    x: jnp.ndarray | None = None,
    *,
    dtype=jnp.float64,
    c: float = 64.0,
):
    """Acceptance threshold ε(N) — grows with server count (paper §IV.E.3).

    Scalar for a single matrix; a (B,) array for a (B, n, n) stack (each
    matrix gets a threshold scaled to its own magnitude).
    """
    u = float(jnp.finfo(dtype).eps)
    if x is not None:
        scale = jnp.linalg.norm(x, axis=(-2, -1)) / np.sqrt(n)
    else:
        scale = jnp.asarray(1.0)
    out = c * (1.0 + num_servers) * n * u * jnp.maximum(scale, 1.0) ** 2
    if out.ndim == 0:
        return float(out)
    return np.asarray(out)


def growth_estimate(u_factor: jnp.ndarray, x: jnp.ndarray):
    """Observed element growth of the no-pivot elimination, clamped ≥ 1:
    max|U| / max|X| per matrix (scalar, or (B,) for a stack).

    This is the classical growth factor ρ of the factorization the client
    actually received — the multiplier on the u·n rounding model that the
    value-independent (pivot-free) schedule cannot bound a priori.
    """
    num = jnp.max(jnp.abs(u_factor), axis=(-2, -1))
    den = jnp.maximum(jnp.max(jnp.abs(x), axis=(-2, -1)),
                      jnp.finfo(x.dtype).tiny)
    out = jnp.maximum(num / den, 1.0)
    if out.ndim == 0:
        return float(out)
    return np.asarray(out)


def q3_growth_cap(n: int, *, c: float = 4.0) -> float:
    """Ceiling on the ε-widening a diagonal-only (Q3) residual may claim.

    The observed growth is computed from the server-supplied U, and Q3
    never probes the strictly-upper entries it is largest over — planted
    mutually-cancelling entries inflate it for free (module docstring).
    Clamping at c·n keeps the acceptance tolerance client-chosen: honest
    factorizations that genuinely need widening (equilibrated input, no
    growth-safe relayout) stay ≥ 25× under the cap, while a malicious
    server's tolerance inflation is bounded by c·n instead of unbounded.
    The secret-probed Q1/Q2 residuals use the raw growth — there the
    widening is self-defeating to inflate.
    """
    return c * n


def per_server_residuals(
    l: jnp.ndarray,
    u: jnp.ndarray,
    x: jnp.ndarray,
    *,
    num_servers: int,
    method: str = "q1",
    r: jnp.ndarray | None = None,
    rng: np.random.Generator | None = None,
) -> np.ndarray:
    """Blocked residuals attributing the check to Alg. 3's block rows.

    Returns (N,) for a single matrix, (B, N) for a stack. method="q1" (the
    default, and what `localize` uses) blocks the Q1 residual vector by
    owner row — attribution-correct for any strip corruption (see module
    docstring). method="q3" blocks the diagonal terms by diagonal owner —
    a diagnostic view, not a culprit-namer.
    """
    n = x.shape[-1]
    if n % num_servers != 0:
        raise ValueError(f"n={n} not partitioned by N={num_servers}")
    batched = x.ndim == 3
    if method == "q1":
        if r is None:
            rng = rng or np.random.default_rng(1)
            r_shape = (x.shape[0], n) if batched else (n,)
            r = jnp.asarray(rng.standard_normal(r_shape), dtype=x.dtype)
        terms = jnp.abs(q1(l, u, x, r))  # (..., n)
        reduce = jnp.max
    elif method == "q3":
        lu_diag = _einsum("...ij,...ji->...i", jnp.tril(l), jnp.triu(u))
        terms = jnp.abs(lu_diag - jnp.diagonal(x, axis1=-2, axis2=-1))
        reduce = jnp.sum
    else:
        raise ValueError(f"unknown localization method {method!r}")
    blocked = terms.reshape(*terms.shape[:-1], num_servers, n // num_servers)
    return np.asarray(reduce(blocked, axis=-1))


#: Verdict fields that may be scalars (single matrix) or per-matrix
#: numpy arrays (a stack) — the wire codec branches on this
_VERDICT_POLY = ("ok", "residual", "eps", "culprit")


@dataclass
class Verdict:
    """Structured Authenticate outcome: global accept/reject PLUS the
    per-server attribution the recovery scheduler consumes.

    Scalars (bool/float) for a single matrix; per-matrix numpy arrays for a
    (B, n, n) stack. `culprit` is the FIRST server whose residual block
    exceeds ε(N) — the owner of the earliest corrupted strip, with every
    strip above it verified-clean (-1 when all blocks pass).

    (The legacy `(verified, residual)` tuple emulation was removed after
    its deprecation cycle — unpack `.ok` / `.residual` explicitly.)

    Serializes with the role-split wire codec (`to_bytes`/`from_bytes`,
    repro.api.wire) so gateways and archives can move verdicts across
    process boundaries without pickle.
    """

    ok: bool | np.ndarray
    residual: float | np.ndarray
    method: str
    eps: float | np.ndarray
    num_servers: int
    server_residual: np.ndarray | None = None  # (N,) or (B, N)
    server_ok: np.ndarray | None = None
    culprit: int | np.ndarray = -1

    @property
    def all_ok(self) -> bool:
        return bool(np.all(self.ok))

    def to_bytes(self) -> bytes:
        from repro.api import wire

        scalars = {"method": self.method, "num_servers": self.num_servers}
        arrays = {"server_residual": self.server_residual,
                  "server_ok": self.server_ok}
        for name in _VERDICT_POLY:
            val = getattr(self, name)
            if isinstance(val, np.ndarray):
                arrays[name] = val
            elif isinstance(val, (bool, np.bool_)):
                scalars[name] = bool(val)
            elif isinstance(val, (int, np.integer)):
                scalars[name] = int(val)
            else:
                scalars[name] = float(val)
        return wire.encode("Verdict", scalars, arrays)

    @classmethod
    def _from_wire(cls, scalars, arrays):
        fields = {
            "method": scalars["method"],
            "num_servers": int(scalars["num_servers"]),
            "server_residual": arrays["server_residual"],
            "server_ok": arrays["server_ok"],
        }
        for name in _VERDICT_POLY:
            fields[name] = (
                arrays[name] if name in arrays else scalars[name]
            )
        return cls(**fields)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Verdict":
        from repro.api import wire

        kind, scalars, arrays = wire.decode(data)
        if kind != "Verdict":
            raise wire.WireError(f"expected Verdict frame, got {kind!r}")
        return cls._from_wire(scalars, arrays)


def _first_culprit(server_ok: np.ndarray) -> int | np.ndarray:
    """Index of the first failing block row; -1 if all pass. (B,) if batched."""
    bad = ~server_ok
    if server_ok.ndim == 1:
        return int(np.argmax(bad)) if bad.any() else -1
    first = np.argmax(bad, axis=-1)
    return np.where(bad.any(axis=-1), first, -1).astype(np.int64)


def localize(
    l: jnp.ndarray,
    u: jnp.ndarray,
    x: jnp.ndarray,
    *,
    num_servers: int,
    eps: float | np.ndarray | None = None,
    rng: np.random.Generator | None = None,
    r: jnp.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, int | np.ndarray]:
    """(server_residual, server_ok, culprit) via the blocked Q1 residual,
    on probe `r` when given (else one drawn from `rng`)."""
    n = x.shape[-1]
    if eps is None:
        eps = epsilon(num_servers, n, x, dtype=x.dtype)
        eps = eps * growth_estimate(u, x)
    sres = per_server_residuals(l, u, x, num_servers=num_servers, rng=rng,
                                r=r)
    eps_col = np.asarray(eps)[..., None] if np.ndim(eps) else eps
    sok = sres <= eps_col
    return sres, sok, _first_culprit(sok)


def authenticate(
    l: jnp.ndarray,
    u: jnp.ndarray,
    x: jnp.ndarray,
    *,
    num_servers: int,
    method: str = "q3",
    rng: np.random.Generator | None = None,
    eps: float | np.ndarray | None = None,
    attribute: bool | str = "auto",
) -> Verdict:
    """Authenticate(L, U, X) → Verdict (accept/reject + per-server blame).

    method ∈ {"q1", "q2", "q3", "q3_literal"} picks the accept/reject
    residual. For q1/q2 a random r is drawn client-side (the server never
    sees it) — an independent probe per matrix when X is a (B, n, n) stack.
    rng SHOULD be seeded from client-held secret material (the protocol
    seeds it from the Ψ digest): with the module-default generator an
    adversarial server who knows the codebase can pick a perturbation
    orthogonal to the predictable probe and evade the q1/q2 checks and the
    localization pass entirely.

    attribute="auto" (default) computes the blocked-Q1 per-server
    residuals and culprit index only when the global verdict rejects (its
    sole consumer is the recovery scheduler) and n divides evenly over
    num_servers; True forces the pass on accepting verdicts too, False
    always skips it.

    Returns a Verdict; its fields are scalars for a single matrix and
    per-matrix numpy arrays for a stack. Unpacking the Verdict as the old
    (verified, residual) tuple still works but warns.
    """
    n = x.shape[-1]
    batched = x.ndim == 3
    widened_eps = None
    if eps is None:
        # scale-model ε widened by the observed element growth of the
        # returned factors (module docstring — the dtype-portable term).
        # The raw widening is reserved for residuals that SEE the factors
        # it is measured from: the secret-probed q1/q2 here, and the
        # Q1-shaped localization pass below. The diagonal-only q3 forms
        # clamp it at q3_growth_cap(n) — otherwise planted cancelling
        # strictly-upper entries hand the server an arbitrarily wide ε.
        base_eps = epsilon(num_servers, n, x, dtype=x.dtype)
        growth = growth_estimate(u, x)
        widened_eps = base_eps * growth
        if method in ("q3", "q3_literal"):
            eps = base_eps * np.minimum(growth, q3_growth_cap(n))
        else:
            eps = widened_eps
    if method in ("q1", "q2"):
        rng = rng or np.random.default_rng(0)
        r_shape = (x.shape[0], n) if batched else (n,)
        r = jnp.asarray(rng.standard_normal(r_shape), dtype=x.dtype)
        if method == "q1":
            resid = jnp.max(jnp.abs(q1(l, u, x, r)), axis=-1)
        else:
            resid = jnp.abs(q2(l, u, x, r))
            # Q2 contracts twice with r: widen by the extra ‖r‖² factor.
            eps = eps * n
    elif method == "q3":
        resid = q3(l, u, x)
    elif method == "q3_literal":
        resid = q3_paper_literal(l, u, x)
    else:
        raise ValueError(f"unknown authentication method {method!r}")
    if batched:
        resid = np.asarray(resid)
        ok = np.asarray(resid <= eps)
        eps_out = np.asarray(eps) + np.zeros_like(resid)
    else:
        resid = float(resid)
        ok = bool(resid <= eps)
        eps_out = float(np.asarray(eps))
    verdict = Verdict(
        ok=ok,
        residual=resid,
        method=method,
        eps=eps_out,
        num_servers=num_servers,
    )
    wanted = attribute is True or (
        attribute == "auto" and not bool(np.all(verdict.ok))
    )
    if wanted and n % num_servers == 0:
        # localization eps: the blocked check is Q1-shaped, so use the raw
        # growth-widened ε(N) (no Q2 widening) — already computed above
        # unless the caller supplied an explicit eps
        if widened_eps is None:
            widened_eps = epsilon(num_servers, n, x, dtype=x.dtype) \
                * growth_estimate(u, x)
        loc_eps = widened_eps
        # a q1 rejection is attributed on the probe that rejected: the
        # blocks partition that residual, so some block exceeds ε. A
        # fresh probe can miss a fault at the detection floor and leave
        # recovery nothing to heal.
        sres, sok, culprit = localize(
            l, u, x, num_servers=num_servers, eps=loc_eps, rng=rng,
            r=r if method == "q1" else None,
        )
        verdict.server_residual = sres
        verdict.server_ok = sok
        verdict.culprit = culprit
    return verdict


def verification_flops(n: int, method: str) -> int:
    """Cost models backing benchmarks/ (paper Table I's Authenticate column)."""
    if method == "q1":
        return 3 * 2 * n * n  # three mat-vec products
    if method == "q2":
        return 3 * 2 * n * n + 2 * 2 * n  # three mat-vec + two dot products
    if method in ("q3", "q3_literal"):
        return 2 * n * (n + 1) // 2 + n  # Σ_i 2i muls/adds + n subtractions
    raise ValueError(method)
