"""LU factorization — unblocked, blocked, and the paper's N-server schedule.

The paper (§IV.D, Algorithms 1–3) computes LU *without pivoting* on the
ciphered matrix: the schedule must be value-independent (pivot choices leak
magnitudes), and the client's ε(N)-thresholded Q2/Q3 check (§IV.E) is the
paper's own guard against the resulting numerical drift.

Implementations, used as successive oracles for one another:

  * lu_unblocked     — textbook Doolittle elimination, pure jnp (oracle).
  * lu_panel_blocked — blocked factorization of one diagonal tile: the
                       panel→TRSM→Schur structure of lu_blocked applied
                       *inside* the b×b tile, shrinking the sequential
                       critical path from b dependent rank-1 updates to
                       b/inner panel steps + matmuls (DESIGN.md §1.1).
  * lu_blocked       — right-looking block LU (panel → TRSM → Schur GEMM),
                       the per-server local computation. Optionally uses the
                       Pallas kernels (kernels/ops.py) for panel/TRSM/GEMM.
  * lu_nserver       — the paper's Algorithm 3: server i owns block row i;
                       computes L_{i,1..i-1}, factors X_ii, computes
                       U_{i,i+1..N}; one-way message log recorded exactly as
                       the paper's communication pattern prescribes.

All pure-jnp paths accept leading batch dimensions — (..., n, n) — so a
stack of matrices factors in one call (DESIGN.md §3); jax.vmap composes
with them as well.

Paper errata handled here (see DESIGN.md §1.1): Alg. 3 line 7 writes
U_kk^{-1}(X_ik − …) — the inverse must right-multiply (cf. Alg. 1 line 3,
L21 = X21·U11^{-1}); line 8 writes Σ L_ik U_ik — the correct Schur term is
Σ L_ik U_ki (cf. Alg. 1 line 5). We implement the corrected algebra.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


#: Precision of every product on the LU, relay, verify and VJP paths. At
#: default precision a TPU multiplies float32 in bfloat16 passes (2.6e-3
#: relative error on a v5e, 1.5e-7 at HIGHEST), far outside the rounding
#: that ε(N) and the log-det budget assume (DESIGN.md §6). XLA:CPU
#: computes the full product either way, so CPU results are unchanged.
#: What HIGHEST costs on the chip against HIGH is not measured yet.
PRECISION = lax.Precision.HIGHEST


def precise_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a @ b at PRECISION."""
    return jnp.matmul(a, b, precision=PRECISION)


def precise_einsum(spec: str, *operands) -> jnp.ndarray:
    """jnp.einsum at PRECISION."""
    return jnp.einsum(spec, *operands, precision=PRECISION)


# ---------------------------------------------------------------------------
# unblocked (oracle)
# ---------------------------------------------------------------------------
def _doolittle_compact(a: jnp.ndarray) -> jnp.ndarray:
    """Doolittle elimination on (..., n, n) without pivoting.

    Returns the compact form: strict-lower multipliers + U in one array.
    """
    n = a.shape[-1]
    idx = jnp.arange(n)

    def body(k, a):
        below = idx > k
        pivot = a[..., k, k]
        lcol = jnp.where(below, a[..., :, k] / pivot[..., None], 0.0)
        urow = jnp.where(below, a[..., k, :], 0.0)
        a = a - lcol[..., :, None] * urow[..., None, :]
        return a.at[..., :, k].set(jnp.where(below, lcol, a[..., :, k]))

    return lax.fori_loop(0, n, body, a)


def _split_compact(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(L unit-lower, U upper) from the compact form; batch-aware."""
    n = a.shape[-1]
    l = jnp.tril(a, -1) + jnp.eye(n, dtype=a.dtype)
    u = jnp.triu(a)
    return l, u


def lu_unblocked(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Doolittle LU without pivoting on (..., n, n).

    Returns (L unit-lower, U upper) with matching leading batch dims.
    """
    return _split_compact(_doolittle_compact(a))


def _trsm_right_upper(u: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Solve Z U = B  →  Z = B U^{-1} via (Uᵀ)^{-1} Bᵀ; batch-aware."""
    ut = jnp.swapaxes(u, -1, -2)
    bt = jnp.swapaxes(b, -1, -2)
    z = jax.scipy.linalg.solve_triangular(ut, bt, lower=True)
    return jnp.swapaxes(z, -1, -2)


# ---------------------------------------------------------------------------
# blocked panel — the pipeline's per-round diagonal factorization
# ---------------------------------------------------------------------------
def lu_panel_blocked(
    a: jnp.ndarray, inner: int = 32
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked factorization of a (..., b, b) diagonal tile.

    Reuses lu_blocked's panel→TRSM→Schur structure *inside* the tile: only
    the inner×inner sub-panels run the dependent Doolittle elimination; the
    off-diagonal strips are triangular solves and the trailing update is one
    GEMM per step. The sequential critical path drops from b dependent
    rank-1 updates to ceil(b/inner) panel factorizations — this is the
    factorization used on the N-server pipeline's critical path (§IV.D,
    DESIGN.md §1.1). Handles ragged tails (b not a multiple of inner) with
    a short final panel. Batch-aware over leading dims.
    """
    b = a.shape[-1]
    if b <= inner:
        return _split_compact(_doolittle_compact(a))
    for s0 in range(0, b, inner):
        s1 = min(s0 + inner, b)
        diag = _doolittle_compact(a[..., s0:s1, s0:s1])
        a = a.at[..., s0:s1, s0:s1].set(diag)
        if s1 < b:
            lkk = jnp.tril(diag, -1) + jnp.eye(s1 - s0, dtype=a.dtype)
            ukk = jnp.triu(diag)
            u_right = jax.scipy.linalg.solve_triangular(
                lkk, a[..., s0:s1, s1:], lower=True, unit_diagonal=True
            )
            l_below = _trsm_right_upper(ukk, a[..., s1:, s0:s1])
            a = a.at[..., s0:s1, s1:].set(u_right)
            a = a.at[..., s1:, s0:s1].set(l_below)
            a = a.at[..., s1:, s1:].add(-precise_matmul(l_below, u_right))
    return _split_compact(a)


#: tile sizes >= this threshold take the blocked-panel path on the pipeline
#: critical path (below it the matmuls are too small to beat plain Doolittle)
PANEL_BLOCK_THRESHOLD = 64


def lu_diag_factor(a: jnp.ndarray, inner: int = 32) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Factor a diagonal tile, choosing blocked vs plain by tile size.

    This is THE entry point for every per-round diagonal factorization in
    lu_nserver and the shard_map pipeline: for b >= PANEL_BLOCK_THRESHOLD
    the blocked panel runs (no full-tile Doolittle on the critical path).
    """
    if a.shape[-1] >= PANEL_BLOCK_THRESHOLD:
        return lu_panel_blocked(a, inner=inner)
    return lu_unblocked(a)


# ---------------------------------------------------------------------------
# blocked right-looking (per-server local compute)
# ---------------------------------------------------------------------------
def lu_blocked(
    a: jnp.ndarray,
    block: int,
    *,
    use_kernels: bool = False,
    acc_dtype=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Right-looking block LU on (..., n, n). n must be divisible by block.

    Per step k over the block diagonal:
      panel:  X_kk = L_kk U_kk              (blocked-panel factorization)
      trsm:   U_kj = L_kk^{-1} X_kj (j>k);  L_ik = X_ik U_kk^{-1} (i>k)
      schur:  X_ij -= L_ik U_kj             (i,j > k — the GEMM hot spot)

    acc_dtype: optional wider accumulation dtype — the "mixed" variant
    (DESIGN.md §6.4): float32 inputs/outputs with float64 accumulation of
    the panel/TRSM/Schur arithmetic. On the jnp path the working matrix is
    upcast once and the factors are cast back; the kernel path threads
    acc_dtype through each Pallas kernel (each tile computes wide in VMEM,
    stores narrow). float64 accumulation requires a backend with f64
    support (CPU, GPU) — TPU callers stay at the storage dtype.
    """
    n = a.shape[-1]
    if n % block != 0:
        raise ValueError(f"n={n} not divisible by block={block}")
    nb = n // block
    out_dtype = a.dtype
    if acc_dtype is not None and not use_kernels:
        a = a.astype(acc_dtype)

    if use_kernels:
        from repro.kernels import ops as kops

        def panel(x):
            return kops.lu_panel(x, acc_dtype=acc_dtype)

        def trsm_l(l, b):
            return kops.trsm_lower(l, b, acc_dtype=acc_dtype)

        def trsm_u(u, b):
            return kops.trsm_upper_right(u, b, acc_dtype=acc_dtype)

        def schur(c, l, u_):
            return kops.schur_update(c, l, u_, acc_dtype=acc_dtype)
    else:
        panel = lu_diag_factor

        def trsm_l(l, b):
            return jax.scipy.linalg.solve_triangular(
                l, b, lower=True, unit_diagonal=True
            )

        trsm_u = _trsm_right_upper

        def schur(c, l, u_):
            return c - precise_matmul(l, u_)

    # Work on an nb×nb grid of views. Python loop: nb is static & small.
    blocks = [
        [
            a[..., i * block : (i + 1) * block, j * block : (j + 1) * block]
            for j in range(nb)
        ]
        for i in range(nb)
    ]
    lout = [[None] * nb for _ in range(nb)]
    uout = [[None] * nb for _ in range(nb)]
    zero = jnp.zeros((*a.shape[:-2], block, block), dtype=a.dtype)

    for k in range(nb):
        lkk, ukk = panel(blocks[k][k])
        lout[k][k], uout[k][k] = lkk, ukk
        for j in range(k + 1, nb):
            uout[k][j] = trsm_l(lkk, blocks[k][j])
        for i in range(k + 1, nb):
            lout[i][k] = trsm_u(ukk, blocks[i][k])
        for i in range(k + 1, nb):
            for j in range(k + 1, nb):
                blocks[i][j] = schur(blocks[i][j], lout[i][k], uout[k][j])

    for i in range(nb):
        for j in range(nb):
            if lout[i][j] is None:
                lout[i][j] = zero
            if uout[i][j] is None:
                uout[i][j] = zero
    l = jnp.block(lout)
    u = jnp.block(uout)
    if l.dtype != out_dtype:
        l, u = l.astype(out_dtype), u.astype(out_dtype)
    return l, u


# ---------------------------------------------------------------------------
# the paper's N-server algorithm (Algorithm 3) with message accounting
# ---------------------------------------------------------------------------
@dataclass
class CommLog:
    """One-way communication record: (src_server, dst_server, n_elements)."""

    messages: list[tuple[int, int, int]] = field(default_factory=list)

    def send(self, src: int, dst: int, elems: int) -> None:
        self.messages.append((src, dst, elems))

    @property
    def total_elements(self) -> int:
        return sum(e for _, _, e in self.messages)

    @property
    def hops(self) -> int:
        return len(self.messages)


def nserver_comm_model(n: int, num_servers: int) -> CommLog:
    """The one-way chain's message log — a pure function of (n, N).

    This IS lu_nserver's log (it builds its CommLog here); also used by the
    batched protocol path (whose LU runs inside jit, where a host-side log
    can't be threaded out) and by comm benchmarks.
    """
    b = n // num_servers
    log = CommLog()
    for i in range(num_servers - 1):
        elems = sum((num_servers - k) * b * b for k in range(i + 1))
        log.send(i, i + 1, elems)
    return log


def _corrupt_row_blocks(blocks, row_faults, *, n, b, batched, factor):
    """In-band injection for lu_nserver: corrupt one server's strip of row
    blocks IN PLACE in the wavefront, so downstream servers consume the
    corrupted relay (the cascading-poison threat model)."""
    from .faults import corrupt_strip

    defined = [j for j in range(len(blocks)) if blocks[j] is not None]
    strip = jnp.concatenate([blocks[j] for j in defined], axis=-1)
    # pad to the full (…, b, n) strip so global column positions line up
    lead = strip.shape[:-2]
    full = jnp.zeros((*lead, b, n), dtype=strip.dtype)
    off = {j: k for k, j in enumerate(defined)}
    for j in defined:
        full = full.at[..., :, j * b : (j + 1) * b].set(
            strip[..., :, off[j] * b : (off[j] + 1) * b]
        )
    for f in row_faults:
        bad = corrupt_strip(full, f, n=n, factor=factor)
        if f.matrices is not None and batched:
            idx = np.asarray(f.matrices, dtype=np.int32)
            full = full.at[idx].set(bad[idx])
        else:
            full = bad
    for j in defined:
        blocks[j] = full[..., :, j * b : (j + 1) * b]


def lu_nserver(
    x: jnp.ndarray, num_servers: int, faults=()
) -> tuple[jnp.ndarray, jnp.ndarray, CommLog]:
    """Paper Algorithm 3 — N-server one-way pipelined block LU.

    Single-process faithful simulation: performs exactly the block operations
    of Alg. 3 in the paper's order and records every inter-server message of
    the one-way chain S_i → S_{i+1}. Server i computes only block row i.
    Accepts (..., n, n) — a batch factors in one sweep of the schedule.
    Returns (L, U, comm_log).

    faults: a FaultPlan (see core.faults). Faults marked ``in_band`` corrupt
    the faulty server's U strip *inside* the wavefront — downstream servers
    consume the poisoned relay, so every later block row is contaminated
    (recovery must cascade). Report-level faults are applied to the
    assembled factors on the way out, exactly as ``apply_faults`` would.
    """
    from .faults import apply_faults, split_plan

    in_band, report = split_plan(faults)
    n = x.shape[-1]
    N = num_servers
    if n % N != 0 or n // N <= 1:
        raise ValueError(
            f"n={n} must be divisible by N={N} with block > 1; augment first"
        )
    b = n // N
    X = [
        [x[..., i * b : (i + 1) * b, j * b : (j + 1) * b] for j in range(N)]
        for i in range(N)
    ]
    L = [[None] * N for _ in range(N)]
    U = [[None] * N for _ in range(N)]
    # one-way forward schedule: server i sends all U rows k <= i to i+1
    log = nserver_comm_model(n, N)

    # Knowledge forwarded along the one-way chain: U rows of upstream servers.
    # (Server i receives {U_kj : k < i, j >= k} from server i-1 and forwards
    # them, plus its own row, to i+1 — §IV.D.3.)
    for i in range(N):
        # L_{ik} for k < i (corrected right-multiply; see module docstring)
        for k in range(i):
            acc = X[i][k]
            for m in range(k):
                acc = acc - precise_matmul(L[i][m], U[m][k])
            # L_ik U_kk = acc  =>  L_ik = acc @ U_kk^{-1}
            L[i][k] = _trsm_right_upper(U[k][k], acc)
        # Schur update of the diagonal block (corrected U_{ki}); the
        # factorization itself is the blocked panel for b >= 64 — no
        # full-tile Doolittle on the critical path (DESIGN.md §1.1).
        acc = X[i][i]
        for k in range(i):
            acc = acc - precise_matmul(L[i][k], U[k][i])
        L[i][i], U[i][i] = lu_diag_factor(acc)
        # U_{ij} for j > i
        for j in range(i + 1, N):
            acc = X[i][j]
            for k in range(i):
                acc = acc - precise_matmul(L[i][k], U[k][j])
            U[i][j] = jax.scipy.linalg.solve_triangular(
                L[i][i], acc, lower=True, unit_diagonal=True
            )
        # in-band faults: server i corrupts its strips BEFORE the relay hop,
        # so rows > i are computed against the poisoned U row
        row_faults = [f for f in in_band if f.server == i]
        if row_faults:
            batched = x.ndim == 3
            u_faults = [f for f in row_faults if "u" in f.target]
            l_faults = [f for f in row_faults if "l" in f.target]
            if u_faults:
                _corrupt_row_blocks(
                    U[i], u_faults, n=n, b=b, batched=batched, factor="u"
                )
            if l_faults:
                _corrupt_row_blocks(
                    L[i], l_faults, n=n, b=b, batched=batched, factor="l"
                )

    zero = jnp.zeros((*x.shape[:-2], b, b), dtype=x.dtype)
    for i in range(N):
        for j in range(N):
            if L[i][j] is None:
                L[i][j] = zero
            if U[i][j] is None:
                U[i][j] = zero
    l_out, u_out = jnp.block(L), jnp.block(U)
    if report:
        l_out, u_out = apply_faults(l_out, u_out, report, num_servers=N)
    return l_out, u_out, log


def lu_block_row(
    x: jnp.ndarray,
    u: jnp.ndarray,
    server: int,
    num_servers: int,
    *,
    style: str = "nserver",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Recompute one server's block row of the Alg.-3 factorization.

    This is the recovery primitive (distrib/recovery.py): given the
    ciphertext ``x`` and factors whose U rows *above* ``server`` are
    verified-correct, recompute exactly the (L strip, U strip) that server
    ``server`` should have reported. Rows of ``u`` at or below the faulty
    block row are masked out, so a corrupted or dropped strip never
    contaminates its own recomputation.

    style selects the *operation order*, which must match the execution
    path that produced the surviving rows — otherwise the recomputed strip
    differs from the honest one by enough rounding that the re-verification
    residual of the (honest!) downstream rows can graze ε(N):

      * "nserver"  — block-wise accumulation, bit-matching lu_nserver (the
        single-process simulation, the protocol's default Parallelize).
      * "pipeline" — full-row matmul accumulation, matching the shard_map
        server program (distrib/spdc_pipeline).

    Batch-aware over leading dims. Returns strips of shape (..., b, n).
    """
    n = x.shape[-1]
    N = num_servers
    if n % N != 0 or n // N <= 1:
        raise ValueError(f"n={n} not partitionable over N={N}")
    if not 0 <= server < N:
        raise ValueError(f"server {server} out of range for N={N}")
    if style not in ("nserver", "pipeline"):
        raise ValueError(f"unknown style {style!r}")
    b = n // N
    s0 = server * b
    x_row = x[..., s0 : s0 + b, :]
    rows = jnp.arange(n)
    u_above = jnp.where((rows < s0)[:, None], u, 0.0)
    l_row = jnp.zeros_like(x_row)

    if style == "pipeline":
        for k in range(server):
            kb = k * b
            u_col = u_above[..., :, kb : kb + b]
            acc = x_row[..., :, kb : kb + b] - precise_matmul(l_row, u_col)
            ukk = u_above[..., kb : kb + b, kb : kb + b]
            lik = _trsm_right_upper(ukk, acc)
            l_row = l_row.at[..., :, kb : kb + b].set(lik)
        s = x_row - precise_matmul(l_row, u_above)
        sii = s[..., :, s0 : s0 + b]
        lii, _ = lu_diag_factor(sii)
        l_row = l_row.at[..., :, s0 : s0 + b].set(lii)
        r = jax.scipy.linalg.solve_triangular(
            lii, s, lower=True, unit_diagonal=True
        )
        u_row = jnp.where((rows >= s0)[None, :], r, 0.0)
        return l_row, u_row

    # "nserver": mirror lu_nserver's per-block sequential accumulation
    def blk(a, i, j):
        return a[..., i * b : (i + 1) * b, j * b : (j + 1) * b]

    L = [None] * N
    for k in range(server):
        acc = blk(x, server, k)
        for m in range(k):
            acc = acc - precise_matmul(L[m], blk(u_above, m, k))
        L[k] = _trsm_right_upper(blk(u_above, k, k), acc)
        l_row = l_row.at[..., :, k * b : (k + 1) * b].set(L[k])
    acc = blk(x, server, server)
    for k in range(server):
        acc = acc - precise_matmul(L[k], blk(u_above, k, server))
    lii, uii = lu_diag_factor(acc)
    l_row = l_row.at[..., :, s0 : s0 + b].set(lii)
    u_row = jnp.zeros_like(x_row)
    u_row = u_row.at[..., :, s0 : s0 + b].set(uii)
    for j in range(server + 1, N):
        acc = blk(x, server, j)
        for k in range(server):
            acc = acc - precise_matmul(L[k], blk(u_above, k, j))
        uij = jax.scipy.linalg.solve_triangular(
            lii, acc, lower=True, unit_diagonal=True
        )
        u_row = u_row.at[..., :, j * b : (j + 1) * b].set(uij)
    return l_row, u_row


# ---------------------------------------------------------------------------
# determinant from LU
# ---------------------------------------------------------------------------
def slogdet_from_lu(l: jnp.ndarray, u: jnp.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(sign, log|det|) from LU factors — paper §IV.F.1 in overflow-safe form.

    det(X) = Π L_ii · Π U_ii; L is unit-diagonal in our construction but we
    include its diagonal anyway to match the paper's formula. Batch-aware:
    (..., n, n) factors give (...,)-shaped sign and logabs.

    Only the (..., n) diagonals leave the device; the logs and their sum
    are taken on the host in float64. A float32 log|det| ≈ 10³ cannot hold
    the 1e-4 budget (its ulp there is 1.2e-4), and the TPU's float32 log is
    biased by ~1.4e-6 per term, which n terms add up (DESIGN.md §6.4).
    """
    d = (np.asarray(jnp.diagonal(l, axis1=-2, axis2=-1), dtype=np.float64)
         * np.asarray(jnp.diagonal(u, axis1=-2, axis2=-1), dtype=np.float64))
    with np.errstate(divide="ignore"):  # a zero pivot: log|det| = -inf
        logabs = np.sum(np.log(np.abs(d)), axis=-1)
    return np.prod(np.sign(d), axis=-1), logabs


def det_from_lu(l: jnp.ndarray, u: jnp.ndarray) -> np.ndarray:
    sign, logabs = slogdet_from_lu(l, u)
    return sign * np.exp(logabs)
