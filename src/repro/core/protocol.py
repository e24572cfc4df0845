"""SPDC end-to-end protocol — the paper's six-algorithm tuple
(SeedGen, KeyGen, Cipher, Parallelize, Authenticate, Decipher), §III–§IV.

As of the role-split redesign (DESIGN.md §7) this module is the stable
one-call FACADE over the role objects in `repro.api`:

    outsource_determinant(m, N)            # == SPDCClient(...).open_session(m, N).run(InlineTransport)

`repro.api.SPDCClient` owns the client-side PMOP (seed/key/cipher/
equilibrate/border) and the RRVP tail (verify/localize/recover/decipher);
`repro.api.EdgeServer` is the untrusted worker; a `Transport` carries the
`ShardTask`/`ShardResult` messages between them. The facades here keep
the historical signatures and result dataclasses unchanged, defaulting to
the fused inline transport — bit-identical to the pre-split protocol and
still the gateway's throughput path.

Batch-first (DESIGN.md §3): `outsource_determinant` accepts one matrix
(n, n) or a stack (B, n, n). The batched path runs every per-matrix stage
as one jitted device program over the stack — independent seeds, blinding
vectors, rotations, probes, and accept/reject decisions per matrix, but
ONE cipher launch, ONE sweep of the N-server schedule, ONE verify — which
is what makes high request throughput possible (see
benchmarks/run.py:throughput).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .cipher import CipherMeta, Mode
from .decipher import Determinant
from .lu import CommLog
from .seed import Seed
from .verify import Verdict


def resolve_dtype(dtype) -> jnp.dtype:
    """Canonical compute dtype for the protocol.

    Accepts a jnp/np dtype object or a string ("float32"/"float64").
    Canonicalization honors the x64 switch: with jax.enable_x64 OFF a
    float64 request resolves to float32 (the only float the backend will
    actually compute in) instead of warning per-array downstream.
    """
    if isinstance(dtype, str):
        dtype = jnp.dtype(dtype)
    return jax.dtypes.canonicalize_dtype(dtype)


def _low_precision(dtype) -> bool:
    """True for compute dtypes that need the growth-control stages."""
    return jnp.dtype(dtype).itemsize < 8


def _resolve_growth_controls(
    dtype, growth_safe, equilibrate, faithful_sign
) -> tuple[bool, bool]:
    """Default growth_safe/equilibrate ON for sub-f64 compute (where the
    no-pivot growth eats the mantissa — DESIGN.md §6), OFF for float64
    (bit-compatible with the pre-f32 protocol). Explicit booleans win."""
    auto = _low_precision(dtype)
    growth_safe = auto if growth_safe is None else bool(growth_safe)
    equilibrate = auto if equilibrate is None else bool(equilibrate)
    if growth_safe and faithful_sign:
        raise ValueError(
            "faithful_sign reproduces the paper's literal (-1)^k Decipher "
            "factor, which has no growth-safe-relayout analog; pass "
            "growth_safe=False (and expect float32 accuracy loss) or drop "
            "faithful_sign"
        )
    return growth_safe, equilibrate


@dataclass
class SessionTimings:
    """Wall-clock phase breakdown of one protocol run (seconds).

    pmop_s is the client-side prepare (seed/key/cipher/equilibrate/
    border) up to the ciphertext on the device; dispatch_s is the
    Parallelize stage as the client saw it, up to the factors on the
    device — for message transports, dominated by wire time. They are
    the seconds of the `spdc.pmop` span, and of the `spdc.scatter` (where
    the transport places the ciphertext on its chips) and `spdc.sweep`
    spans (repro.spans, DESIGN.md §10.5). collect_s is the RRVP tail
    (authenticate → recovery → decipher: the `spdc.verify` and
    `spdc.decipher` spans and the bookkeeping around them). With the
    async-overlap API (`Session.start` / `SPDCClient.run_pipelined`,
    DESIGN.md §9) batch k+1's pmop_s runs INSIDE batch k's dispatch_s —
    the sum of phases across a pipelined run exceeds its wall clock,
    which is the point.
    """

    pmop_s: float = 0.0
    dispatch_s: float = 0.0
    collect_s: float = 0.0
    total_s: float = 0.0


@dataclass(frozen=True)
class OpRecord:
    """One operation of a multi-op linalg session (DESIGN.md §12).

    The shared-LU op plan runs several client-facing ops (slogdet, solve,
    adjoint solve, inverse) through ONE outsourced factorization; each op
    appends one of these so SPDCReport covers the whole plan, not just
    the factor sweep. `round_trips` counts triangular-solve rounds the op
    added through the transport (0 for slogdet — it reads the already
    verified factors); `healed` counts chunks recovery re-dispatched.
    """

    op: str  # "factor" | "slogdet" | "solve" | "solve_t" | "inv"
    verified: bool = True
    residual: float = 0.0
    wall_s: float = 0.0
    round_trips: int = 0
    healed: int = 0


@dataclass
class SPDCReport:
    """The ONE typed diagnostics surface on a protocol result.

    Consolidates what used to be three ad-hoc optional result fields:

    verdict: structured Authenticate outcome (method, ε(N), per-server
        blame) — core.verify.Verdict.
    recovery: verification-driven re-dispatch log (None unless
        recover=True fired) — distrib.recovery.RecoveryReport.
    fleet: rateless dispatch report (strip counts, per-worker health;
        None on classic sessions) — distrib.rateless.RatelessReport.
    timings: wall-clock phase breakdown (None on paths that don't time
        themselves, e.g. a hand-driven tasks→collect flow).
    ops: per-operation timing/verdict records for multi-op linalg
        sessions (empty on plain determinant runs) — OpRecord.
    """

    verdict: Verdict | None = None
    recovery: object | None = None
    fleet: object | None = None
    timings: SessionTimings | None = None
    ops: tuple = ()


def _deprecated_report_field(name: str):
    """One-cycle shim: `result.verdict` etc. still answer, loudly."""

    @property
    def shim(self):
        warnings.warn(
            f"result.{name} is deprecated; read result.report.{name} "
            "(the consolidated SPDCReport surface)",
            DeprecationWarning,
            stacklevel=2,
        )
        return getattr(self.report, name)

    return shim


@dataclass
class SPDCResult:
    det: Determinant
    verified: bool
    residual: float
    seed: Seed
    meta: CipherMeta
    comm: CommLog | None
    padding: int
    num_servers: int
    #: consolidated diagnostics (verdict / recovery / fleet / timings)
    report: SPDCReport = field(default_factory=SPDCReport)

    # one-cycle deprecated aliases for the pre-consolidation fields
    verdict = _deprecated_report_field("verdict")
    recovery = _deprecated_report_field("recovery")
    fleet = _deprecated_report_field("fleet")


@dataclass
class SPDCBatchResult:
    """Per-matrix protocol outcomes for a (B, n, n) stack.

    `verified`/`residual` are (B,) arrays — one accept/reject decision per
    matrix (a single tampered matrix in the batch is flagged individually).

    `padding` is always a border *amount* (rows added), matching
    SPDCResult. On the uniform (B, n, n) path it is the per-matrix amount
    and `paddings`/`pad_to` are None. On the mixed-size path
    (`outsource_determinant_mixed`, the gateway's coalescing primitive)
    the amount differs per matrix: `paddings` lists them, `pad_to` is the
    common padded size n' the stack ran at, and `padding` is 0 — there is
    no single amount, so consumers of `n + padding` must use `pad_to`.
    """

    dets: list[Determinant]
    verified: np.ndarray
    residual: np.ndarray
    seeds: list[Seed]
    metas: list[CipherMeta]
    comm: CommLog | None
    padding: int
    num_servers: int
    #: consolidated diagnostics (verdict / recovery / fleet / timings)
    report: SPDCReport = field(default_factory=SPDCReport)
    #: mixed-size path only: per-matrix border amounts
    paddings: list[int] | None = None
    #: mixed-size path only: the common padded size n' of the sweep
    pad_to: int | None = None

    verdict = _deprecated_report_field("verdict")
    recovery = _deprecated_report_field("recovery")
    fleet = _deprecated_report_field("fleet")

    @property
    def batch(self) -> int:
        return len(self.dets)


def _probe_rng(digest: bytes) -> np.random.Generator:
    """Verification-probe generator keyed to client-secret material."""
    return np.random.default_rng(int.from_bytes(digest[:8], "big"))


def _batch_digest(seeds: list[Seed]) -> bytes:
    """One dispatch-channel digest for a whole stack: H(Ψ₀-digest ‖ … ‖
    Ψ_{B-1}-digest), so recovery sub-seeds are keyed to the batch's full
    secret material rather than matrix 0's alone."""
    import hashlib

    h = hashlib.sha256()
    for s in seeds:
        h.update(s.digest)
    return h.digest()


def _cipher_host(m: np.ndarray, v: np.ndarray, k: int, mode: Mode,
                 *, growth_safe: bool = False) -> np.ndarray:
    """Host-side Cipher for the mixed-size path: EWO row scaling + k
    clockwise quarter-turns, pure numpy.

    The gateway serves arbitrary client sizes; routing each raw (n, n)
    shape through the jnp cipher would compile a throwaway XLA program per
    distinct size. The O(n²) elementwise/relayout work is a host
    responsibility here (exactly the paper's client-side PMOP placement);
    the device only ever sees the uniform stacked bucket shape. numpy f64
    elementwise ops round identically to XLA-CPU f64, so results agree
    with core.cipher.cipher to the last ulp. growth_safe composes odd
    rotations with the exchange flip (core.cipher semantics).
    """
    if mode == "ewd":
        x = m / v.reshape(-1, 1)
    elif mode == "ewm":
        x = m * v.reshape(-1, 1)
    else:
        raise ValueError(f"unknown EWO mode: {mode!r}")
    x = np.rot90(x, k=-(k % 4))  # cw k turns == ccw -k (core.prt.rot90_cw)
    if growth_safe and k % 2 == 1:
        x = x[:, ::-1] if k % 4 == 1 else x[::-1, :]
    return np.ascontiguousarray(x)


def _equilibrate_host(x: np.ndarray) -> tuple[np.ndarray, int]:
    """numpy twin of core.cipher.equilibrate for the mixed-size path:
    power-of-two row then column scaling; returns (x_eq, log2_scale)."""
    def pow2_exp(maxabs):
        safe = np.where(maxabs > 0, maxabs, 1.0)
        return np.round(np.log2(safe)).astype(np.int64)

    e_r = pow2_exp(np.max(np.abs(x), axis=-1))
    x = x * np.exp2(-e_r.astype(x.dtype))[:, None]
    e_c = pow2_exp(np.max(np.abs(x), axis=-2))
    x = x * np.exp2(-e_c.astype(x.dtype))[None, :]
    return x, -int(e_r.sum() + e_c.sum())


def _augment_host(x: np.ndarray, p: int, rng: np.random.Generator) -> np.ndarray:
    """Host-side det-preserving border for the mixed-size path:
    [[X, 0], [R, I_p]] with R drawn from client-secret-keyed `rng`
    (core.augment semantics, numpy execution — same per-shape-compile
    rationale as _cipher_host)."""
    if p == 0:
        return x
    n = x.shape[-1]
    out = np.zeros((n + p, n + p), dtype=x.dtype)
    out[:n, :n] = x
    out[n:, :n] = rng.uniform(-1.0, 1.0, (p, n))
    out[n:, n:] = np.eye(p, dtype=x.dtype)
    return out


def common_padded_size(sizes, num_servers: int) -> int:
    """Smallest n' ≥ max(sizes) that the N-server schedule accepts
    (n' % N == 0 and n'/N > 1) — the shared shape a mixed-size stack is
    padded to before one coalesced sweep."""
    from .augment import padding_for_servers

    n = max(int(s) for s in sizes)
    return n + padding_for_servers(n, num_servers)


def _make_client(
    *, lambda1, lambda2, mode, method, use_kernel, faithful_sign,
    recover, standby, straggler_deadline, dtype, growth_safe, equilibrate,
    rateless=False,
):
    from repro.api import SPDCClient

    return SPDCClient(
        lambda1=lambda1, lambda2=lambda2, mode=mode, method=method,
        use_kernel=use_kernel, faithful_sign=faithful_sign,
        recover=recover, standby=standby,
        straggler_deadline=straggler_deadline, dtype=dtype,
        growth_safe=growth_safe, equilibrate=equilibrate,
        rateless=rateless,
    )


def outsource_determinant_mixed(
    ms,
    num_servers: int,
    *,
    pad_to: int | None = None,
    lambda1: int = 128,
    lambda2: int = 128,
    mode: Mode = "ewd",
    method: str = "q3",
    distributed: bool = False,
    faithful_sign: bool = False,
    tamper=None,
    faults=None,
    recover: bool = False,
    standby: int = 0,
    straggler_deadline: int | None = None,
    dtype="float64",
    growth_safe: bool | None = None,
    equilibrate: bool | None = None,
    transport=None,
    rateless=False,
) -> SPDCBatchResult:
    """Run the SPDC protocol for a *mixed-size* list of matrices in ONE
    coalesced N-server sweep — the gateway's batching primitive.

    Each matrix is ciphered at its own size (per-matrix Ψ, blinding vector,
    rotation — the host-side PMOP stages are O(n²) and cheap), then its
    ciphertext is padded post-cipher to the common size `pad_to` with the
    determinant-preserving [[X, 0], [R, I]] border (core.augment) so the
    whole stack shares one (B, n', n') shape and ONE jitted LU sweep, ONE
    batched verification, and one relay-hop schedule amortize over all B
    requests.

    Padding MUST happen after Cipher: the PRT stage rotates the matrix by
    a secret quarter-turn count, and any pre-cipher identity/zero border
    lands in a rotated position where the no-pivot LU hits structurally
    singular leading minors (see DESIGN.md §5.1). The post-cipher border
    never rotates; its Schur complement is exactly I, so it adds no
    element growth for any padding amount.

    pad_to: common padded size (defaults to the smallest valid size for
    the largest matrix, `common_padded_size`). Must satisfy
    pad_to % num_servers == 0 and pad_to / num_servers > 1.
    Remaining keywords match `outsource_determinant` (which routes list /
    tuple inputs here); `faults=`/`recover=`/`standby=` give the whole
    stack the fault-tolerance semantics of DESIGN.md §4, and `transport=`
    selects the execution boundary (DESIGN.md §7).

    Returns an SPDCBatchResult whose `pad_to` is the common n' and whose
    `paddings` list the per-matrix border amounts.
    """
    from repro.api import resolve_transport

    client = _make_client(
        lambda1=lambda1, lambda2=lambda2, mode=mode, method=method,
        use_kernel=False, faithful_sign=faithful_sign, recover=recover,
        standby=standby, straggler_deadline=straggler_deadline,
        dtype=dtype, growth_safe=growth_safe, equilibrate=equilibrate,
        rateless=rateless,
    )
    session = client.open_session(
        list(ms), num_servers, faults=faults, tamper=tamper, pad_to=pad_to
    )
    return session.run(resolve_transport(transport, distributed=distributed))


def outsource_determinant(
    m: np.ndarray | jnp.ndarray,
    num_servers: int,
    *,
    lambda1: int = 128,
    lambda2: int = 128,
    mode: Mode = "ewd",
    method: str = "q3",
    use_kernel: bool = False,
    distributed: bool = False,
    faithful_sign: bool = False,
    tamper=None,
    faults=None,
    recover: bool = False,
    standby: int = 0,
    straggler_deadline: int | None = None,
    dtype="float64",
    growth_safe: bool | None = None,
    equilibrate: bool | None = None,
    transport=None,
    rateless=False,
) -> SPDCResult | SPDCBatchResult:
    """Run the full SPDC protocol — the package's main entry point.

    Accepts one matrix (n, n), a same-size stack (B, n, n), or a Python
    list/tuple of mixed-size square matrices (routed through
    `outsource_determinant_mixed`: one coalesced sweep at a shared padded
    size — the gateway path, see repro.serve.spdc_gateway).

    Keyword reference (every public kwarg):

    num_servers: N, the edge-server count of the Parallelize stage. The
        ciphertext is padded so N divides its size (paper §IV.D.1).
    lambda1 / lambda2: security parameters of SeedGen / KeyGen — bits of
        entropy behind the seed Ψ and the blinding vector v (paper §IV.A).
    mode: element-wise obfuscation flavor, "ewd" (row-divide by v, the
        paper's default) or "ewm" (row-multiply).
    method: Authenticate residual — "q1" (Gao & Yu vector probe), "q2"
        (paper's scalar probe), "q3" (deterministic diagonal check,
        default), or "q3_literal" (paper's weaker literal form; see
        DESIGN.md §1.1.4).
    use_kernel: route Cipher through the fused Pallas CED kernel instead
        of the jnp oracle (TPU target; interpret-mode on CPU).
    distributed: route Parallelize through the shard_map pipeline — the
        edge servers on the process's devices, N / chips consecutive
        servers per chip; equivalent to transport="shardmap". See
        DESIGN.md §2.
    faithful_sign: reproduce the paper's literal (−1)^k rotation sign in
        Decipher instead of the Panth Rotation Theorem's case split —
        wrong for n ≡ 0,1 (mod 4); kept for faithfulness studies
        (DESIGN.md §1.1.3).
    tamper: optional fn (L, U) -> (L, U) applied to the servers' results
        before authentication — models a malicious edge server (tests use
        it to show Q2/Q3 reject tampered results, including a single bad
        matrix inside a batch).
    faults: a core.faults FaultPlan (or one ServerFault) — the structured
        untrusted-server model: per-server tamper/dropout/delay,
        batch-aware, applied inside the Parallelize stage (in-band faults
        poison the relay in the single-process simulation; the distributed
        pipeline injects at the device output; message transports play
        the faults on the matching WORKER, so every tamper is naturally
        in-band — the relay forwards what the worker reported).
    recover: on a rejected verdict, localize the faulty server (blocked-Q1
        attribution) and re-dispatch ONLY its shard — the Session emits a
        fresh ShardTask per blamed server through the same transport
        (distrib.recovery runs the loop) — result.report.recovery holds
        the RecoveryReport.
    standby: provision N+r spare servers for those re-dispatches
        (distrib.recovery.ServerPool).
    straggler_deadline: rounds after which a delayed server is treated as
        dropped and its shard re-dispatched (None = wait forever).
    dtype: compute dtype — "float64" (default; what the rtol 1e-10
        acceptance tests are calibrated for) or "float32" (the edge /
        accelerator profile — TPUs have no f64 and GPU f64 runs at 1/32
        rate). Strings or dtype objects accepted; with jax.enable_x64
        OFF, float64 resolves to float32. The ε(N) thresholds read the
        compute dtype's unit roundoff, so verification is calibrated for
        either (DESIGN.md §6).
    growth_safe: compose odd PRT rotations with a det-tracked exchange
        flip so a diagonally dominant input stays diagonally dominant
        under the no-pivot LU (None = auto: on for sub-f64 compute, off
        for float64). See DESIGN.md §6.1 for the precision/obfuscation
        trade.
    equilibrate: two-sided power-of-two scaling of the ciphertext, folded
        into Decipher exactly (None = same auto rule). Lossless in any
        binary float format; keeps ‖X‖-driven rounding flat (DESIGN.md
        §6.2).
    transport: execution boundary for the Parallelize stage (DESIGN.md
        §7/§9) — None (inline fused fast path, bit-identical to the
        pre-split protocol), a name ("threadpool"; "multiprocess" —
        spawned workers, ShardTask/ShardResult bytes on a real OS pipe;
        "socket" — warm worker daemons over TCP/UDS; "shardmap"), a
        repro.api.TransportConfig (declarative: name + addresses +
        timeout), or a live repro.api.Transport instance. All three
        spellings funnel through repro.api.resolve_transport.
    rateless: straggler-adaptive streaming dispatch (DESIGN.md §8) —
        True (default knobs) or a configs.spdc.RatelessConfig. The
        session over-decomposes into F = overdecompose·N strips and
        streams them to whichever workers are free; completion is
        "every strip verified", so there is no straggler_deadline to
        tune (the kwarg is ignored), slow workers just complete fewer
        strips, tampering workers get quarantined mid-session, and the
        client finishes strips inline if the fleet collapses.
        result.report.fleet carries the RatelessReport.

    Returns SPDCResult for a single matrix, SPDCBatchResult (per-matrix
    dets and verdicts) for a stack or list; both carry a consolidated
    `report` (SPDCReport: verdict, recovery, fleet, timings).
    """
    if isinstance(m, (list, tuple)):
        if use_kernel:
            raise ValueError(
                "use_kernel is not supported for mixed-size lists: the "
                "mixed path ciphers each matrix on the host (DESIGN.md "
                "§5.1); stack same-size matrices into a (B, n, n) array "
                "for the Pallas CED kernel"
            )
        return outsource_determinant_mixed(
            m, num_servers,
            lambda1=lambda1, lambda2=lambda2, mode=mode, method=method,
            distributed=distributed, faithful_sign=faithful_sign,
            tamper=tamper, faults=faults, recover=recover, standby=standby,
            straggler_deadline=straggler_deadline, dtype=dtype,
            growth_safe=growth_safe, equilibrate=equilibrate,
            transport=transport, rateless=rateless,
        )
    from repro.api import resolve_transport

    client = _make_client(
        lambda1=lambda1, lambda2=lambda2, mode=mode, method=method,
        use_kernel=use_kernel, faithful_sign=faithful_sign,
        recover=recover, standby=standby,
        straggler_deadline=straggler_deadline, dtype=dtype,
        growth_safe=growth_safe, equilibrate=equilibrate,
        rateless=rateless,
    )
    session = client.open_session(m, num_servers, faults=faults,
                                  tamper=tamper)
    return session.run(resolve_transport(transport, distributed=distributed))
