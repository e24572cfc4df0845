"""SPDCClient / Session — the trusted-client role of the SPDC protocol.

The paper's trust boundary (§III–IV) splits the six-algorithm tuple in
two: SeedGen, KeyGen, Cipher, Authenticate, and Decipher run on the
constrained CLIENT; only the Parallelize stage (the N-server LU) runs on
untrusted edge hardware. This module is everything on the client side of
that line, as an object API:

    client  = SPDCClient(method="q3", dtype="float64", recover=True)
    session = client.open_session(m, num_servers=4)      # PMOP runs here
    result  = session.run(transport)                     # SPCP + RRVP

`open_session` performs the full PMOP (seed → key → cipher → equilibrate
→ det-preserving border) and captures every secret the protocol needs —
seeds, blinding keys, rotation metadata, the augmented ciphertext the
probes verify against. What leaves the session is only what
`Session.tasks()` emits: per-server ShardTasks holding encrypted block
rows and dispatch sub-seeds (messages.ShardTask; the boundary is checked
at task-build time and adversarially in tests/test_api.py).

`Session.collect()` is the RRVP tail: Authenticate over the assembled
factors with a secret-keyed probe, then — when the client opted into
recovery — the verification-driven re-dispatch loop, expressed as the
session emitting NEW ShardTasks for blamed servers (fresh sub-seed per
attempt, verified upstream rows attached) through the same transport.
The one-way model survives recovery: servers still never talk backwards,
the client re-issues work instead.

The module-level `outsource_determinant` facades in core.protocol are
thin wrappers over exactly this flow and remain the stable entry point;
this API is for callers that need the roles separated — multi-process
serving, real remote workers, or security tests that must see the wire.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.augment import augment, padding_for_servers
from repro.core.cipher import CipherMeta, cipher, cipher_batch
from repro.core.cipher import equilibrate as ced_equilibrate
from repro.core.decipher import decipher, decipher_batch
from repro.core.faults import normalize_plan, resolve_delays
from repro.core.keygen import keygen, keygen_batch
from repro.core.lu import nserver_comm_model
from repro.core.prt import rotate_degree
from repro.core.seed import Seed, seedgen, seedgen_batch
from repro.core.verify import authenticate
from repro.spans import span

from .messages import ShardResult, ShardTask
from .transport import Transport, TransportConfig, resolve_transport

__all__ = ["SPDCClient", "Session", "PendingResult", "BoundaryViolation"]


class BoundaryViolation(AssertionError):
    """A ShardTask was about to carry plaintext or key material."""


#: everything a ShardTask is allowed to hold — a new field on the message
#: is a deliberate API change, not something a refactor may smuggle in
_TASK_FIELDS = frozenset(
    {"server", "num_servers", "x_row", "subseed", "style", "attempt",
     "u_upstream", "session_id"}
)

#: everything a TriSolveTask (linalg.session's triangular-solve rounds,
#: DESIGN.md §12) is allowed to hold — same contract as _TASK_FIELDS:
#: repro-lint's SPDC105 cross-checks this set against the dataclass
_SOLVE_TASK_FIELDS = frozenset(
    {"server", "num_servers", "l", "u", "rhs", "subseed", "transpose",
     "col0", "attempt", "session_id"}
)

#: auto boundary check: full entry-level plaintext-disjointness screening
#: up to this many payload elements per sweep (beyond it the structural
#: checks still run; tests force the full check at every size)
_FULL_CHECK_ELEMS = 1 << 20


@partial(jax.jit, static_argnames=("padding", "equilibrate"))
def _equilibrate_augment_jit(x, aug_key, *, padding, equilibrate):
    if equilibrate:
        x, log2_scale = ced_equilibrate(x)
    else:
        log2_scale = jnp.zeros(x.shape[:-2], dtype=jnp.int32)
    return augment(x, padding, key=aug_key), log2_scale


def _equilibrate_augment(x, aug_key, *, padding, equilibrate):
    """PMOP tail for device ciphertexts: optional two-sided power-of-two
    equilibration, then the det-preserving [[X,0],[R,I]] border. Both
    transforms are exact in floating point, so running them here (vs
    fused into the old monolithic sweep) is value-identical. When both
    stages are no-ops (p = 0, no equilibration — every n divisible by N)
    the jit is skipped entirely: an identity program would still cost a
    dispatch plus a full ciphertext copy per sweep on the gateway's hot
    path."""
    if padding == 0 and not equilibrate:
        # host zeros, not device zeros: converting a device array back to
        # numpy at session-build time would SYNC the CPU stream and
        # serialize the still-in-flight cipher program behind it
        return x, np.zeros(x.shape[:-2], dtype=np.int32)
    return _equilibrate_augment_jit(x, aug_key, padding=padding,
                                    equilibrate=equilibrate)


@dataclass
class SPDCClient:
    """The trusted client role: holds the security configuration and
    mints Sessions. One client may run many concurrent sessions; all
    per-matrix secrets live on the Session, not here.

    Parameters mirror `core.protocol.outsource_determinant` (that facade
    constructs one of these); see its docstring for the full reference.
    """

    lambda1: int = 128
    lambda2: int = 128
    mode: str = "ewd"
    method: str = "q3"
    use_kernel: bool = False
    faithful_sign: bool = False
    recover: bool = False
    standby: int = 0
    straggler_deadline: int | None = None
    dtype: Any = "float64"
    growth_safe: bool | None = None
    equilibrate: bool | None = None
    #: rateless straggler-adaptive dispatch (DESIGN.md §8): True uses the
    #: default RatelessConfig, or pass one. Sessions over-decompose into
    #: F = overdecompose·N strips streamed to whichever workers are free;
    #: straggler_deadline is ignored (there is no deadline to tune).
    rateless: Any = False
    #: default execution boundary for this client's sessions: a name, a
    #: TransportConfig, or a Transport instance (resolve_transport). A
    #: config is BUILT here and OWNED — `close()` (or the client's
    #: context manager) tears it down deterministically; names resolve to
    #: the process-shared instance and instances stay caller-owned.
    transport: Any = None

    def __post_init__(self):
        from repro.configs.spdc import RATELESS_DEFAULT, RatelessConfig
        from repro.core.protocol import (
            _resolve_growth_controls, resolve_dtype,
        )

        self._owns_transport = False
        if isinstance(self.transport, TransportConfig):
            self.transport = self.transport.build()
            self._owns_transport = True
        elif self.transport is not None and not isinstance(
            self.transport, Transport
        ):
            # a name string — shared instance, not owned
            self.transport = resolve_transport(self.transport)
        self.dtype = resolve_dtype(self.dtype)
        self.growth_safe, self.equilibrate = _resolve_growth_controls(
            self.dtype, self.growth_safe, self.equilibrate,
            self.faithful_sign,
        )
        if self.rateless is True:
            self.rateless = RATELESS_DEFAULT
        elif not self.rateless:
            self.rateless = None
        elif not isinstance(self.rateless, RatelessConfig):
            raise ValueError(
                "rateless must be a bool or a configs.spdc.RatelessConfig, "
                f"got {self.rateless!r}"
            )
        # fleet health OUTLIVES sessions: what one session learned about
        # the workers (speed, tamper history) steers the next
        if self.rateless is not None:
            from repro.distrib.rateless import FleetHealth

            self.fleet = FleetHealth(self.rateless)
        else:
            self.fleet = None

    def _partitions(self, num_servers: int) -> int:
        """Strips per matrix: F = overdecompose·N rateless, N classic."""
        if self.rateless is None:
            return num_servers
        return num_servers * self.rateless.overdecompose

    # -- transport lifecycle -------------------------------------------------

    def close(self) -> None:
        """Close the transport this client OWNS (built from a
        TransportConfig). Shared (name-resolved) and caller-provided
        instances are left alone — their owner closes them. Idempotent."""
        if self._owns_transport and self.transport is not None:
            self.transport.close()

    def __enter__(self) -> "SPDCClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- async-overlap pipeline (DESIGN.md §9) --------------------------------

    def run_pipelined(self, inputs, num_servers: int, *, depth: int = 2,
                      transport=None, faults=None, tamper=None) -> list:
        """Run many independent protocol inputs with PMOP/wire overlap.

        The sequential loop `[open_session(m).run() for m in inputs]`
        leaves the wire idle during every PMOP and the client idle during
        every wire round trip. This pipeline keeps up to `depth` sessions
        in flight: batch k's ShardTasks ride the transport (a
        `Session.start` Future) WHILE batch k+1's cipher/border runs on
        the client — on message transports the client-side prepare cost
        disappears into wire time. Results come back in input order, each
        collected (authenticate → decipher) on this thread as its dispatch
        resolves; `inputs` elements are anything `open_session` accepts.

        depth=1 degrades to the sequential loop; depth beyond the
        transport's driver width (4) adds nothing.
        """
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        results: list = []
        pending: list[PendingResult] = []
        for m in inputs:
            if len(pending) >= depth:
                results.append(pending.pop(0).result())
            session = self.open_session(m, num_servers, faults=faults,
                                        tamper=tamper)
            pending.append(session.start(transport))
        while pending:
            results.append(pending.pop(0).result())
        return results

    # -- PMOP: everything before any server is involved ---------------------

    def open_session(
        self,
        m,
        num_servers: int,
        *,
        faults=None,
        tamper=None,
        pad_to: int | None = None,
    ) -> "Session":
        """Run the client-side PMOP and return the dispatchable Session.

        m: one (n, n) matrix, a (B, n, n) stack, or a list/tuple of
        mixed-size square matrices (coalesced at a shared padded size —
        `pad_to` applies only there). faults/tamper configure SIMULATED
        misbehavior: faults ride to the Parallelize stage (in-sweep for
        fused transports, worker-side for message transports); tamper is
        a client-side hook on the assembled factors.
        """
        # the span ends once the ciphertext is on the device
        with span("spdc.pmop", wait=lambda: sess.x_aug) as pmop:
            plan = resolve_delays(
                normalize_plan(faults),
                # rateless has no rounds deadline — slow servers just do less
                None if self.rateless is not None else self.straggler_deadline,
            )
            if isinstance(m, (list, tuple)):
                sess = self._open_mixed(m, num_servers, plan, tamper, pad_to)
            else:
                if pad_to is not None:
                    raise ValueError("pad_to applies to mixed-size lists only")
                m = jnp.asarray(m, dtype=self.dtype)
                if m.ndim == 3:
                    sess = self._open_batch(m, num_servers, plan, tamper)
                else:
                    if m.ndim != 2 or m.shape[0] != m.shape[1]:
                        raise ValueError(
                            f"expected a square matrix, got {m.shape}"
                        )
                    sess = self._open_single(m, num_servers, plan, tamper)
        sess._pmop_s = pmop.seconds
        return sess

    def _open_single(self, m, num_servers, plan, tamper) -> "Session":
        n = int(m.shape[0])
        m_host = np.asarray(m)
        seed = seedgen(self.lambda1, m_host)
        key = keygen(self.lambda2, seed, n)
        x, meta = cipher(m, key, seed, mode=self.mode,
                         growth_safe=self.growth_safe,
                         use_kernel=self.use_kernel)
        if self.equilibrate:
            x, log2_scale = ced_equilibrate(x)
            log2_scale = float(log2_scale)
        else:
            log2_scale = 0.0
        aug_key = jax.random.key(
            int.from_bytes(seed.digest[8:16], "big") % (2**31)
        )
        parts = self._partitions(num_servers)
        padding = self._padding_for(n, parts)
        x_aug = augment(x, padding, key=aug_key)
        return Session(
            client=self, kind="single", num_servers=num_servers,
            x_aug=x_aug, seeds=[seed], metas=[meta],
            log2_scale=log2_scale, n=n, padding=padding,
            digest=seed.digest, plan=plan, tamper=tamper,
            num_strips=parts if parts != num_servers else None,
            _m_host=m_host,
        )

    def _padding_for(self, n: int, parts: int) -> int:
        """Identity-border padding to the partition grid; the rateless
        grid (F strips) additionally keeps strips ≥ 2 rows — the same
        n'/N > 1 floor the paper puts on the classic schedule."""
        padding = padding_for_servers(n, parts)
        if (n + padding) // parts < 2:
            padding = 2 * parts - n
        return padding

    def _open_batch(self, m, num_servers, plan, tamper) -> "Session":
        from repro.core.protocol import _batch_digest

        n = int(m.shape[-1])
        m_host = np.asarray(m)
        seeds = seedgen_batch(self.lambda1, m_host)
        v = keygen_batch(self.lambda2, seeds, n)
        x, metas = cipher_batch(m, v, seeds, mode=self.mode,
                                growth_safe=self.growth_safe,
                                use_kernel=self.use_kernel)
        aug_key = jax.random.key(
            int.from_bytes(seeds[0].digest[8:16], "big") % (2**31)
        )
        parts = self._partitions(num_servers)
        padding = self._padding_for(n, parts)
        x_aug, log2_scale = _equilibrate_augment(
            x, aug_key, padding=padding, equilibrate=self.equilibrate
        )
        # log2_scale may still be a device array here; collect() converts
        # it at Decipher time (the old fused path's sync point) — forcing
        # it now would stall the session behind the cipher program
        return Session(
            client=self, kind="batch", num_servers=num_servers,
            x_aug=x_aug, seeds=seeds, metas=metas,
            log2_scale=log2_scale, n=n, padding=padding,
            digest=_batch_digest(seeds), plan=plan, tamper=tamper,
            num_strips=parts if parts != num_servers else None,
            _m_host=m_host,
        )

    def _open_mixed(self, ms, num_servers, plan, tamper, pad_to) -> "Session":
        # host-native from the start: raw-size client matrices must never
        # individually touch the device (DESIGN.md §5.1)
        from repro.core.protocol import (
            _augment_host, _batch_digest, _cipher_host, _equilibrate_host,
            common_padded_size,
        )

        np_dtype = np.dtype(self.dtype.name)
        ms = [np.asarray(mi, dtype=np_dtype) for mi in ms]
        if not ms:
            raise ValueError("outsource_determinant_mixed needs >= 1 matrix")
        for mi in ms:
            if mi.ndim != 2 or mi.shape[0] != mi.shape[1]:
                raise ValueError(
                    f"expected square matrices, got shape {mi.shape}"
                )
        sizes = [int(mi.shape[0]) for mi in ms]
        parts = self._partitions(num_servers)
        if pad_to is None:
            pad_to = common_padded_size(sizes, parts)
        if pad_to % parts != 0 or pad_to // parts <= 1:
            raise ValueError(
                f"pad_to={pad_to} not servable by {parts} partitions "
                f"(N={num_servers}"
                + (f" × overdecompose={parts // num_servers}"
                   if parts != num_servers else "")
                + "; need pad_to % parts == 0 and pad_to / parts > 1)"
            )
        if max(sizes) > pad_to:
            raise ValueError(
                f"matrix of size {max(sizes)} exceeds pad_to={pad_to}"
            )
        seeds, metas, xs, paddings, log2_scales = [], [], [], [], []
        for mi in ms:
            n = int(mi.shape[0])
            seed = seedgen(self.lambda1, mi)
            key = keygen(self.lambda2, seed, n)
            k = rotate_degree(seed.psi)
            x = _cipher_host(mi, np.asarray(key.v, dtype=np_dtype), k,
                             self.mode, growth_safe=self.growth_safe)
            if self.equilibrate:
                x, ls = _equilibrate_host(x)
            else:
                ls = 0
            aug_rng = np.random.default_rng(
                int.from_bytes(seed.digest[8:16], "big") % (2**31)
            )
            xs.append(_augment_host(x, pad_to - n, aug_rng))
            seeds.append(seed)
            metas.append(CipherMeta(mode=self.mode, rotate_k=k, n=n,
                                    flipped=self.growth_safe and k % 2 == 1))
            paddings.append(pad_to - n)
            log2_scales.append(ls)
        return Session(
            client=self, kind="mixed", num_servers=num_servers,
            x_aug=jnp.asarray(np.stack(xs)), seeds=seeds, metas=metas,
            log2_scale=np.asarray(log2_scales), n=pad_to, padding=0,
            digest=_batch_digest(seeds), plan=plan, tamper=tamper,
            paddings=paddings, pad_to=pad_to,
            num_strips=parts if parts != num_servers else None,
            _m_host=None, _m_hosts=ms,
        )


@dataclass
class Session:
    """One protocol run: the client's secrets + the dispatchable state.

    Everything here except `tasks()`'s output is client-private. The
    life cycle is tasks → (transport) → collect, or just `run(transport)`
    which does both and prefers the fused sweep on fused transports.
    """

    client: SPDCClient
    kind: str  # "single" | "batch" | "mixed"
    num_servers: int
    x_aug: jnp.ndarray  # (…, n', n') augmented CIPHERTEXT (client-held)
    seeds: list[Seed]
    metas: list[CipherMeta]
    log2_scale: Any
    n: int  # raw size (single/batch) or the common n' (mixed)
    padding: int
    digest: bytes
    plan: tuple = ()
    tamper: Any = None
    paddings: list[int] | None = None
    pad_to: int | None = None
    #: rateless over-decomposition: F > N strips (None = classic, one
    #: strip per server). The PARTITION geometry (authenticate blocks,
    #: strip minting, recovery) keys off `partitions`; `num_servers`
    #: stays the physical fleet size.
    num_strips: int | None = None
    fleet_report: Any = None
    #: retain the verified (possibly healed) factors after collect() so
    #: linalg.LinalgSession can grow its op plan — solve/inv rounds reuse
    #: the SAME verified LU instead of outsourcing a second factorization
    keep_factors: bool = False
    _factors: tuple | None = None
    _m_host: np.ndarray | None = None
    _m_hosts: list[np.ndarray] = field(default_factory=list)
    # phase timings feeding SPDCReport.timings: the spdc.pmop and
    # spdc.sweep spans' seconds (collect adds its own)
    _pmop_s: float = 0.0
    _dispatch_s: float = 0.0

    def __post_init__(self):
        from repro.distrib.recovery import dispatch_subseed

        # opaque routing tag: one-way derived from the secret digest so it
        # can be logged/echoed without leaking probe or channel material
        self.session_id = dispatch_subseed(self.digest, -1, -1)[:8].hex()

    # -- geometry ------------------------------------------------------------

    @property
    def n_aug(self) -> int:
        return int(self.x_aug.shape[-1])

    @property
    def block(self) -> int:
        return self.n_aug // self.num_servers

    @property
    def partitions(self) -> int:
        """Block rows the protocol partitions n' into: F when rateless,
        N classically. Verification, recovery, and task minting all key
        off this count — authenticate works for ANY divisor of n'."""
        return self.num_strips or self.num_servers

    @property
    def strip_block(self) -> int:
        return self.n_aug // self.partitions

    @property
    def batch(self) -> int | None:
        return int(self.x_aug.shape[0]) if self.x_aug.ndim == 3 else None

    # -- dispatch ------------------------------------------------------------

    def tasks(self, *, check_boundary: bool | None = None) -> list[ShardTask]:
        """The initial ShardTasks — one encrypted block row + dispatch
        sub-seed per partition (N classically, F when rateless).
        u_upstream is left to the transport's relay.

        check_boundary: None (default) runs the structural boundary
        checks always and the full entry-level plaintext screening up to
        ~1M payload elements; True forces the full screening at any size;
        False runs structural checks only.
        """
        from repro.distrib.recovery import dispatch_subseed

        b = self.strip_block
        out = []
        for i in range(self.partitions):
            out.append(
                ShardTask(
                    server=i,
                    num_servers=self.partitions,
                    x_row=np.asarray(
                        self.x_aug[..., i * b : (i + 1) * b, :]
                    ),
                    subseed=dispatch_subseed(self.digest, i, 0),
                    style="nserver",
                    session_id=self.session_id,
                )
            )
        self._assert_boundary(out, check_boundary)
        return out

    def _repair_task(self, server: int, attempt: int, u) -> ShardTask:
        """A verification-driven re-issue for one blamed block row: fresh
        dispatch sub-seed, verified upstream U rows attached (the
        replacement is stateless and the culprit's relay is untrusted)."""
        from repro.distrib.recovery import dispatch_subseed

        b, s0 = self.strip_block, server * self.strip_block
        return ShardTask(
            server=server,
            num_servers=self.partitions,
            x_row=np.asarray(self.x_aug[..., s0 : s0 + b, :]),
            subseed=dispatch_subseed(self.digest, server, attempt),
            style=self._style,
            attempt=attempt,
            u_upstream=np.asarray(u[..., :s0, :]),
            session_id=self.session_id,
        )

    def _assert_boundary(self, tasks, check_boundary) -> None:
        """No plaintext, no key material, no unexpected fields — checked
        at the moment messages are minted, not left to code review."""
        plaintexts = (
            self._m_hosts if self._m_hosts
            else ([self._m_host] if self._m_host is not None else [])
        )
        total = sum(t.x_row.size for t in tasks)
        full = check_boundary or (
            check_boundary is None and total <= _FULL_CHECK_ELEMS
        )
        secrets = np.asarray([s.psi for s in self.seeds])

        def informative(a):
            # exact 0/±1 entries are structural constants (zero border,
            # identity block) that carry no client information — screening
            # them would false-alarm on sparse client matrices
            a = np.asarray(a).ravel()
            return a[(a != 0.0) & (np.abs(a) != 1.0)]

        # the plaintext side of the screen is loop-invariant: filter and
        # sort it once, not once per task
        plain_sorted = [np.sort(informative(m)) for m in plaintexts] \
            if full else []

        def leaks(payload, reference_sorted):
            if not reference_sorted.size or not payload.size:
                return False
            idx = np.clip(np.searchsorted(reference_sorted, payload),
                          0, reference_sorted.size - 1)
            return bool(np.any(reference_sorted[idx] == payload))

        for t in tasks:
            extra = set(vars(t)) - _TASK_FIELDS
            if extra:
                raise BoundaryViolation(
                    f"ShardTask grew unreviewed fields {sorted(extra)}"
                )
            if not (isinstance(t.subseed, bytes) and len(t.subseed) == 32):
                raise BoundaryViolation("subseed must be a 32-byte digest")
            for m in plaintexts:
                if np.shares_memory(t.x_row, m):
                    raise BoundaryViolation(
                        "ShardTask payload aliases the plaintext buffer"
                    )
            if full:
                payload = informative(t.x_row)
                for ref in plain_sorted:
                    if leaks(payload, ref):
                        raise BoundaryViolation(
                            "ShardTask payload contains verbatim plaintext "
                            "entries — cipher did not run?"
                        )
                if leaks(payload, np.sort(secrets)):
                    raise BoundaryViolation(
                        "ShardTask payload contains client key material"
                    )

    # -- execution -----------------------------------------------------------

    _style: str = "nserver"

    def _resolve_transport(self, transport):
        """None falls back to the client's configured transport (which
        itself defaults to inline)."""
        if transport is None:
            transport = self.client.transport
        return resolve_transport(transport)

    def run(self, transport=None):
        """Dispatch + collect through a transport (default: the client's
        configured one, else inline).

        Rateless sessions always take the streaming scheduler — the
        fused sweep has no per-strip dispatch for health tracking to
        steer (distrib.rateless; DESIGN.md §8).
        """
        transport = self._resolve_transport(transport)
        self._style = transport.style
        x_aug, scatter_s = self._scatter(transport)
        # the span ends once the factors are on the device
        with span("spdc.sweep", wait=lambda: (l, u)) as sweep:
            if self.num_strips is not None:
                from repro.distrib.rateless import run_rateless

                self._style = "nserver"  # the scheduler's strip primitive
                l_host, u_host, rpt = run_rateless(
                    self, transport, self.client.rateless, self.client.fleet,
                    faults=self.plan,
                )
                self.fleet_report = rpt
                dt = self.x_aug.dtype
                l = jnp.asarray(l_host, dtype=dt)
                u = jnp.asarray(u_host, dtype=dt)
            elif transport.fused:
                l, u = transport.sweep(x_aug, self.num_servers,
                                       faults=self.plan)
            else:
                results = transport.factor(self.tasks(), faults=self.plan)
                l, u = self._assemble(results)
        self._dispatch_s = scatter_s + sweep.seconds
        return self.collect((l, u), transport=transport)

    def _scatter(self, transport):
        """(the ciphertext where the transport's sweep wants it, seconds):
        placed by `transport.place` under its own `spdc.scatter` span, a
        sibling of `spdc.sweep`. Transports with nothing to place (and
        rateless sessions) get `x_aug` as it is, in no time."""
        if transport.place is None or self.num_strips is not None:
            return self.x_aug, 0.0
        # the span ends once the block rows are on their chips
        with span("spdc.scatter", wait=lambda: x_aug) as scatter:
            x_aug = transport.place(self.x_aug, self.num_servers)
        return x_aug, scatter.seconds

    def start(self, transport=None) -> "PendingResult":
        """Nonblocking dispatch: ship this session's Parallelize stage
        and return a PendingResult whose `.result()` runs the RRVP tail.

        On message transports the sweep rides the transport's driver
        threads (`Transport.driver_submit`), so the caller's NEXT
        `open_session` — the client PMOP for batch k+1 — overlaps this
        session's wire time; `SPDCClient.run_pipelined` is the loop
        built on exactly this. There the `spdc.sweep` span opens and
        closes on the driver thread, where the factors arrive. Fused
        transports complete the future synchronously, once the factors
        are on the device.
        """
        transport = self._resolve_transport(transport)
        self._style = transport.style
        if self.num_strips is not None:
            from repro.distrib.rateless import run_rateless

            self._style = "nserver"

            def drive_rateless():
                with span("spdc.sweep", wait=lambda: out) as sweep:
                    l_host, u_host, rpt = run_rateless(
                        self, transport, self.client.rateless,
                        self.client.fleet, faults=self.plan,
                    )
                    self.fleet_report = rpt
                    dt = self.x_aug.dtype
                    out = (jnp.asarray(l_host, dtype=dt),
                           jnp.asarray(u_host, dtype=dt))
                self._dispatch_s = sweep.seconds
                return out

            future = transport.driver_submit(drive_rateless)
        elif transport.fused:
            from concurrent.futures import Future

            future = Future()
            try:
                x_aug, scatter_s = self._scatter(transport)
                with span("spdc.sweep", wait=lambda: out) as sweep:
                    out = transport.sweep(x_aug, self.num_servers,
                                          faults=self.plan)
                self._dispatch_s = scatter_s + sweep.seconds
                future.set_result(out)
            except Exception as e:  # noqa: BLE001 — future carries it
                future.set_exception(e)
        else:
            tasks = self.tasks()  # boundary-checked on THIS thread

            def drive_factor():
                with span("spdc.sweep") as sweep:
                    out = transport.factor(tasks, self.plan)
                self._dispatch_s = sweep.seconds
                return out

            future = transport.driver_submit(drive_factor)
        return PendingResult(session=self, transport=transport,
                             future=future)

    def _assemble(self, results) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Stack per-partition strips into full (…, n', n') factors."""
        byid = {r.server: r for r in results}
        if sorted(byid) != list(range(self.partitions)):
            raise ValueError(
                f"need one ShardResult per partition, got {sorted(byid)}"
            )
        l = np.concatenate(
            [np.asarray(byid[i].l_row) for i in range(self.partitions)],
            axis=-2,
        )
        u = np.concatenate(
            [np.asarray(byid[i].u_row) for i in range(self.partitions)],
            axis=-2,
        )
        dt = self.x_aug.dtype
        return jnp.asarray(l, dtype=dt), jnp.asarray(u, dtype=dt)

    # -- RRVP: verify, heal, decipher ---------------------------------------

    def collect(self, results, *, transport=None):
        """Authenticate → (recovery) → Decipher.

        results: an (L, U) pair of full factors, or a list of
        ShardResults to assemble. Returns core.protocol.SPDCResult /
        SPDCBatchResult exactly as the facades always have.
        """
        from repro.core.protocol import (
            SPDCBatchResult, SPDCReport, SPDCResult, SessionTimings,
            _probe_rng,
        )
        from repro.distrib.recovery import recover_lu

        t_collect = time.perf_counter()
        transport = self._resolve_transport(transport)
        self._style = transport.style
        if (isinstance(results, tuple) and len(results) == 2
                and not isinstance(results[0], ShardResult)):
            l, u = results
        else:
            l, u = self._assemble(results)
        if self.tamper is not None:
            l, u = self.tamper(l, u)
        fleet = self.client.fleet

        def dispatch(x, u_now, server, attempt, replacement):
            # recovery IS re-streaming one strip: rateless sessions
            # route the re-issue to the healthiest live worker (or
            # compute it inline when the fleet is gone) instead of
            # the pool's positional replacement
            task = self._repair_task(server, attempt, u_now)
            if fleet is not None:
                ids = tuple(range(self.num_servers))
                live = (fleet.assignable(ids, set(), time.monotonic())
                        or fleet.live(ids))
                if live:
                    res = transport.repair(task, replacement=live[0])
                else:
                    from .server import EdgeServer

                    res = EdgeServer(None).run(task)
            else:
                res = transport.repair(task, replacement=replacement)
            dt = self.x_aug.dtype
            return (jnp.asarray(res.l_row, dtype=dt),
                    jnp.asarray(res.u_row, dtype=dt))

        # the verdict arrives as host scalars / numpy arrays: no wait
        with span("spdc.verify"):
            verdict = authenticate(
                l, u, self.x_aug, num_servers=self.partitions,
                method=self.client.method, rng=_probe_rng(self.digest),
            )
            report = None
            if self.client.recover and not bool(np.all(verdict.ok)):
                l, u, verdict, report = recover_lu(
                    l, u, self.x_aug, num_servers=self.partitions,
                    method=self.client.method, standby=self.client.standby,
                    digest=self.digest, style=self._style, verdict=verdict,
                    dispatch=dispatch,
                )
        if self.keep_factors:
            # post-recovery: these are the factors Authenticate accepted,
            # so every later trisolve round goes through healed material
            self._factors = (np.asarray(l), np.asarray(u))
        comm = (
            None if transport.style == "pipeline"
            else nserver_comm_model(self.n_aug, self.partitions)
        )

        def build_report() -> SPDCReport:
            collect_s = time.perf_counter() - t_collect
            return SPDCReport(
                verdict=verdict,
                recovery=report,
                fleet=self.fleet_report,
                timings=SessionTimings(
                    pmop_s=self._pmop_s,
                    dispatch_s=self._dispatch_s,
                    collect_s=collect_s,
                    total_s=self._pmop_s + self._dispatch_s + collect_s,
                ),
            )

        # Decipher sums the factor diagonals on the host: no wait
        if self.kind == "single":
            with span("spdc.decipher"):
                det = decipher(self.seeds[0], self.metas[0], l, u,
                               faithful=self.client.faithful_sign,
                               log2_scale=self.log2_scale)
            return SPDCResult(
                det=det,
                verified=bool(np.all(verdict.ok)),
                residual=verdict.residual,
                seed=self.seeds[0],
                meta=self.metas[0],
                comm=comm,
                padding=self.padding,
                num_servers=self.num_servers,
                report=build_report(),
            )
        with span("spdc.decipher"):
            dets = decipher_batch(self.seeds, self.metas, l, u,
                                  faithful=self.client.faithful_sign,
                                  log2_scale=np.asarray(self.log2_scale))
        return SPDCBatchResult(
            dets=dets,
            verified=np.atleast_1d(np.asarray(verdict.ok)),
            residual=np.atleast_1d(np.asarray(verdict.residual)),
            seeds=self.seeds,
            metas=self.metas,
            comm=comm,
            padding=self.padding,
            num_servers=self.num_servers,
            report=build_report(),
            paddings=self.paddings,
            pad_to=self.pad_to,
        )


@dataclass
class PendingResult:
    """A `Session.start`ed protocol run awaiting its RRVP tail.

    `result(timeout=)` blocks on the in-flight Parallelize stage (the
    timeout is a client-side wait — expiry raises TransportTimeout and
    the dispatch keeps running; call `result` again to re-wait), then
    runs `Session.collect` on the CALLING thread: authenticate, recovery,
    and decipher touch session secrets and stay on the client thread by
    construction — only the wire wait is asynchronous.
    """

    session: Session
    transport: Any
    future: Any

    def done(self) -> bool:
        """True once the dispatch resolved (collect still pending)."""
        return self.future.done()

    def result(self, timeout: float | None = None):
        out = self.transport.result(self.future, timeout)
        return self.session.collect(out, transport=self.transport)
