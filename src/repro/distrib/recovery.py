"""Verification-driven recovery — re-dispatch ONE shard, not the protocol.

The paper's client has exactly one remedy when Authenticate rejects: throw
the whole result away and re-outsource (re-cipher, re-send, re-factor —
O(n²) wire + O(n³) compute, again). Algorithm 3's block-row ownership
admits something far cheaper: the blocked-Q1 localization
(core.verify.localize) names the faulty server, every strip ABOVE it is
verified-clean, and the faulty server's strip is a pure function of

    (its shard of the ciphertext) × (the verified U rows above it)

— so the client re-derives that one shard (core.augment.augment_block_row:
replay the padding draw, slice the block row), re-keys the dispatch channel
with a fresh sub-seed, hands the shard + upstream U rows to a standby (or
any healthy) server, and splices the recomputed strips into the wavefront
result. Cost: one recompute of ~1/N of the factorization plus O(n·b) wire
— vs a full restart.

The loop is *verification-driven*: recompute → re-verify → repeat. A
report-only fault converges in one round; an in-band relay poisoning
(the tampered U row was consumed downstream) heals one block row per
round, cascading at most N−s rounds — each round's first-failing block is
provably computable from the verified rows above it, so progress is
monotone. `max_rounds` defaults to num_servers (the worst cascade).

N+r standby (ServerPool): the client provisions r spare servers up front;
a failed server is retired and its shard re-dispatched to a spare, so
recovery costs one extra hop instead of a renegotiation. With the pool
exhausted, re-dispatch falls back to the failed server's healthy neighbor
(the client has no reason to trust the culprit twice).
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

import jax

from repro.core.augment import augment_block_row
from repro.core.lu import lu_block_row
from repro.core.verify import Verdict, authenticate

#: the jitted strip recompute, one program per (shape, server, N, style)
#: for a single matrix and a stack alike: jitted like the inline sweep
#: (api.transport._lu_sweep), so its strips match the sweep's bit for bit.
lu_block_row_jit = jax.jit(
    lu_block_row, static_argnums=(2, 3), static_argnames=("style",)
)


def dispatch_subseed(digest: bytes, server: int, attempt: int) -> bytes:
    """Fresh per-dispatch sub-seed: H(Ψ-digest ‖ server ‖ attempt).

    Re-keys the client→replacement channel so a replayed or stale shard
    from the original (possibly malicious) server cannot impersonate the
    re-dispatch. Derived, never stored — the client only keeps Ψ's digest.
    """
    h = hashlib.sha256()
    h.update(digest)
    h.update(struct.pack(">qq", int(server), int(attempt)))
    return h.digest()


def trisolve_subseed(
    digest: bytes, rnd: int, chunk: int, attempt: int
) -> bytes:
    """Dispatch-channel key for one triangular-solve chunk (DESIGN.md
    §12): H(Ψ-digest ‖ "trisolve" ‖ round ‖ chunk ‖ attempt).

    A lane DISJOINT from `dispatch_subseed` (the literal tag separates
    the domains), so a server holding LU-round sub-seeds learns nothing
    about the solve rounds' probe or masking keys, and a replayed chunk
    cannot impersonate a re-issue (attempt is part of the derivation).
    """
    h = hashlib.sha256()
    h.update(digest)
    h.update(b"trisolve")
    h.update(struct.pack(">qqq", int(rnd), int(chunk), int(attempt)))
    return h.digest()


def recover_solve(
    results: list,
    bad: list[int],
    *,
    make_task,
    verify_chunk,
    transport,
    num_servers: int,
    standby: int = 0,
    max_rounds: int | None = None,
    pool: "ServerPool | None" = None,
) -> tuple[list, "RecoveryReport"]:
    """Heal rejected triangular-solve chunks by re-dispatching them.

    The solve analogue of `recover_lu`, column-wise instead of row-wise:
    chunks are independent (no relay, no cascade), so each round simply
    re-issues every failed chunk to a pool replacement with attempt+1 —
    a fresh `trisolve_subseed` keys the re-dispatch — and re-verifies it
    with the round's check. Convergence needs one honest replacement per
    chunk; `max_rounds` (default num_servers) bounds a fleet that keeps
    lying.

    results: the round's TriSolveResult list, indexed by chunk (None for
        timeouts). Healed in place on a copy, returned.
    bad: chunk indices whose verification failed (or that are None).
    make_task(chunk, attempt, replacement) -> TriSolveTask: mints the
        re-issue — the LinalgSession closure holds the factors/RHS and
        the digest, so this module never touches secret material.
    verify_chunk(chunk, result) -> float | None: residual if the healed
        chunk now verifies, None if it still fails.
    """
    pool = pool or ServerPool(num_servers, standby)
    max_rounds = num_servers if max_rounds is None else max_rounds
    report = RecoveryReport(ok=False, rounds=0)
    results = list(results)
    pending = sorted(set(bad))
    attempts: dict[int, int] = {}
    for rnd in range(max_rounds):
        if not pending:
            break
        report.rounds = rnd + 1
        still_bad = []
        for c in pending:
            attempts[c] = attempts.get(c, 0) + 1
            phys, pool = pool.replacement_for(c % num_servers)
            task = make_task(c, attempts[c], phys)
            res = transport.repair(task, replacement=phys)
            residual = verify_chunk(c, res)
            if residual is None:
                still_bad.append(c)
                continue
            results[c] = res
            report.events.append(
                RecoveryEvent(
                    round=rnd,
                    server=c,
                    replacement=phys,
                    residual=float(residual),
                    comm_elements=2 * task.rhs.size + 2 * task.l.size,
                    subseed=task.subseed.hex(),
                )
            )
        pending = still_bad
    report.ok = not pending
    report.standby_used = pool.spares_used
    return results, report


def recovery_comm_elements(n: int, num_servers: int, server: int) -> int:
    """Wire cost (elements) of re-dispatching server `server`'s shard:
    its (b, n) ciphertext block row + the verified upstream U rows
    (their structural support only) + the (2·b·n) L/U strips coming back."""
    b = n // num_servers
    upstream = sum(b * (n - k * b) for k in range(server))
    return b * n + upstream + 2 * b * n


@dataclass(frozen=True)
class ServerPool:
    """N workers + r standbys (frozen bookkeeping; replace() returns the
    next pool state so recovery rounds stay functional)."""

    num_servers: int
    standby: int = 0
    spares_used: int = 0
    retired: tuple[int, ...] = ()

    def replacement_for(self, server: int) -> tuple[int, "ServerPool"]:
        """Physical id that re-runs `server`'s shard, and the next pool.

        Standbys are numbered num_servers..num_servers+standby−1; once
        exhausted, the shard goes to the culprit's next healthy neighbor.
        """
        retired = (*self.retired, server)
        if self.spares_used < self.standby:
            phys = self.num_servers + self.spares_used
            pool = ServerPool(
                self.num_servers,
                self.standby,
                self.spares_used + 1,
                retired,
            )
            return phys, pool
        # no spares: prefer a never-retired neighbor; failing that, a
        # retired-but-healed one — anyone but the culprit itself
        candidates = [
            (server + 1 + i) % self.num_servers
            for i in range(max(self.num_servers - 1, 1))
        ]
        fresh = [c for c in candidates if c not in retired]
        phys = fresh[0] if fresh else candidates[0]
        return phys, ServerPool(
            self.num_servers,
            self.standby,
            self.spares_used,
            retired,
        )


@dataclass(frozen=True)
class RecoveryEvent:
    """One re-dispatch: which logical server failed, who re-ran its shard."""

    round: int
    server: int
    replacement: int
    residual: float
    comm_elements: int
    subseed: str  # hex digest of the fresh dispatch channel key
    matrices: tuple[int, ...] | None = None  # batch indices spliced


@dataclass
class RecoveryReport:
    """Outcome of the verification-driven re-dispatch loop."""

    ok: bool
    rounds: int
    events: list[RecoveryEvent] = field(default_factory=list)
    standby_used: int = 0

    @property
    def servers_replaced(self) -> tuple[int, ...]:
        return tuple(sorted({e.server for e in self.events}))


def recover_lu(
    l: jnp.ndarray,
    u: jnp.ndarray,
    x: jnp.ndarray,
    *,
    num_servers: int,
    method: str = "q3",
    standby: int = 0,
    max_rounds: int | None = None,
    digest: bytes = b"",
    pool: ServerPool | None = None,
    style: str = "nserver",
    verdict: Verdict | None = None,
    dispatch=None,
) -> tuple[jnp.ndarray, jnp.ndarray, Verdict, RecoveryReport]:
    """Heal a rejected factorization by re-dispatching localized shards.

    x is the (verified-held) ciphertext the client dispatched — (n, n) or a
    (B, n, n) stack. Each round: authenticate → take each matrix's FIRST
    failing block row (rows above are clean) → recompute that strip from x
    and the verified upstream U rows (lu_block_row — the same arithmetic a
    replacement server runs) → splice it into l/u for exactly the matrices
    that blamed that server. Converges in ≤ num_servers rounds for any
    single-server fault, including in-band relay poisoning (one healed row
    per round). `style` must name the Parallelize implementation that
    produced the surviving rows ("nserver" simulation / "pipeline"
    shard_map) so the recompute replays its exact operation order — see
    core.lu.lu_block_row. When the replacement's arithmetic still cannot
    be bitwise-identical to the original (a jitted pipeline vs a host-side
    recompute, or a genuinely different machine), splice-induced rounding
    can push a downstream row's residual over ε(N); the loop simply heals
    that row on the next round — an extra hop, never a wrong answer.

    dispatch: optional hook actually EXECUTING one re-dispatch —
    ``dispatch(x, u, server, attempt, replacement) -> (l_row, u_row)``.
    The role-split Session passes one that mints a fresh ShardTask
    (sub-seed H(Ψ ‖ server ‖ attempt), verified upstream U rows attached)
    and runs it on the replacement worker through its Transport
    (repro.api.client), so recovery stays client-driven under every
    execution boundary. Default: recompute locally via lu_block_row —
    identical arithmetic, no transport.

    Returns (l, u, final verdict, report).
    """
    n = x.shape[-1]
    batched = x.ndim == 3
    pool = pool or ServerPool(num_servers, standby)
    max_rounds = num_servers if max_rounds is None else max_rounds
    report = RecoveryReport(ok=False, rounds=0)
    attempts: dict[int, int] = {}

    def _probe_rng(rnd: int) -> np.random.Generator:
        # fresh SECRET probe per verification round — a server that solved
        # one probe's null space gains nothing against the next
        h = hashlib.sha256(digest + struct.pack(">q", rnd)).digest()
        return np.random.default_rng(int.from_bytes(h[:8], "big"))

    if verdict is None:
        verdict = authenticate(
            l, u, x, num_servers=num_servers, method=method,
            rng=_probe_rng(-1),
        )

    for rnd in range(max_rounds):
        # the global verdict is the accept/reject authority; localization
        # only guides healing — matrices whose verdict already passes are
        # never re-dispatched (a block residual may graze the raw ε(N)
        # while the configured method accepts)
        failing = ~np.atleast_1d(np.asarray(verdict.ok))
        culprit = np.where(
            failing, np.atleast_1d(np.asarray(verdict.culprit)), -1
        )
        to_heal = sorted({int(c) for c in culprit if c >= 0})
        if not to_heal:
            # recovered, or the failure is global and unattributable —
            # either way there is nothing localizable left to re-dispatch
            break
        report.rounds = rnd + 1
        for s in to_heal:
            attempts[s] = attempts.get(s, 0) + 1
            phys, pool = pool.replacement_for(s)
            if dispatch is not None:
                l_row, u_row = dispatch(x, u, s, attempts[s], phys)
            else:
                l_row, u_row = lu_block_row_jit(x, u, s, num_servers,
                                                style=style)
            b = n // num_servers
            sl = slice(s * b, (s + 1) * b)
            if batched:
                idx = np.nonzero(culprit == s)[0]
                l = l.at[idx, sl, :].set(l_row[idx])
                u = u.at[idx, sl, :].set(u_row[idx])
                sres = float(np.max(verdict.server_residual[idx, s]))
                hit: tuple[int, ...] | None = tuple(int(i) for i in idx)
            else:
                l = l.at[..., sl, :].set(l_row)
                u = u.at[..., sl, :].set(u_row)
                sres = float(verdict.server_residual[s])
                hit = None
            report.events.append(
                RecoveryEvent(
                    round=rnd,
                    server=s,
                    replacement=phys,
                    residual=sres,
                    comm_elements=recovery_comm_elements(n, num_servers, s),
                    subseed=dispatch_subseed(digest, s, attempts[s]).hex(),
                    matrices=hit,
                )
            )
        verdict = authenticate(
            l, u, x, num_servers=num_servers, method=method,
            rng=_probe_rng(rnd),
        )

    report.ok = bool(np.all(verdict.ok))
    report.standby_used = pool.spares_used
    return l, u, verdict, report


def rederive_shard(
    x: jnp.ndarray,
    *,
    padding: int,
    server: int,
    num_servers: int,
    aug_key=None,
) -> jnp.ndarray:
    """Re-derive one server's shard of the augmented ciphertext from the
    (unaugmented) ciphertext x — replaying the deterministic padding draw
    instead of caching X_aug (core.augment.augment_block_row). Returns the
    (…, b, n_aug) block row the replacement server receives."""
    n_aug = x.shape[-1] + padding
    if n_aug % num_servers != 0:
        raise ValueError(f"n+p={n_aug} not partitioned by N={num_servers}")
    b = n_aug // num_servers
    return augment_block_row(x, padding, server * b, b, key=aug_key)
