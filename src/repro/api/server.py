"""EdgeServer — the untrusted worker role of the SPDC protocol.

A stateless executor of ShardTasks: given its encrypted block row and the
U rows relayed from upstream, it computes the (L strip, U strip) of paper
Algorithm 3's block row `task.server` and reports them back. It holds NO
session state between tasks, sees ONLY ciphertext (the trust boundary —
DESIGN.md §7), and its arithmetic is exactly `core.lu.lu_block_row` in
the task's declared operation order, so an honest EdgeServer's strips are
bit-identical to the strips the fused single-process sweep produces for
the same inputs.

Misbehavior is first-class but OPT-IN: `run(task, faults=plan)` applies
the core.faults model to the strips this server reports — tampering its
own block row before the relay hop forwards it, which is precisely the
paper's in-band threat (downstream servers consume the poisoned rows).
Faults bind to the initial assignment (attempt 0): verification-driven
re-dispatches go to replacement servers the pool chose specifically for
not being the culprit, so repair tasks always execute honestly.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from jax.scipy.linalg import solve_triangular

from repro.core.faults import corrupt_strip, normalize_plan, sample_delay
from repro.distrib.recovery import lu_block_row_jit

from .messages import ShardResult, ShardTask, TriSolveResult, TriSolveTask

__all__ = ["EdgeServer"]

def _embed_rows(zeros, strip, row0, rows):
    """Place a (…, rows, n) strip into a zero (…, n, n) frame (eager —
    values only; lu_block_row never reads outside the strip)."""
    return zeros.at[..., row0 : row0 + rows, :].set(strip)


class EdgeServer:
    """One untrusted edge worker (see module docstring).

    worker_id identifies the PHYSICAL worker (process/thread slot) — it
    is labelling for logs and fault routing, not protocol state.
    """

    def __init__(self, worker_id: int | None = None):
        self.worker_id = worker_id

    def run(self, task, faults=()):
        """Execute one protocol task → its result message.

        ShardTask → ShardResult (one LU block row); TriSolveTask →
        TriSolveResult (one triangular-solve column chunk, DESIGN.md
        §12). The dispatch is by message type, so every transport whose
        worker loop decodes frames with `wire.decode_message` serves the
        linalg rounds with zero transport-side changes.

        For ShardTasks, the strips are embedded into zero-filled
        (…, n', n') frames because `lu_block_row` is written against
        full-matrix coordinates; it only ever READS block row
        `task.server` of x and the rows above `task.server` of u, so the
        zeros are never consumed and the embedding changes no arithmetic.
        """
        if isinstance(task, TriSolveTask):
            return self._run_trisolve(task, faults)
        if task.style not in ("nserver", "pipeline"):
            raise ValueError(f"unknown task style {task.style!r}")
        n, b, s0 = task.n, task.block, task.server * task.block
        if b * task.num_servers != n:
            raise ValueError(
                f"task block {b}×{task.num_servers} servers does not tile "
                f"n'={n}"
            )
        x_row = jnp.asarray(task.x_row)
        lead = x_row.shape[:-2]
        zeros = jnp.zeros((*lead, n, n), dtype=x_row.dtype)
        x = _embed_rows(zeros, x_row, s0, b)
        if task.u_upstream is not None and task.u_upstream.shape[-2]:
            u_up = jnp.asarray(task.u_upstream, dtype=x_row.dtype)
            u = _embed_rows(zeros, u_up, 0, int(u_up.shape[-2]))
        else:
            if task.server != 0:
                raise ValueError(
                    f"server {task.server} needs upstream U rows; the "
                    "transport must thread the one-way relay"
                )
            u = zeros
        self._straggle(task, faults)
        l_row, u_row = lu_block_row_jit(x, u, task.server, task.num_servers,
                                        style=task.style)
        l_row, u_row = self._misbehave(task, l_row, u_row, faults)
        return ShardResult(
            server=task.server,
            l_row=np.asarray(l_row),
            u_row=np.asarray(u_row),
            subseed=task.subseed,
            attempt=task.attempt,
            session_id=task.session_id,
        )

    def _run_trisolve(self, task: TriSolveTask, faults=()) -> TriSolveResult:
        """One triangular-solve column chunk through the session's
        verified factors: X' y = rhs via L a = rhs, U y = a — or the
        adjoint X'ᵀ y = rhs via Uᵀ a = rhs, Lᵀ y = a when
        task.transpose. The server only ever touches material it already
        produced (l/u) or blinded/public RHS columns."""
        l = jnp.asarray(task.l)
        u = jnp.asarray(task.u)
        rhs = jnp.asarray(task.rhs, dtype=l.dtype)
        if l.ndim != 2 or l.shape != u.shape or rhs.shape[0] != l.shape[-1]:
            raise ValueError(
                f"trisolve shapes disagree: l {l.shape}, u {u.shape}, "
                f"rhs {rhs.shape}"
            )
        self._straggle(task, faults)
        if task.transpose:
            a = solve_triangular(u, rhs, lower=False, trans=1)
            y = solve_triangular(l, a, lower=True, trans=1)
        else:
            a = solve_triangular(l, rhs, lower=True)
            y = solve_triangular(u, a, lower=False)
        y = self._misbehave_solve(task, y, faults)
        return TriSolveResult(
            server=task.server,
            y=np.asarray(y),
            subseed=task.subseed,
            transpose=task.transpose,
            col0=task.col0,
            attempt=task.attempt,
            session_id=task.session_id,
        )

    def _misbehave_solve(self, task, y, faults):
        """Trisolve leg of the fault model: a tamper fault naming this
        worker corrupts the reported solution chunk (any target — the
        chunk is the only thing this round reports); a dropout zeroes
        it. Initial dispatch only, like `_misbehave` — re-issues go to
        replacements chosen for not being the culprit.

        Positions are picked directly inside the (n', c) chunk rather
        than through `corrupt_strip`'s LU-strip geometry: a solve chunk
        has no triangle structure, and the strip mapping can land outside
        a narrow chunk (where jax's out-of-bounds scatter silently drops
        the update — a tamper that never happened)."""
        plan = [
            f for f in normalize_plan(faults)
            if f.server == self._bound(task) and task.attempt == 0
            and f.kind != "delay"
        ]
        for f in plan:
            if f.kind == "dropout":
                y = jnp.zeros_like(y)
                continue
            if f.mode == "block":
                y = y * (1.0 + f.magnitude)
                continue
            h = (f.seed * 1315423911 + f.server * 2654435761) & 0x7FFFFFFF
            r = h % y.shape[0]
            c = (h >> 8) % y.shape[1]
            if f.mode == "sign_flip":
                y = y.at[r, c].multiply(-1.0)
            else:
                y = y.at[r, c].set(y[r, c] * (1.0 + f.magnitude)
                                   + f.magnitude)
        return y

    def _bound(self, task) -> int:
        """The id faults bind to: the PHYSICAL worker when known, else the
        task's block row. Identical on the classic paths (transports run
        task i on worker i); under rateless dispatch ``task.server`` is a
        strip index while the fault plan names workers, so the physical
        id is the one that matters."""
        return self.worker_id if self.worker_id is not None else task.server

    def _straggle(self, task, faults) -> None:
        """Play this worker's wall-clock delay faults (core.faults
        ``delay_s``) as a real sleep — unlike tampering, slowness is a
        property of the MACHINE, so it fires on every attempt, repairs
        and probation probes included (a retry on the same slow worker is
        slow again; a retry elsewhere escapes it)."""
        bound = self._bound(task)
        wait = sum(
            sample_delay(f, token=task.subseed)
            for f in normalize_plan(faults)
            if f.kind == "delay" and f.server == bound and f.delay_s > 0.0
        )
        if wait > 0.0:
            import time

            time.sleep(wait)

    def _misbehave(self, task, l_row, u_row, faults):
        """Apply the simulated fault model to this server's reported strips.

        Only faults naming this worker (`_bound`) fire, and only on the
        initial dispatch (module docstring). Because message transports
        forward the reported U row down the relay, every tamper here is
        effectively in-band — the cascading-poison threat model.
        """
        plan = [
            f for f in normalize_plan(faults)
            if f.server == self._bound(task) and task.attempt == 0
            and f.kind != "delay"
        ]
        if not plan:
            return l_row, u_row
        batched = l_row.ndim == 3
        for f in plan:
            targets = ("l", "u") if f.kind == "dropout" else tuple(f.target)

            def hit(orig, factor, f=f):
                bad = corrupt_strip(orig, f, n=task.n, factor=factor)
                if f.matrices is not None and batched:
                    idx = np.asarray(f.matrices, dtype=np.int32)
                    bad = orig.at[idx].set(bad[idx])
                return bad

            if "l" in targets:
                l_row = hit(l_row, "l")
            if "u" in targets:
                u_row = hit(u_row, "u")
        return l_row, u_row
