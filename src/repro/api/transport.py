"""Transports — how ShardTasks reach edge servers and results come back.

A Transport is the pluggable boundary between the SPDC client role and
the N untrusted workers. All transports execute the SAME protocol
messages; they differ in what the wire physically is:

  * ``InlineTransport``      — client and servers share one process and
    the "wire" is elided: the fused, jitted single-sweep fast path of the
    pre-split protocol (bit-identical to it, and the gateway's
    throughput path). ShardTasks still exist (`Session.tasks()`), the
    fused path just never materializes them.
  * ``ShardMapTransport``    — the distrib.spdc_pipeline shard_map
    program: the servers on the JAX mesh's chips (k = N / chips per
    chip), the relay a real `lax.ppermute` between chips. Fused like
    inline (the sweep is one SPMD program), after a placement phase of
    its own (`place`).
  * ``ThreadPoolTransport``  — one EdgeServer object per worker slot,
    tasks executed on a thread pool, the relay threaded between them as
    in-memory messages. The cheapest transport with a real
    scheduler-visible boundary.
  * ``MultiprocessTransport``— spawned worker PROCESSES; every message
    crosses the boundary as `to_bytes()` frames over an OS pipe and is
    decoded with `from_bytes()` on the far side.
  * ``SocketTransport``      — persistent worker DAEMONS reached over
    TCP or Unix-domain sockets (socket_transport.py): length-prefixed
    wire-codec frames, a versioned HELLO handshake, and warm worker
    processes whose jit caches survive across sessions and client
    restarts (launch/serve_worker.py). The closest shape to the paper's
    real deployment.

Dispatch surface (the async-overlap redesign, DESIGN.md §9):

  * ``start(task, worker_id) -> Future``  — the canonical NONBLOCKING
    primitive: ship one ShardTask to one worker, return immediately.
    The rateless scheduler streams strips with it, and `Session.start`
    rides it so the client's PMOP for batch k+1 overlaps the wire time
    of batch k.
  * ``result(future, timeout)``           — resolve a started dispatch,
    mapping a client-side wait expiry to the typed `TransportTimeout`.
  * ``submit(task, worker_id)``           — the BLOCKING facade:
    ``result(start(...))``. Kept for callers that want one strip now.
  * ``factor(tasks)`` / ``factor_async(tasks)`` — one session's whole
    relay sweep, blocking / as a Future (the unit `Session.start`
    pipelines).

One-way model: for the sequential (message) transports the relay is run
by the transport — task i executes only after i−1's result, and its
``u_upstream`` is exactly the U rows servers 0..i−1 reported, i.e. the
content of the paper's single S_{i-1} → S_i send. No server ever
receives anything from downstream, and the client never ships plaintext
or key material (messages.ShardTask).

Lifecycle: every transport is a context manager with an idempotent
``close()`` and a ``closed`` flag; dispatching on a closed transport
raises TransportError. Long-lived role objects (SPDCClient, the
gateway) BUILD and OWN their transports from a `TransportConfig` and
close them deterministically; the one-shot facades
(`outsource_determinant(transport=...)`) resolve strings and configs to
process-wide SHARED instances so repeated calls — and every gateway
flush — reuse one warm pool instead of respawning workers per call;
`close_all()` runs at interpreter exit.

Fault simulation: ``factor(tasks, faults=plan)`` plays core.faults
misbehavior on the matching workers (a FaultPlanFrame control message on
the message transports). Faults bind to initial dispatches; repairs run
honestly on replacement workers (api.server docstring).
"""
from __future__ import annotations

import atexit
import threading
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from dataclasses import dataclass
from functools import partial

import jax
import numpy as np

from repro.core.lu import lu_nserver

from .messages import FaultPlanFrame, ShardResult, ShardTask
from .server import EdgeServer

__all__ = [
    "Transport",
    "TransportConfig",
    "TransportError",
    "TransportTimeout",
    "TransportWorkerDied",
    "TransportProtocolError",
    "AcceleratorHeld",
    "InlineTransport",
    "ShardMapTransport",
    "ThreadPoolTransport",
    "MultiprocessTransport",
    "resolve_transport",
    "close_all",
]


class TransportError(RuntimeError):
    """A worker died, timed out, replied with a malformed frame, or the
    transport was used after close()."""


class TransportTimeout(TransportError):
    """A per-request wall-clock deadline expired before the worker
    replied. On the process-backed transports the worker (multiprocess)
    or its connection (socket) is killed — a reply arriving after the
    deadline would desynchronize the lock-step channel — and respawned /
    reconnected lazily on the next dispatch; the caller treats the
    request as a dropout — zero strips, localize, re-dispatch — exactly
    the rounds-deadline straggler policy (core.faults.resolve_delays)."""


class TransportWorkerDied(TransportError):
    """The worker process/thread/connection went away mid-request
    (crash, kill, broken pipe, dropped socket). Unlike a timeout the
    worker did not merely straggle — transports respawn or reconnect it
    and retry the request once before surfacing the error; the
    fleet-health layer counts it as a failure either way."""


class TransportProtocolError(TransportError):
    """The far side violated the framing or handshake protocol: a
    truncated or oversized frame, a non-wire-codec reply, or a HELLO
    carrying an incompatible protocol/wire version. Unlike a death this
    is not retried — a peer speaking the wrong protocol will speak it
    again — the connection is dropped and the error surfaces typed."""


class AcceleratorHeld(TransportError):
    """A spawning transport was asked for local worker processes while
    this process holds an accelerator. Each worker imports JAX and would
    claim the chip, which belongs to one process at a time: it would fail
    on the chip's lock or hang. Raised at construction, never retried."""


def refuse_spawn_on_accelerator(transport: str) -> None:
    """Raise AcceleratorHeld unless this process's backend is the CPU."""
    from repro.runtime import on_cpu

    if not on_cpu():
        raise AcceleratorHeld(
            f"the {transport} transport spawns worker processes that "
            f"import JAX, but this process holds the "
            f"{jax.default_backend()} backend; use the inline or shardmap "
            "transport here, or socket daemons started on other hosts"
        )


@partial(jax.jit, static_argnames=("num_servers", "faults"))
def _lu_sweep(x_aug, *, num_servers, faults=()):
    """Jitted fused sweep for one (n', n') matrix or a (B, n', n') stack —
    ONE device program per (shape, N, fault-plan), whatever the rank,
    traced once and then a cached dispatch (DESIGN.md §3)."""
    l, u, _ = lu_nserver(x_aug, num_servers, faults=faults)
    return l, u


def serve_frame(edge: EdgeServer, state: dict, data: bytes) -> bytes:
    """One worker-side request → reply step, shared by every byte-framed
    worker loop (the multiprocess pipe worker and the socket daemon).

    Strict request-reply: EVERY frame gets exactly one reply — ShardTask
    → ShardResult bytes, FaultPlanFrame → b"ACK", anything that fails
    (including a frame that does not decode) → an ERR frame. One reply
    per request keeps the channel in lock-step, so a failure can never
    desynchronize later requests' replies. `state` holds the channel's
    fault plan (simulation control; per-pipe on multiprocess, per-
    connection on sockets).
    """
    from .wire import decode_message

    try:  # noqa: SIM105 — report every failure, don't die silently
        msg = decode_message(data)
        if isinstance(msg, FaultPlanFrame):
            state["plan"] = msg.plan
            return b"ACK"
        return edge.run(msg, faults=state.get("plan", ())).to_bytes()
    except Exception as e:  # noqa: BLE001
        return b"ERR:" + repr(e).encode()


class Transport:
    """Base transport: the message-executing interface.

    fused: True when `sweep()` runs the whole factorization as one fused
        program and `Session` should skip task materialization.
    style: the core.lu.lu_block_row operation order this transport's
        factors follow — what repair recomputes must replay.
    place: None, or on a fused transport whose servers sit on chips of
        their own, `place(x_aug, num_servers)` → the ciphertext on those
        chips, a phase that `Session` runs (and times) before `sweep()`.
    """

    name = "abstract"
    fused = False
    style = "nserver"
    place = None

    _closed = False
    _driver_pool = None
    _driver_lock = threading.Lock()

    @property
    def closed(self) -> bool:
        """True once close() ran; a closed transport refuses dispatch."""
        return self._closed

    def _ensure_open(self) -> None:
        if self._closed:
            raise TransportError(
                f"transport {self.name!r} is closed; build or resolve a "
                "fresh one"
            )

    # -- whole-sweep surface -------------------------------------------------

    def factor(self, tasks, faults=()) -> list[ShardResult]:
        """Run one session's initial ShardTasks (the full sweep)."""
        raise NotImplementedError

    def driver_submit(self, fn, *args) -> Future:
        """Run `fn(*args)` on this transport's driver threads — the
        mechanism behind `factor_async` and `Session.start`. 4 drivers
        bound the pipeline depth, not the worker parallelism."""
        self._ensure_open()
        with Transport._driver_lock:
            if self._driver_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                # instance attribute (class default is None)
                self._driver_pool = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix=f"spdc-{self.name}-drv"
                )
        return self._driver_pool.submit(fn, *args)

    def factor_async(self, tasks, faults=()) -> Future:
        """`factor` as a Future: the whole relay sweep runs on a driver
        thread so the caller — `Session.start` — can overlap the client
        PMOP for the NEXT session with this one's wire time. The relay
        inside stays strictly sequential (the one-way chain is a data
        dependency); only the session boundary is asynchronous."""
        return self.driver_submit(self.factor, tasks, faults)

    def repair(self, task: ShardTask, *, replacement: int) -> ShardResult:
        """Run one verification-driven re-dispatch on `replacement`."""
        raise NotImplementedError

    # -- per-task surface (the async-overlap redesign) -----------------------

    def start(self, task: ShardTask, worker_id: int, *, faults=(),
              timeout: float | None = None) -> Future:
        """Nonblocking single-task dispatch → `concurrent.futures.Future`
        resolving to a ShardResult (or raising a TransportError). The
        canonical async primitive: the rateless scheduler streams tasks
        to whichever workers are free with it, and `submit` is its
        blocking facade. `timeout` bounds the request where the transport
        can enforce one (multiprocess kills the worker, socket drops the
        connection); where it cannot (a thread has no preemption), the
        caller enforces its own wait and the late future becomes a
        zombie — discarded on arrival, the worker busy until it really
        returns. Fused transports don't have per-task workers; they
        raise."""
        raise NotImplementedError(
            f"transport {self.name!r} has no per-task dispatch surface "
            "(fused transports run the sweep as one program)"
        )

    def result(self, future: Future, timeout: float | None = None
               ) -> ShardResult:
        """Resolve a `start`ed dispatch. `timeout` is a CLIENT-side wait
        bound: expiry raises the typed TransportTimeout but does not kill
        the worker (pass timeout= to `start` for an enforced deadline);
        the future keeps running and may be resolved again later."""
        try:
            return future.result(timeout)
        except _FutureTimeout as e:
            raise TransportTimeout(
                f"dispatch did not resolve within the {timeout}s "
                "client-side wait (the worker-side request may still be "
                "running; start(timeout=) enforces a worker deadline)"
            ) from e

    def submit(self, task: ShardTask, worker_id: int, *, faults=(),
               timeout: float | None = None) -> ShardResult:
        """Blocking single-task facade: `result(start(...))`."""
        return self.result(
            self.start(task, worker_id, faults=faults, timeout=timeout)
        )

    def solve_shards(self, tasks, faults=(), timeout: float | None = None):
        """One triangular-solve round (DESIGN.md §12): dispatch each
        TriSolveTask to its column-chunk's worker and gather the
        TriSolveResults in task order.

        Chunks are independent (column-partitioned RHS — no relay, no
        data dependency), so transports with a per-task surface run them
        concurrently via `start`; fused transports without one (shardmap)
        fall back to an inline EdgeServer, same as their `repair` path. A
        straggler past `timeout` yields None in its slot — the caller
        treats it as a dropout: the residual check localizes the missing
        chunk and recovery re-dispatches it.
        """
        self._ensure_open()
        futures = []
        for t in tasks:
            try:
                futures.append(
                    self.start(t, t.server, faults=faults, timeout=timeout)
                )
            except NotImplementedError:
                fut: Future = Future()
                try:
                    fut.set_result(EdgeServer(t.server).run(t, faults))
                except Exception as e:  # noqa: BLE001 — future carries it
                    fut.set_exception(e)
                futures.append(fut)
        out = []
        for fut in futures:
            try:
                out.append(self.result(fut, timeout))
            except TransportTimeout:
                out.append(None)
        return out

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Release workers/pools; idempotent. Subclasses extend this and
        MUST call super().close() so `closed` flips and the driver pool
        shuts down. Shared instances are closed at interpreter exit."""
        self._closed = True
        pool, self._driver_pool = self._driver_pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class InlineTransport(Transport):
    """Degenerate (single-process) transport: today's jitted fast path.

    `sweep()` IS the pre-split protocol's server stage — one jitted
    program per (shape, N, fault plan) for a single matrix and a stack
    alike, whose strips the jitted recovery recompute
    (`distrib.recovery.lu_block_row_jit`) bit-matches. The message methods
    exist for uniformity (tests drive them); the Session prefers `sweep()`.
    """

    name = "inline"
    fused = True

    def sweep(self, x_aug, num_servers: int, faults=()):
        self._ensure_open()
        return _lu_sweep(x_aug, num_servers=num_servers, faults=faults)

    def factor(self, tasks, faults=()):
        self._ensure_open()
        return _run_relay(tasks, lambda t, wid: EdgeServer(wid).run(t, faults))

    def repair(self, task, *, replacement):
        self._ensure_open()
        return EdgeServer(replacement).run(task)

    def start(self, task, worker_id, *, faults=(), timeout=None):
        """Synchronous start: compute now, return a completed Future.
        Lets the rateless scheduler run against the inline boundary
        (tests, and the degradation ladder's last rung)."""
        self._ensure_open()
        fut: Future = Future()
        try:
            fut.set_result(EdgeServer(worker_id).run(task, faults))
        except Exception as e:  # noqa: BLE001 — future carries it
            fut.set_exception(e)
        return fut


class ShardMapTransport(Transport):
    """distrib.spdc_pipeline as a transport: the servers on the chips of a
    1-D mesh, k = N / chips consecutive servers per chip, the relay a real
    lax.ppermute where it crosses chips (DESIGN.md §2). Fused — the sweep
    is a single SPMD program; repairs recompute host-side in the
    pipeline's operation order ("pipeline" style), exactly as recovery
    always has.

    `place` is a phase of its own: it puts the ciphertext's block rows on
    their servers' chips before the sweep (the `spdc.scatter` span).
    """

    name = "shardmap"
    fused = True
    style = "pipeline"

    def __init__(self, program: str = "baseline"):
        self.program = program

    def place(self, x_aug, num_servers: int):
        self._ensure_open()
        from repro.distrib.spdc_pipeline import relay_sharding

        return jax.device_put(x_aug, relay_sharding(
            num_servers, x_aug.ndim == 3, program=self.program))

    def sweep(self, x_aug, num_servers: int, faults=()):
        self._ensure_open()
        from repro.distrib.spdc_pipeline import lu_nserver_shardmap

        return lu_nserver_shardmap(
            x_aug, num_servers, program=self.program, faults=faults
        )

    def repair(self, task, *, replacement):
        self._ensure_open()
        return EdgeServer(replacement).run(task)


def _run_relay(tasks, execute) -> list[ShardResult]:
    """The one-way relay schedule over single-shot workers: execute task i
    with u_upstream = the U rows servers 0..i−1 reported. `execute(task,
    worker_id)` runs one task on one worker.

    A per-request TransportTimeout is absorbed here as a DROPOUT: the
    straggler's strips are substituted with zeros — byte-for-byte what a
    `kind="dropout"` fault reports — so verification localizes it and
    recovery re-dispatches, identically to the pipeline-rounds deadline
    path (core.faults.resolve_delays). One straggler policy, two clocks.
    """
    tasks = sorted(tasks, key=lambda t: t.server)
    if [t.server for t in tasks] != list(range(len(tasks))):
        raise ValueError(
            f"factor() needs exactly one task per server 0..N-1, got "
            f"{[t.server for t in tasks]}"
        )
    results: list[ShardResult] = []
    u_rows: list[np.ndarray] = []
    for t in tasks:
        if t.server > 0:
            t = t.with_upstream(np.concatenate(u_rows, axis=-2))
        try:
            r = execute(t, t.server)
        except TransportTimeout:
            zero = np.zeros_like(np.asarray(t.x_row))
            r = ShardResult(
                server=t.server, l_row=zero, u_row=zero,
                subseed=t.subseed, attempt=t.attempt,
                session_id=t.session_id,
            )
        results.append(r)
        u_rows.append(np.asarray(r.u_row))
    return results


class ThreadPoolTransport(Transport):
    """EdgeServers on a thread pool: in-memory messages, real scheduler
    boundary, zero serialization cost. The relay is sequential per sweep
    (the one-way chain is a data dependency); concurrency comes from
    independent sessions sharing the pool — and from jitted strip
    programs releasing the GIL while they run."""

    name = "threadpool"

    def __init__(self, max_workers: int | None = None):
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="spdc-edge"
        )
        self._edges: dict[int, EdgeServer] = {}  #: guarded-by: self._lock
        self._lock = threading.Lock()

    def _edge(self, worker_id: int) -> EdgeServer:
        with self._lock:
            if worker_id not in self._edges:
                self._edges[worker_id] = EdgeServer(worker_id)
            return self._edges[worker_id]

    def factor(self, tasks, faults=()):
        self._ensure_open()

        def execute(t, wid):
            return self._pool.submit(self._edge(wid).run, t, faults).result()

        return _run_relay(tasks, execute)

    def repair(self, task, *, replacement):
        self._ensure_open()
        return self._pool.submit(self._edge(replacement).run, task).result()

    def start(self, task, worker_id, *, faults=(), timeout=None):
        """Future[ShardResult] on the shared pool. Threads cannot be
        preempted, so `timeout` is advisory here — the rateless scheduler
        enforces its own wait and zombifies a late future (the worker
        slot stays busy until the thread actually returns)."""
        self._ensure_open()
        return self._pool.submit(self._edge(worker_id).run, task, faults)

    def close(self):
        self._pool.shutdown(wait=True)
        super().close()


def _edge_worker_main(conn, worker_id: int, enable_x64: bool) -> None:
    """Entry point of one spawned edge-server process.

    One `serve_frame` reply per received frame keeps the pipe in strict
    lock-step; an empty frame is the shutdown sentinel. Everything in and
    out is the wire codec — no pickle of task data crosses the boundary.
    """
    import jax as _jax

    _jax.config.update("jax_enable_x64", bool(enable_x64))
    from repro.api.server import EdgeServer as _Edge
    from repro.api.transport import serve_frame as _serve

    edge = _Edge(worker_id)
    state: dict = {}
    while True:
        try:
            data = conn.recv_bytes()
        except (EOFError, OSError):
            return
        if not data:
            return
        conn.send_bytes(_serve(edge, state, data))


class MultiprocessTransport(Transport):
    """Spawned worker processes; ShardTask/ShardResult cross as bytes.

    Workers spawn lazily per worker id (first dispatch pays the process +
    jax import + jit cost; a shared instance amortizes it across every
    later sweep) and inherit the parent's x64 setting. CPU hosts only:
    under an accelerator construction raises AcceleratorHeld.

    Request discipline: each pipe is strict lock-step request-reply, so
    each WORKER has its own lock (requests to different workers run
    concurrently — the property the rateless scheduler needs) and every
    request takes a PER-REQUEST wall-clock deadline (`timeout` is only
    the default). A deadline miss kills the worker — its eventual reply
    would desynchronize the pipe — and raises TransportTimeout; a worker
    found dead mid-request (crash, external kill) is respawned and the
    request retried once before TransportWorkerDied surfaces, so a
    session heals across a worker death instead of failing.
    """

    name = "multiprocess"

    def __init__(self, *, timeout: float = 600.0):
        import multiprocessing as mp

        refuse_spawn_on_accelerator(self.name)
        self._ctx = mp.get_context("spawn")
        self._conns: dict[int, object] = {}  #: guarded-by: self._meta
        self._procs: dict[int, object] = {}  #: guarded-by: self._meta
        self._sent_plan: dict[int, tuple] = {}  #: guarded-by: self._meta
        self._locks: dict[int, threading.Lock] = {}
        self._meta = threading.RLock()  # guards the dicts, not the pipes
        self._io = None  # lazy executor behind start()
        self.timeout = float(timeout)

    @property
    def workers(self) -> tuple[int, ...]:
        with self._meta:
            return tuple(sorted(self._procs))

    def _worker_lock(self, worker_id: int) -> threading.Lock:
        with self._meta:
            return self._locks.setdefault(worker_id, threading.Lock())

    def _conn(self, worker_id: int):
        with self._meta:
            conn = self._conns.get(worker_id)
            if conn is not None and self._procs[worker_id].is_alive():
                return conn
            parent, child = self._ctx.Pipe()
            proc = self._ctx.Process(
                target=_edge_worker_main,
                args=(child, worker_id,
                      bool(jax.config.jax_enable_x64)),
                daemon=True,
                name=f"spdc-edge-{worker_id}",
            )
            proc.start()
            child.close()
            self._conns[worker_id] = parent
            self._procs[worker_id] = proc
            self._sent_plan[worker_id] = ()
            return parent

    def _discard(self, worker_id: int) -> None:
        """Forget a worker whose pipe can no longer be trusted (dead, or
        timed out with a reply still owed). The next dispatch respawns
        it lazily with a fresh, in-sync pipe."""
        with self._meta:
            conn = self._conns.pop(worker_id, None)
            proc = self._procs.pop(worker_id, None)
            self._sent_plan.pop(worker_id, None)
        if conn is not None:
            try:
                conn.close()
            except (OSError, ValueError):
                pass
        if proc is not None and proc.is_alive():
            proc.terminate()
            proc.join(timeout=5)

    def _request(self, worker_id: int, frame: bytes,
                 timeout: float | None = None) -> bytes:
        """One lock-step request-reply round trip (raw reply bytes).
        Caller holds the worker's lock. Raises TransportTimeout (worker
        killed) past the deadline, TransportWorkerDied on a dead pipe."""
        deadline = self.timeout if timeout is None else float(timeout)
        conn = self._conn(worker_id)
        try:
            conn.send_bytes(frame)
            if not conn.poll(deadline):
                self._discard(worker_id)
                raise TransportTimeout(
                    f"edge worker {worker_id} exceeded its {deadline}s "
                    "request deadline (killed; respawns on next dispatch)"
                )
            data = conn.recv_bytes()
        except (EOFError, OSError, BrokenPipeError) as e:
            self._discard(worker_id)
            raise TransportWorkerDied(
                f"edge worker {worker_id} died mid-request: {e!r}"
            ) from e
        if data[:4] == b"ERR:":
            raise TransportError(
                f"edge worker {worker_id} failed: {data[4:].decode()}"
            )
        return data

    def _configure_faults(self, worker_id: int, faults,
                          timeout: float | None = None) -> None:
        plan = tuple(faults)
        # _sent_plan is _meta-guarded: close() clears it from another
        # thread. The caller's per-worker lock serializes the
        # check-then-send pair for THIS worker; the pipe round-trip
        # stays outside _meta (never block the fleet on one worker).
        with self._meta:
            if self._sent_plan.get(worker_id) == plan:
                return
        ack = self._request(worker_id, FaultPlanFrame(plan).to_bytes(),
                            timeout)
        if ack != b"ACK":
            raise TransportError(
                f"edge worker {worker_id} mis-acknowledged a fault-plan "
                f"frame: {ack[:32]!r}"
            )
        with self._meta:
            self._sent_plan[worker_id] = plan

    def _run_on(self, task, worker_id: int, faults=(),
                timeout: float | None = None):
        from .wire import decode_message

        def once():
            self._configure_faults(worker_id, faults, timeout)
            # decode by wire kind, not a pinned class: the same pipe
            # carries ShardResult and TriSolveResult replies
            return decode_message(
                self._request(worker_id, task.to_bytes(), timeout)
            )

        with self._worker_lock(worker_id):
            try:
                return once()
            except TransportWorkerDied:
                # the pipe state was discarded, so the retry spawns a
                # fresh worker (and re-sends the fault plan) — one crash
                # costs one respawn, not the session
                return once()

    def factor(self, tasks, faults=()):
        self._ensure_open()
        return _run_relay(tasks, lambda t, wid: self._run_on(t, wid, faults))

    def repair(self, task, *, replacement):
        self._ensure_open()
        return self._run_on(task, replacement)

    def start(self, task, worker_id, *, faults=(), timeout=None):
        """Future[ShardResult]: the blocking request-reply runs on an IO
        thread; the per-worker lock serializes a worker's pipe while
        different workers' requests proceed concurrently. `timeout` is
        REAL here — a deadline miss kills the straggling process."""
        self._ensure_open()
        with self._meta:
            if self._io is None:
                from concurrent.futures import ThreadPoolExecutor

                self._io = ThreadPoolExecutor(
                    max_workers=32, thread_name_prefix="spdc-mp-io"
                )
            io = self._io
        return io.submit(self._run_on, task, worker_id, faults, timeout)

    def close(self):
        # swap state out under _meta, then do the goodbye sends and the
        # (up to 5 s per worker) joins unlocked: a wedged worker must
        # not hold the metadata lock against every other thread
        with self._meta:
            io, self._io = self._io, None
            conns, self._conns = dict(self._conns), {}
            procs, self._procs = dict(self._procs), {}
            self._sent_plan.clear()
            self._locks.clear()
        for conn in conns.values():
            try:
                conn.send_bytes(b"")
                conn.close()
            except (OSError, ValueError):
                pass
        for proc in procs.values():
            proc.join(timeout=5)
            if proc.is_alive():
                proc.terminate()
        if io is not None:
            io.shutdown(wait=False)
        super().close()


def _socket_factory(**kwargs):
    from .socket_transport import SocketTransport

    return SocketTransport(**kwargs)


_FACTORIES = {
    "inline": InlineTransport,
    "shardmap": ShardMapTransport,
    "threadpool": ThreadPoolTransport,
    "multiprocess": MultiprocessTransport,
    "socket": _socket_factory,
}


@dataclass(frozen=True)
class TransportConfig:
    """Declarative transport spec — the third leg of `resolve_transport`.

    Everything that accepts `transport=` (`outsource_determinant{,_mixed}`,
    `SPDCClient`, `SPDCGatewayConfig.spdc`, gateway `submit()` overrides,
    the `serve_spdc`/`serve_worker` CLIs) takes a string name, a live
    `Transport` instance, or one of these — resolved by the ONE
    `resolve_transport()`. Frozen and hashable, so it can ride a gateway
    `BucketKey` and serve as the shared-instance registry key.

    name: "inline" | "shardmap" | "threadpool" | "multiprocess" | "socket".
    addresses: socket only — the worker fleet's endpoints
        ("tcp://host:port" / "unix:///path.sock"), worker_id i connecting
        to addresses[i % len]. Empty = spawn local warm UDS daemons on
        demand.
    timeout: default per-request deadline (multiprocess / socket).
    max_workers: thread pool width (threadpool only).
    program: relay program (shardmap only).

    `build()` returns a FRESH instance the caller owns (and must close —
    SPDCClient and the gateway do this deterministically);
    `resolve_transport(config)` instead returns a process-wide shared
    instance keyed by the config, for one-shot facade calls.
    """

    name: str
    addresses: tuple[str, ...] = ()
    timeout: float | None = None
    max_workers: int | None = None
    program: str | None = None

    def __post_init__(self):
        if self.name not in _FACTORIES:
            raise ValueError(
                f"unknown transport {self.name!r}; expected one of "
                f"{sorted(_FACTORIES)}"
            )
        # tolerate list input without breaking hashability
        object.__setattr__(self, "addresses", tuple(self.addresses))
        if self.addresses and self.name != "socket":
            raise ValueError("addresses= applies to the socket transport")
        if self.max_workers is not None and self.name != "threadpool":
            raise ValueError("max_workers= applies to threadpool")
        if self.program is not None and self.name != "shardmap":
            raise ValueError("program= applies to shardmap")
        if self.timeout is not None and self.name not in (
            "multiprocess", "socket",
        ):
            raise ValueError(
                "timeout= applies to the message transports "
                "(multiprocess, socket)"
            )

    def build(self) -> Transport:
        """Instantiate a FRESH transport the caller owns."""
        kwargs: dict = {}
        if self.name == "socket":
            if self.addresses:
                kwargs["addresses"] = self.addresses
            if self.timeout is not None:
                kwargs["timeout"] = self.timeout
        elif self.name == "multiprocess" and self.timeout is not None:
            kwargs["timeout"] = self.timeout
        elif self.name == "threadpool" and self.max_workers is not None:
            kwargs["max_workers"] = self.max_workers
        elif self.name == "shardmap" and self.program is not None:
            kwargs["program"] = self.program
        return _FACTORIES[self.name](**kwargs)


_SHARED: dict[object, Transport] = {}
_SHARED_LOCK = threading.Lock()


def resolve_transport(spec=None, *, distributed: bool = False) -> Transport:
    """THE transport resolver — every `transport=` kwarg in the package
    funnels here. Accepts:

      * None          → inline (or shardmap when the legacy
        `distributed=True` flag is set);
      * a name string from {"inline", "shardmap", "threadpool",
        "multiprocess", "socket"} → the process-wide shared instance;
      * a `TransportConfig` → a process-wide shared instance keyed by the
        config (equal configs share one warm pool; `config.build()` is
        the fresh-instance escape hatch role objects use);
      * a `Transport` instance → returned as-is (caller-owned).

    Shared instances that were individually closed are rebuilt on the
    next resolve; `close_all()` (atexit) closes the whole registry.
    """
    if isinstance(spec, Transport):
        if distributed and spec.name != "shardmap":
            raise ValueError(
                "distributed=True conflicts with an explicit non-shardmap "
                f"transport ({spec.name!r}); drop one of the two"
            )
        return spec
    if spec is None:
        spec = "shardmap" if distributed else "inline"
    elif distributed and getattr(spec, "name", spec) != "shardmap":
        raise ValueError(
            f"distributed=True conflicts with transport={spec!r}; "
            "pass transport='shardmap' (or drop distributed)"
        )
    if isinstance(spec, TransportConfig):
        with _SHARED_LOCK:
            inst = _SHARED.get(spec)
            if inst is None or inst.closed:
                _SHARED[spec] = inst = spec.build()
            return inst
    if spec not in _FACTORIES:
        raise ValueError(
            f"unknown transport {spec!r}; expected one of "
            f"{sorted(_FACTORIES)}, a TransportConfig, or a Transport "
            "instance"
        )
    with _SHARED_LOCK:
        inst = _SHARED.get(spec)
        if inst is None or inst.closed:
            _SHARED[spec] = inst = _FACTORIES[spec]()
        return inst


def close_all() -> None:
    """Close every shared transport (atexit; tests may call it)."""
    with _SHARED_LOCK:
        for t in _SHARED.values():
            t.close()
        _SHARED.clear()


atexit.register(close_all)
