"""Role-split SPDC API — client, edge servers, wire, transports.

The paper's protocol is defined by a trust boundary; this package makes
the boundary the shape of the code (DESIGN.md §7):

  * `SPDCClient` / `Session` (client.py) — the trusted role: KeyGen /
    Cipher / Authenticate / Decipher, plus client-driven recovery and
    the async-overlap pipeline (`Session.start` → `PendingResult`,
    `SPDCClient.run_pipelined`).
  * `EdgeServer` (server.py)            — the untrusted role: a stateless
    `run(ShardTask) → ShardResult` worker.
  * `ShardTask` / `ShardResult` (messages.py) and the codec (wire.py) —
    the ONLY things that cross the boundary, serializable to versioned
    pickle-free byte frames.
  * transports (transport.py, socket_transport.py) — inline (fused fast
    path), shardmap (mesh pipeline), threadpool, multiprocess (real
    process boundary, bytes on the wire), socket (warm worker daemons
    over TCP/UDS — DESIGN.md §9). Select any of them by name, by
    `TransportConfig`, or by instance through `resolve_transport`; all
    share the `start`/`result`/`submit` dispatch surface and a uniform
    `close()`/context-manager lifecycle.

`core.protocol.outsource_determinant` remains the one-call facade over
exactly these objects.
"""
from .client import (
    BoundaryViolation,
    PendingResult,
    Session,
    SPDCClient,
)
from .messages import (
    FaultPlanFrame,
    ShardResult,
    ShardTask,
    TriSolveResult,
    TriSolveTask,
)
from .server import EdgeServer
from .transport import (
    AcceleratorHeld,
    InlineTransport,
    MultiprocessTransport,
    ShardMapTransport,
    ThreadPoolTransport,
    Transport,
    TransportConfig,
    TransportError,
    TransportProtocolError,
    TransportTimeout,
    TransportWorkerDied,
    close_all,
    resolve_transport,
)
from .wire import WireError, decode_message

__all__ = [
    "SPDCClient", "Session", "PendingResult", "BoundaryViolation",
    "EdgeServer",
    "ShardTask", "ShardResult", "TriSolveTask", "TriSolveResult",
    "FaultPlanFrame",
    "Transport", "TransportConfig", "TransportError", "TransportTimeout",
    "TransportWorkerDied", "TransportProtocolError", "AcceleratorHeld",
    "InlineTransport", "ShardMapTransport",
    "ThreadPoolTransport", "MultiprocessTransport", "SocketTransport",
    "WorkerDaemon", "resolve_transport",
    "close_all",
    "WireError", "decode_message",
]


def __getattr__(name):
    # SocketTransport/WorkerDaemon import lazily: socket_transport pulls
    # in distrib.rateless (FleetHealth), which itself imports this
    # package's transport module — a top-level import here would cycle.
    if name in ("SocketTransport", "WorkerDaemon"):
        from . import socket_transport

        return getattr(socket_transport, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
