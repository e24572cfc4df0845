"""SPDC edge-worker daemon launcher: one warm worker process a fleet of
clients can reach over TCP or a Unix-domain socket (DESIGN.md §9).

    # serve ANY worker id on an ephemeral TCP port (printed on start)
    PYTHONPATH=src python -m repro.launch.serve_worker --bind tcp://127.0.0.1:0

    # one daemon per worker identity, the paper's fleet shape
    PYTHONPATH=src python -m repro.launch.serve_worker \
        --bind unix:///tmp/spdc-w0.sock --workers 0

    # client side
    from repro.api import SPDCClient, TransportConfig
    client = SPDCClient(transport=TransportConfig(
        "socket", addresses=("tcp://127.0.0.1:45123",)))

The daemon holds this process's EdgeServers — and therefore its jit
caches — warm across every connection, session, and client restart: the
first sweep of a given shape pays the trace, every later one (from any
client) reuses it. Worker ids map onto daemons client-side as
``addresses[i % len(addresses)]``, so one daemon serving "any id" can
stand in for a whole fleet, and recovery's replacement ids wrap onto
the same endpoints.

--smoke starts a UDS daemon, runs one small verified determinant through
it over a real SocketTransport, and exits — the runnable quickstart CI
executes.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile


def parse_workers(spec: str | None):
    if spec is None or spec == "":
        return None
    try:
        return tuple(int(s) for s in spec.split(",") if s != "")
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--workers wants comma-separated ints, got {spec!r}"
        ) from None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="warm SPDC edge-worker daemon (TCP or Unix socket)"
    )
    ap.add_argument("--bind", default="tcp://127.0.0.1:0",
                    help="tcp://host:port (port 0 = ephemeral, printed) "
                         "or unix:///path.sock")
    ap.add_argument("--workers", type=parse_workers, default=None,
                    help="comma-separated worker ids this daemon serves "
                         "(default: any id)")
    ap.add_argument("--no-x64", dest="x64", action="store_false",
                    help="serve the float32 protocol shape on the CPU "
                         "too (x64 is always off on an accelerator)")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test: UDS daemon + one verified "
                         "determinant over SocketTransport, then exit")
    args = ap.parse_args(argv)

    from repro.runtime import init_process

    x64 = init_process(args.x64)

    from repro.api.socket_transport import WorkerDaemon

    if args.smoke:
        return smoke(x64)

    daemon = WorkerDaemon(args.bind, workers=args.workers)
    addr = daemon.start()
    served = "any" if args.workers is None else ",".join(
        str(w) for w in args.workers
    )
    print(f"[serve_worker] listening on {addr} workers={served} "
          f"x64={'on' if x64 else 'off'}", flush=True)
    try:
        daemon.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        daemon.close()
    return 0


def smoke(x64: bool = True) -> int:
    """Daemon + client in one process: the quickstart, executably. The
    determinant must match numpy at rtol 1e-10 (1e-4 in float32)."""
    import numpy as np

    from repro.api import SPDCClient, TransportConfig
    from repro.api.socket_transport import WorkerDaemon

    path = os.path.join(tempfile.mkdtemp(prefix="spdc-smoke-"), "w.sock")
    with WorkerDaemon(f"unix://{path}") as daemon:
        cfg = TransportConfig("socket", addresses=(daemon.address,))
        rng = np.random.default_rng(7)
        x = rng.standard_normal((48, 48)) + 48 * np.eye(48)
        with SPDCClient(transport=cfg) as client:
            sess = client.open_session(x, num_servers=2)
            res = sess.run(client.transport)
            hello = client.transport.hello(0)
        ws, wl = np.linalg.slogdet(x)
        ok = (res.verified and res.det.sign == ws
              and np.isclose(res.det.logabs, wl,
                             rtol=1e-10 if x64 else 1e-4))
        print(f"[serve_worker --smoke] addr={daemon.address} "
              f"verified={res.verified} "
              f"det matches slogdet={ok} "
              f"daemon connections={hello['connections'] if hello else '?'}")
        return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
