"""Drives `repro.serve.AsyncSPDCGateway.submit`, the served path users call.

Open loop: requests go out at their scheduled times, whether or not
earlier ones have finished; a producer thread builds the matrices ahead of
the schedule so the sender only waits for the clock. Closed loop: each
client sends its next request when its previous answer arrives.
"""
from __future__ import annotations

import asyncio
import itertools
import queue
import threading
import time
from dataclasses import replace

from bench.record import Req
from bench.traffic import Matrices, Schedule, size_range

#: how long after the window closes to wait for requests still in flight
DRAIN_S = 60.0


def gateway_config(cfg: dict):
    """The SPDCGatewayConfig a configuration file describes: its preset with
    the file's changed keys applied."""
    from repro.configs import spdc as presets

    base = getattr(presets, cfg["preset"])
    spdc_changes = cfg.get("spdc_changes", {})
    gw_changes = {k: (tuple(v) if isinstance(v, list) else v)
                  for k, v in cfg.get("gateway_changes", {}).items()}
    spdc = replace(base.spdc, **spdc_changes) if spdc_changes else base.spdc
    return replace(base, spdc=spdc, **gw_changes)


def buckets_for(gw_config, lo: int, hi: int) -> tuple[int, ...]:
    """The buckets that requests of sizes lo..hi land in."""
    from repro.serve.queue import bucket_size_for

    return tuple(sorted({
        bucket_size_for(n, gw_config.buckets, gw_config.spdc.num_servers)
        for n in range(max(lo, 2), hi + 1)
    }))


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 chips: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds = seconds
        self.gw_config = gateway_config(cfg)
        self.schedule = Schedule(mix, seed, seconds)
        self.lo, self.hi = size_range(mix["sizes"])
        self.matrices = None
        self.requests: list[Req] = []
        self.flushes: list = []

    def setup(self) -> None:
        """Seeded pools, and the sweep programs of the buckets this mix can
        reach at every batch size padding can produce (no others)."""
        from repro.serve.spdc_gateway import SPDCGateway

        self.matrices = Matrices(self.mix["matrices"], self.seed, max_n=self.hi)
        hit = buckets_for(self.gw_config, self.lo, self.hi)
        with SPDCGateway(replace(self.gw_config, buckets=hit)) as warm:
            warm.warmup()

    def input(self, req: Req):
        """The exact matrix request `req` sent (for the reference)."""
        return self.matrices.host(req.idx, req.n)

    def measure(self, window) -> tuple[float, float]:
        """Run the window; returns its (start, end) on the host clock."""
        return asyncio.run(self._main(window))

    async def _main(self, window):
        from repro.serve.spdc_gateway import AsyncSPDCGateway

        def on_flush(ev):
            self.flushes.append((time.monotonic(), ev))

        async with AsyncSPDCGateway(self.gw_config, on_flush=on_flush) as gw:
            if self.mix["loop"] == "open":
                return await self._open(gw, window)
            return await self._closed(gw, window)

    async def _send(self, gw, req: Req, matrix) -> None:
        from repro.serve import AdmissionRejected, BreakerOpen, GatewayOverloaded

        req.sent = time.monotonic()
        try:
            res = await gw.submit(matrix)
        except (GatewayOverloaded, AdmissionRejected, BreakerOpen) as e:
            req.refused, req.error = True, type(e).__name__
            req.done = time.monotonic()
            return
        req.done = time.monotonic()
        req.verified, req.error = bool(res.verified), res.error
        if res.det is not None:
            req.sign, req.logabs = float(res.det.sign), float(res.det.logabs)

    @staticmethod
    def _open_window(window) -> tuple[float, list]:
        """Open the window; a traced run's profiler stops on a worker thread
        at its deadline, so the event loop keeps its schedule."""
        t0 = window.open()
        stopper = []
        if window.trace_deadline is not None:
            loop = asyncio.get_running_loop()
            loop.call_at(loop.time() + window.trace_deadline - time.monotonic(),
                         lambda: stopper.append(
                             asyncio.ensure_future(asyncio.to_thread(window.stop_trace))))
        return t0, stopper

    async def _open(self, gw, window):
        sched = self.schedule
        ready: queue.Queue = queue.Queue(maxsize=64)
        stop = threading.Event()

        def produce():
            for i in range(len(sched)):
                item = (i, self.matrices.host(i, sched.size(i)))
                while not stop.is_set():
                    try:
                        ready.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        # let the producer get ahead before the clock starts
        while ready.qsize() < min(ready.maxsize, len(sched)) and producer.is_alive():
            await asyncio.sleep(0.01)
        tasks = []
        t0, stopper = self._open_window(window)
        t1 = t0 + self.seconds
        try:
            for i in range(len(sched)):
                due = t0 + float(sched.due[i])
                if due >= t1:
                    break
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                try:
                    _, matrix = ready.get_nowait()
                except queue.Empty:
                    _, matrix = await asyncio.to_thread(ready.get)
                req = Req(idx=i, n=sched.size(i), due=due)
                self.requests.append(req)
                tasks.append(asyncio.create_task(self._send(gw, req, matrix)))
            await asyncio.sleep(max(0.0, t1 - time.monotonic()))
            await asyncio.to_thread(window.close)
            await asyncio.gather(*stopper)
            if tasks:
                await asyncio.wait(tasks, timeout=DRAIN_S)
        finally:
            stop.set()
            producer.join(timeout=5)
        return t0, t1

    async def _closed(self, gw, window):
        sched = self.schedule
        counter = itertools.count()
        t0, stopper = self._open_window(window)
        t1 = t0 + self.seconds

        async def client():
            while time.monotonic() < t1:
                i = next(counter)
                n = sched.size(i)
                matrix = self.matrices.host(i, n)
                req = Req(idx=i, n=n, due=time.monotonic())
                self.requests.append(req)
                await self._send(gw, req, matrix)

        clients = [asyncio.create_task(client())
                   for _ in range(int(self.mix["clients"]))]
        await asyncio.sleep(max(0.0, t1 - time.monotonic()))
        await asyncio.to_thread(window.close)
        await asyncio.gather(*stopper)
        await asyncio.wait(clients, timeout=DRAIN_S)
        return t0, t1

    def close(self) -> None:
        """Nothing to free: the gateway closed with its window."""
