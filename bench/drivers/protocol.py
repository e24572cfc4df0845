"""Drives `repro.core.outsource_determinant` for one matrix at a time: one
client that waits for each verified answer before it sends the next.

Each matrix is made on the device from (seed, index) in one jitted call.
The call that is in flight when the window closes runs to its end and
counts: the window's time per answer is taken over all its work.
"""
from __future__ import annotations

import time
from dataclasses import replace

from bench.record import Req
from bench.traffic import Matrices, Schedule

#: the index of the warm-up matrix (never one of the window's)
WARMUP_IDX = 2**31 - 1


def spdc_config(cfg: dict):
    """The SPDCConfig a configuration file describes: its preset with the
    file's changed keys applied."""
    from repro.configs import spdc as presets

    return replace(getattr(presets, cfg["preset"]), **cfg.get("spdc_changes", {}))


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 chips: int):
        if int(mix.get("clients", 1)) != 1:
            raise ValueError("the protocol driver runs one client")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds = seconds
        self.spdc = spdc_config(cfg)
        self.schedule = Schedule(mix, seed, seconds, default_n=self.spdc.matrix_n)
        self.matrices = Matrices(mix["matrices"], seed)
        self.requests: list[Req] = []
        self.flushes: list = []

    def _call(self, matrix):
        from repro.core import outsource_determinant

        return outsource_determinant(matrix, self.spdc.num_servers,
                                     **self.spdc.protocol_kwargs())

    def setup(self) -> None:
        """One whole protocol call at the window's size warms every program
        (its answer is not judged: the window's answers are)."""
        self._call(self.matrices.device(WARMUP_IDX, self.schedule.size(0)))

    def input(self, req: Req):
        import numpy as np

        return np.asarray(self.matrices.device(req.idx, req.n))

    def measure(self, window) -> tuple[float, float]:
        t0 = window.open()
        t1 = t0 + self.seconds
        i = 0
        while time.monotonic() < t1:
            n = self.schedule.size(i)
            req = Req(idx=i, n=n, due=time.monotonic())
            req.sent = req.due
            self.requests.append(req)
            try:
                res = self._call(self.matrices.device(i, n))
            except Exception as e:  # noqa: BLE001 -- an answer that never came
                req.error = f"{type(e).__name__}: {e}"
            else:
                req.verified = bool(res.verified)
                req.sign, req.logabs = float(res.det.sign), float(res.det.logabs)
            req.done = time.monotonic()
            i += 1
            if window.trace_deadline is not None and req.done >= window.trace_deadline:
                window.stop_trace()  # between answers, so the trace holds whole ones
        window.close()
        return t0, t1

    def close(self) -> None:
        """Drop the compiled pipelines' cached executables and buffers."""
        import jax

        jax.clear_caches()
