"""The plain reference and the comparison that decides `correct`.

The reference is numpy's float64 `slogdet` (LU with partial pivoting) of
the exact float32 matrix each request sent. It imports nothing of the
program. Each compared number has its limit in `bench/limits/<cell>.json`,
set from the readings recorded there.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench.traffic import rng_for

LIMITS_DIR = Path(__file__).resolve().parent / "limits"


def limits(workload: str) -> dict:
    """The limits of cell `workload` (bench/limits/<workload>.json)."""
    path = LIMITS_DIR / f"{workload}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no limits for cell {workload!r} at {path}")
    return json.loads(path.read_text())["limits"]


def slogdet64(matrix) -> tuple[float, float]:
    sign, logabs = np.linalg.slogdet(np.asarray(matrix, dtype=np.float64))
    return float(sign), float(logabs)


def sample(reqs, seed: int, size: int | None) -> list:
    """`size` of `reqs` drawn from the seed, the largest always among them;
    all of them when size is None or not smaller."""
    if size is None or size >= len(reqs):
        return list(reqs)
    largest = max(range(len(reqs)), key=lambda i: reqs[i].n)
    rest = [i for i in range(len(reqs)) if i != largest]
    pick = rng_for(seed, 5).choice(len(rest), size=size - 1, replace=False)
    return [reqs[largest]] + [reqs[rest[i]] for i in sorted(pick)]


def compare(run, input_of, limit: dict, sample_size: int | None) -> dict:
    """The numbers compared for `run`, each with its limit.

    missing: requests due in the window that never answered (an error, or
    no answer a minute after the close); a typed refusal is a failure of
    the run but says nothing wrong. unverified: answers whose verdict
    rejected them. sign_mismatch and max_dlogdet: over the sampled answers,
    against the float64 reference of the same float32 input.
    """
    due = [r for r in run.in_window if not r.refused]
    answered = [r for r in due if r.answered]
    checked = sample(answered, run.seed, sample_size)
    sign_bad, worst = 0, 0.0
    for r in checked:
        sign, logabs = slogdet64(input_of(r))
        if r.sign != sign:
            sign_bad += 1
        worst = max(worst, abs(r.logabs - logabs))
    numbers = {
        "missing": (len(due) - len(answered), 0),
        "unverified": (sum(1 for r in answered if not r.verified), 0),
        "sign_mismatch": (sign_bad, 0),
        "max_dlogdet": (worst, limit["max_dlogdet"]),
        "compared": (len(checked), None),
    }
    return numbers


def is_correct(numbers: dict) -> bool:
    ok = all(v <= lim for v, lim in numbers.values() if lim is not None)
    return ok and numbers["compared"][0] > 0
