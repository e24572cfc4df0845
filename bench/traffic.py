"""One general generator for every traffic mix in `bench/traffic/*.json`.

A mix is data: the loop (`open`: arrivals on a schedule; `closed`: clients
that each wait for their reply), the rate or the client count, the size
distribution and the matrix family. Everything is drawn from `--seed`, and
every seed gets the same multiset of sizes and inter-arrival gaps in
another order, so two seeds do the same work and differ only in order and
in the matrices' values.

Matrix families (the `matrices` object of a mix):

* `lowrank_shift`: M = I + (alpha/sqrt(n)) G + (gamma/sqrt(r)) B Bᵀ with G
  (n, n) and B (n, r) standard normal. The rank-r part makes the LU cancel
  O(1) products down to O(1) pivots in its first r steps, so the float32
  products' precision shows in log|det| (the diagonally dominant
  randn + n·I family hides a 2.6e-3 product error). Every leading minor is
  well away from zero, so the protocol's pivot-free LU is stable on it.
"""
from __future__ import annotations

import json
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    """The mix `bench/traffic/<name>.json`."""
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path.name}: loop must be 'open' or 'closed'")
    return mix


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run, from any whole seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def jax_key(seed: int, *stream: int):
    """A JAX PRNG key from any whole seed (it may exceed 32 bits)."""
    state = np.random.SeedSequence([seed, *stream]).generate_state(2)
    return jax.random.wrap_key_data(state.astype(np.uint32), impl="threefry2x32")


def size_quantiles(spec: dict, count: int, default_n: int | None = None) -> np.ndarray:
    """`count` sizes at the midpoint quantiles of the mix's distribution —
    the same multiset for every seed."""
    dist = spec["dist"]
    q = (np.arange(count) + 0.5) / count
    if dist == "fixed":
        n = spec.get("n", default_n)
        if n is None:
            raise ValueError("a fixed size needs 'n' or the configuration's matrix_n")
        return np.full(count, int(n))
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "uniform":
        vals = lo + q * (hi + 1 - lo)
        return np.minimum(np.floor(vals), hi).astype(int)
    if dist == "log_uniform":
        return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))).astype(int)
    raise ValueError(f"unknown size distribution {dist!r}")


def size_range(spec: dict, default_n: int | None = None) -> tuple[int, int]:
    """The least and the largest size the distribution can give."""
    if spec["dist"] == "fixed":
        n = int(spec.get("n", default_n))
        return n, n
    return int(spec["lo"]), int(spec["hi"])


class Schedule:
    """Which request comes when, and how large it is.

    Open loop: `count` = rate × seconds requests, due at the cumulative sum
    of exponential gaps taken at their quantiles and shuffled by the seed.
    Closed loop: an endless cycle of sizes; each client takes the next.
    """

    def __init__(self, mix: dict, seed: int, seconds: float,
                 default_n: int | None = None):
        self.loop = mix["loop"]
        rng = rng_for(seed, 1)
        if self.loop == "open":
            rate = float(mix["rate_per_s"])
            count = max(1, int(round(rate * seconds)))
            q = (np.arange(count) + 0.5) / count
            gaps = -np.log1p(-q) / rate
            self.due = np.cumsum(rng.permutation(gaps)) - gaps.min()
        else:
            count = int(mix.get("cycle", 1024))
            self.due = None
        self.sizes = rng.permutation(size_quantiles(mix["sizes"], count, default_n))

    def size(self, i: int) -> int:
        return int(self.sizes[i % len(self.sizes)])

    def __len__(self) -> int:
        return len(self.sizes)


class Matrices:
    """The matrix family of a mix, made from (seed, request index).

    `host(i, n)` builds request i on the host from seeded pools with O(n²)
    work, so set-up never makes gigabytes: a window of a pooled Gaussian at
    a seeded offset, made distinct per request by one perturbed row (an
    O(n) change). `device(i, n)` builds it on the device in one jitted call.
    The same (seed, i, n) always gives the same bytes.
    """

    POOL = 2048

    def __init__(self, spec: dict, seed: int, max_n: int = 0):
        if spec["family"] != "lowrank_shift":
            raise ValueError(f"unknown matrix family {spec['family']!r}")
        self.alpha = float(spec["alpha"])
        self.gamma = float(spec["gamma"])
        self.rank = int(spec["rank"])
        self.seed = seed
        self._pool = None
        if max_n and max_n <= self.POOL:
            rng = rng_for(seed, 2)
            self._pool = rng.standard_normal((self.POOL, self.POOL), dtype=np.float32)
            self._bpool = rng.standard_normal((self.POOL, self.rank), dtype=np.float32)

    def host(self, i: int, n: int) -> np.ndarray:
        if self._pool is None or n > self.POOL:
            raise ValueError(f"host matrices need a pool of at least {n}")
        rng = rng_for(self.seed, 3, i)
        r0, c0, b0 = rng.integers(0, self.POOL - n + 1, size=3)
        g = self._pool[r0:r0 + n, c0:c0 + n]
        b = self._bpool[b0:b0 + n]
        m = np.float32(self.gamma / math.sqrt(self.rank)) * (b @ b.T)
        m += np.float32(self.alpha / math.sqrt(n)) * g
        m[np.diag_indices(n)] += np.float32(1.0)
        row = int(rng.integers(n))
        m[row] += np.float32(self.alpha / math.sqrt(n)) * rng.standard_normal(n, dtype=np.float32)
        return m

    def device(self, i: int, n: int):
        return _device_matrix(jax_key(self.seed, 4, i), n, self.alpha,
                              self.gamma, self.rank)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _device_matrix(key, n, alpha, gamma, rank):
    kg, kb = jax.random.split(key)
    g = jax.random.normal(kg, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n, rank), jnp.float32)
    low = jnp.matmul(b, b.T, precision=jax.lax.Precision.HIGHEST)
    return (jnp.eye(n, dtype=jnp.float32) + (alpha / math.sqrt(n)) * g
            + (gamma / math.sqrt(rank)) * low)
