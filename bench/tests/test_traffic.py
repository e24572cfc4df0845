import hashlib
from collections import Counter

import numpy as np
import pytest

from bench import traffic

FAMILY = {"family": "lowrank_shift", "alpha": 0.5, "gamma": 1.0, "rank": 16}
BIG_SEED = 2**40 + 12345  # seeds may exceed 32 bits
#: an open-loop mix: Poisson arrivals, sizes log-uniform over every bucket
OPEN_MIX = {"loop": "open", "rate_per_s": 56.0,
            "sizes": {"dist": "log_uniform", "lo": 48, "hi": 1024}, "matrices": FAMILY}


def digest(m) -> str:
    return hashlib.sha256(np.ascontiguousarray(m).tobytes()).hexdigest()


@pytest.mark.parametrize("name", ["closed_small", "one_client"])
def test_every_mix_loads(name):
    mix = traffic.load(name)
    assert mix["loop"] in ("open", "closed")
    assert mix["matrices"]["family"] == "lowrank_shift"


def test_schedule_is_deterministic_and_the_same_work_for_every_seed():
    mix = OPEN_MIX
    a = traffic.Schedule(mix, BIG_SEED, 10)
    b = traffic.Schedule(mix, BIG_SEED, 10)
    c = traffic.Schedule(mix, 7, 10)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.sizes, b.sizes)
    assert not np.array_equal(a.sizes, c.sizes)
    assert Counter(a.sizes.tolist()) == Counter(c.sizes.tolist())
    assert a.due[-1] == pytest.approx(c.due[-1])
    assert len(a) == round(mix["rate_per_s"] * 10)
    assert min(a.sizes) >= mix["sizes"]["lo"] and max(a.sizes) <= mix["sizes"]["hi"]
    assert np.all(np.diff(a.due) >= 0)


@pytest.mark.parametrize("dist,lo,hi", [("uniform", 16, 128), ("log_uniform", 48, 1024)])
def test_size_quantiles_cover_the_range(dist, lo, hi):
    q = traffic.size_quantiles({"dist": dist, "lo": lo, "hi": hi}, 4096)
    assert q.min() == lo and q.max() == hi


def test_host_matrices_are_deterministic_and_distinct():
    a = traffic.Matrices(FAMILY, BIG_SEED, max_n=128)
    b = traffic.Matrices(FAMILY, BIG_SEED, max_n=128)
    c = traffic.Matrices(FAMILY, BIG_SEED + 1, max_n=128)
    ms = [a.host(i, n) for i, n in enumerate([16, 64, 64, 128, 128, 128])]
    assert all(m.dtype == np.float32 and m.shape == (m.shape[0],) * 2 for m in ms)
    assert len({digest(m) for m in ms}) == len(ms)
    assert all(digest(a.host(i, 64)) == digest(b.host(i, 64)) for i in range(4))
    assert digest(a.host(0, 64)) != digest(c.host(0, 64))
    # every leading minor positive: the pivot-free LU is safe on the family
    m = ms[-1].astype(np.float64)
    assert all(np.linalg.det(m[:k, :k]) > 0 for k in range(1, 129, 9))


def test_device_matrices_are_deterministic():
    m = traffic.Matrices(FAMILY, BIG_SEED)
    x, y = np.asarray(m.device(3, 64)), np.asarray(m.device(3, 64))
    assert x.dtype == np.float32 and np.array_equal(x, y)
    assert not np.array_equal(x, np.asarray(m.device(4, 64)))
