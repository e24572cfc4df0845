"""repro.spans: the program's phase spans, and the timings they fill."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.spans as spans_mod
from repro.api import InlineTransport, SPDCClient
from repro.spans import span


class _Slow:
    """A pytree leaf whose device work takes `delay` seconds to finish."""

    def __init__(self, delay):
        self.delay, self.blocked_at = delay, None

    def block_until_ready(self):
        time.sleep(self.delay)
        self.blocked_at = time.perf_counter()
        return self


def _wellcond(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, n)) + n * np.eye(n)


def test_seconds_cover_the_body():
    with span("test.body") as s:
        t0 = time.perf_counter()
        time.sleep(0.02)
        body = time.perf_counter() - t0
    assert s.seconds >= body >= 0.02
    assert s.name == "test.body"


def test_wait_blocks_on_the_outputs_before_the_span_closes():
    leaves = [_Slow(0.03), _Slow(0.0)]
    with span("test.wait", wait=lambda: {"l": leaves[0], "u": leaves[1]}) as s:
        t_body = time.perf_counter()
    t_out = time.perf_counter()
    assert all(t_body < leaf.blocked_at <= t_out for leaf in leaves)
    assert s.seconds >= 0.03
    x = jnp.arange(4.0) * 2
    with span("test.wait.array", wait=lambda: x):
        pass
    assert x.is_ready()


def test_wait_is_skipped_when_the_body_raises():
    leaf = _Slow(0.0)
    with pytest.raises(RuntimeError, match="phase failed"), \
            span("test.raise", wait=lambda: leaf) as s:
        raise RuntimeError("phase failed")
    assert leaf.blocked_at is None and s.seconds >= 0


def test_without_a_profiler_a_span_is_one_traceme_and_two_clock_reads(monkeypatch):
    calls = []

    class FakeTraceMe:
        def __init__(self, name):
            calls.append(("traceme", name))

        def __enter__(self):
            calls.append(("enter",))

        def __exit__(self, *exc):
            calls.append(("exit",))

    real_clock = time.perf_counter

    def clock():
        calls.append(("clock",))
        return real_clock()

    def no_block(x):
        raise AssertionError("a span without wait= must not block")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeTraceMe)
    monkeypatch.setattr(spans_mod.time, "perf_counter", clock)
    monkeypatch.setattr(jax, "block_until_ready", no_block)
    with span("test.cheap"):
        pass
    assert calls == [("traceme", "test.cheap"), ("enter",), ("clock",),
                     ("clock",), ("exit",)]


def test_dispatch_s_is_stamped_after_the_factors_are_ready(monkeypatch):
    """On the fused inline path the sweep span waits on exactly the (L, U)
    that collect() verifies, and dispatch_s is taken after that wait."""
    waited, collected = [], []
    real_block = jax.block_until_ready

    def block(x):
        out = real_block(x)
        time.sleep(0.05)  # device work still in flight when dispatch returns
        waited.append((x, time.perf_counter()))
        return out

    from repro.api.client import Session

    real_collect = Session.collect

    def spy_collect(self, results, **kw):
        collected.append((results, time.perf_counter()))
        return real_collect(self, results, **kw)

    monkeypatch.setattr(jax, "block_until_ready", block)
    monkeypatch.setattr(Session, "collect", spy_collect)
    session = SPDCClient().open_session(_wellcond(12, seed=3), 2)
    waited.clear()  # the PMOP span's wait
    res = session.run(InlineTransport())
    assert res.verified
    (lu_waited, _), = waited
    (lu_collected, _), = collected
    assert all(a is b for a, b in zip(lu_waited, lu_collected))
    assert res.report.timings.dispatch_s >= 0.05


def test_session_timings_are_the_spans_seconds(monkeypatch):
    seen = []
    real_exit = span.__exit__

    def record_exit(self, *exc):
        real_exit(self, *exc)
        seen.append((self.name, self.seconds))

    monkeypatch.setattr(span, "__exit__", record_exit)
    res = SPDCClient().open_session(_wellcond(12, seed=4), 2).run()
    t = res.report.timings
    assert [name for name, _ in seen] == ["spdc.pmop", "spdc.sweep",
                                          "spdc.verify", "spdc.decipher"]
    by = dict(seen)
    assert t.pmop_s == by["spdc.pmop"] and t.dispatch_s == by["spdc.sweep"]
    assert t.collect_s >= by["spdc.verify"] + by["spdc.decipher"]
    assert t.total_s == pytest.approx(t.pmop_s + t.dispatch_s + t.collect_s)


@pytest.mark.parametrize("call", ["run", "start"])
def test_the_shardmap_transport_places_the_ciphertext_in_a_span_of_its_own(
        monkeypatch, call):
    """On the shard_map relay, `spdc.scatter` puts the block rows on their
    servers' chips before `spdc.sweep` opens; the sweep gets the placed
    array, and dispatch_s counts both spans."""
    from repro.api import ShardMapTransport

    seen, swept = [], []
    real_exit = span.__exit__

    def record_exit(self, *exc):
        real_exit(self, *exc)
        seen.append((self.name, self.seconds))

    transport = ShardMapTransport()
    real_sweep = transport.sweep

    def spy_sweep(x_aug, num_servers, faults=()):
        swept.append(x_aug)
        return real_sweep(x_aug, num_servers, faults=faults)

    monkeypatch.setattr(span, "__exit__", record_exit)
    monkeypatch.setattr(transport, "sweep", spy_sweep)
    session = SPDCClient().open_session(_wellcond(48, seed=6), 16)
    res = getattr(session, call)(transport)
    res = res if call == "run" else res.result()
    assert res.verified
    assert [name for name, _ in seen] == ["spdc.pmop", "spdc.scatter",
                                          "spdc.sweep", "spdc.verify",
                                          "spdc.decipher"]
    (x,) = swept
    assert len(x.sharding.device_set) == 8  # 16 servers, two per device
    by = dict(seen)
    assert res.report.timings.dispatch_s == pytest.approx(
        by["spdc.scatter"] + by["spdc.sweep"])
