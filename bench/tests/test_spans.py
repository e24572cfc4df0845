"""The program's spans as the benchmark reads them: recorded on XLA:CPU,
whose profiler has the host plane with no device, and reduced with
`from_xplane`; then the seven readers on hand-made traces whose answers
are known."""
import dataclasses

import numpy as np
import pytest

from bench import harness, tracing
from bench.record import Req, Run
from bench.spans import span_seconds
from bench.tracing import Trace

PROTOCOL = ("spdc.pmop", "spdc.sweep", "spdc.verify", "spdc.decipher")
FLUSH = ("spdc.gateway.pack", *PROTOCOL, "spdc.gateway.deliver")


def _matrix(n, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One small `outsource_determinant` call, then three submissions and
    one flush of the gw_small gateway cut to one bucket, under the
    profiler. Returns the call's result, the reduced Trace, and the
    `spdc.*` events of each host thread as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    from bench.drivers.gateway import gateway_config
    from repro.core import outsource_determinant
    from repro.serve.spdc_gateway import SPDCGateway

    _, cfg, _, _ = harness.resolve(harness.load_spec(), "gw_small")
    gw_config = dataclasses.replace(gateway_config(cfg), buckets=(64,), max_batch=4)
    m = _matrix(16, 1)
    outsource_determinant(m, 2, dtype="float32")  # compile outside the trace
    log_dir = tmp_path_factory.mktemp("trace")
    session = tracing.Session(log_dir)
    try:
        res = outsource_determinant(m, 2, dtype="float32")
        with SPDCGateway(gw_config, auto_flush=False) as gw:
            for i in range(3):
                gw.submit(_matrix(12 + i, 2 + i))
            flushed = gw.drain()
    finally:
        session.stop()
    assert res.verified and len(flushed) == 3 and all(r.verified for r in flushed)
    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    threads = [
        sorted((int(e.start_ns), int(e.end_ns), e.name) for e in line.events
               if e.name.startswith("spdc."))
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name == "/host:CPU" for line in plane.lines
    ]
    return res, tracing.from_xplane(path, 1), [t for t in threads if t]


def test_each_phase_appears_once_per_call_and_flush_in_order(recorded):
    _, trace, _ = recorded
    names = [name for _, _, name in sorted(trace.host) if name.startswith("spdc.")]
    assert names == [*PROTOCOL, *["spdc.gateway.submit"] * 3, *FLUSH]


def test_no_program_span_lies_inside_another_on_one_thread(recorded):
    _, _, threads = recorded
    assert threads
    for events in threads:
        for (_, e0, a), (s1, _, b) in zip(events, events[1:]):
            assert e0 <= s1, f"{b} starts inside {a}"


def test_session_timings_match_the_spans(recorded):
    res, trace, _ = recorded
    first = {name: (e - s) / 1e9 for s, e, name in sorted(trace.host, reverse=True)
             if name in PROTOCOL}  # the call's own spans: the earliest of each
    t = res.report.timings
    assert t.pmop_s == pytest.approx(first["spdc.pmop"], abs=1e-3)
    assert t.dispatch_s == pytest.approx(first["spdc.sweep"], abs=1e-3)
    assert t.collect_s >= first["spdc.verify"] + first["spdc.decipher"] - 1e-3


# -- the readers, on a hand-made trace -------------------------------------


def made(window=(1_000_000_000, 3_000_000_000), host=None):
    """A 2 s window (ns 1e9..3e9) with no device ops. Default host spans:
    two flushes of six 0.1 s spans each, from 0.95 s and 2.0 s, so the
    first flush's pack straddles the window's start."""
    ms = 1_000_000
    if host is None:
        host = []
        for t0 in (950 * ms, 2000 * ms):
            edges = [t0 + k * 100 * ms for k in range(7)]
            host += [(a, b, name) for a, b, name in zip(edges, edges[1:], FLUSH)]
        host += [(2110 * ms, 2150 * ms, "PjitFunction(_lu_sweep)"),  # inside spdc.pmop
                 (2500 * ms, 2600 * ms, "spdc.gateway.submit")]
    return Trace(window, [tracing.Device()], host)


def run_with(trace, done=(1.5, 2.0, 2.5), t_traced_end=2.6, cell="gw_small"):
    """A Run whose window opens at host time 1.0 s; answers complete at
    `done` (host seconds), the profiler stopped at `t_traced_end`."""
    run = Run(workload={"name": cell}, config={}, mix={}, seconds=5.0, chips=1,
              t0=1.0, t1=6.0, trace=trace, t_traced_end=t_traced_end)
    run.requests = [Req(idx=i, n=16, due=1.0, sent=1.0, done=d, verified=True)
                    for i, d in enumerate(done)]
    run.requests.append(Req(idx=9, n=16, due=1.0, done=2.7, verified=True))  # after t_end
    return run


def read(metric, run):
    return harness.metric_module(metric).read(run)


def test_span_seconds_sums_clipped_or_merged():
    t = made()
    assert span_seconds(t, "spdc.pmop") == pytest.approx(0.2)
    # the first pack, [0.95, 1.05) s, counts only its 0.05 s inside the window
    assert span_seconds(t, "spdc.gateway.pack") == pytest.approx(0.15)
    assert span_seconds(t, ["spdc.verify", "spdc.decipher"]) == pytest.approx(0.4)
    # the flusher's spans tile [1.0, 1.55) and [2.0, 2.6)
    assert span_seconds(t, FLUSH, merged=True) == pytest.approx(1.15)
    assert span_seconds(t, FLUSH) == pytest.approx(1.15)
    assert span_seconds(t, "spdc.absent") is None
    before = made(host=[(0, 1_000_000_000, "spdc.pmop")])
    assert span_seconds(before, "spdc.pmop") is None
    overlapping = made(host=[(1_000_000_000, 1_500_000_000, "a"),
                             (1_200_000_000, 1_700_000_000, "b")])
    assert span_seconds(overlapping, ("a", "b")) == pytest.approx(1.0)
    assert span_seconds(overlapping, ("a", "b"), merged=True) == pytest.approx(0.7)


@pytest.mark.parametrize("metric,names,scale", [
    ("pmop_s.answer", ["spdc.pmop"], 1.0),
    ("sweep_s.answer", ["spdc.sweep"], 1.0),
    ("verify_s.answer", ["spdc.verify", "spdc.decipher"], 1.0),
    ("pmop_ms.closed", ["spdc.pmop"], 1000.0),
    ("sweep_ms.closed", ["spdc.sweep"], 1000.0),
    ("verify_ms.closed", ["spdc.verify", "spdc.decipher"], 1000.0),
])
def test_per_answer_readers(metric, names, scale):
    run = run_with(made())
    # three answers by t_end = 2.6 s; the fourth, at 2.7 s, is past it
    assert len(run.answers_until(run.t_end)) == 3
    want = scale * span_seconds(run.trace, names) / 3
    assert read(metric, run) == pytest.approx(want)
    assert read(metric, run_with(None)) is None  # untraced
    assert read(metric, run_with(made(), done=())) is None  # no answers by t_end
    assert read(metric, run_with(made(host=[]))) is None  # a program without spans


def test_per_answer_readers_known_values():
    run = run_with(made())
    assert read("pmop_s.answer", run) == pytest.approx(0.2 / 3)
    assert read("sweep_ms.closed", run) == pytest.approx(1000 * 0.2 / 3)
    assert read("verify_ms.closed", run) == pytest.approx(1000 * 0.4 / 3)


def test_flusher_busy_reads_the_union_over_the_window():
    assert read("flusher_busy.closed", run_with(made())) == pytest.approx(100 * 1.15 / 2)
    # submit spans are the clients' side, not the flusher's
    only_submit = made(host=[(1_000_000_000, 3_000_000_000, "spdc.gateway.submit")])
    assert read("flusher_busy.closed", run_with(only_submit)) is None
    assert read("flusher_busy.closed", run_with(None)) is None
    assert read("flusher_busy.closed", run_with(made(), done=())) is None


def test_a_gap_inside_a_protocol_call_reads_the_phase():
    """The phase spans are flat, so a gap that both a phase and a JAX
    span nested in it cover whole is a tie, and the earlier-starting
    span, the phase, names it."""
    trace = made()
    trace.devices = [tracing.Device(ops=[(2_000_000_000, 2_120_000_000, "%a = f32[] add()"),
                                         (2_140_000_000, 3_000_000_000, "%b = f32[] add()")])]
    gaps = trace.breakdown()["idle_gaps"]
    assert ["spdc.pmop", pytest.approx(0.02)] in gaps
