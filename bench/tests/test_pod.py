"""The `pod4_n8192` cell on a small copy: its own configuration, traffic
mix and limits, with n cut to 256 and the 16 servers relayed over the
CPU's virtual devices (two per device). A sound run is correct; an answer
nudged in Decipher and a relay whose hops between chips are left out are
caught; a traced rehearsal records the `spdc.scatter` span. Then the
cell's three readers on hand-made traces whose answers are known."""
import dataclasses
import json
import time

import jax
import pytest

from bench import harness, tracing
from bench.record import Req, Run
from bench.tracing import Device, Trace

CELL = "pod4_n8192"
N_SMALL = 256


def small_cell():
    """(workload, configuration, mix, limits) of pod4_n8192 with n cut."""
    wl, cfg, mix, limits = harness.resolve(harness.load_spec(), CELL)
    cfg = dict(cfg, spdc_changes=dict(cfg["spdc_changes"], matrix_n=N_SMALL))
    return dict(wl, name="small_pod"), json.loads(json.dumps(cfg)), mix, limits


def run_small(traced: bool = False, seed: int = 2**33 + 15) -> dict:
    cell = small_cell()
    line = harness.run_cell(cell[0]["name"], seed, 0.1, traced,
                            t_process=time.monotonic(), require_tpu=False,
                            cell=cell)
    return line


def test_the_small_copy_keeps_the_cells_servers_and_transport():
    _, cfg, mix, limits = small_cell()
    assert cfg["spdc"]["num_servers"] == 16 and cfg["spdc"]["transport"] == "shardmap"
    assert mix["sizes"] == {"dist": "fixed"} and mix["check"]["sample"] == 16
    assert limits["max_dlogdet"] > 0


def test_a_sound_run_is_correct(jax_settings):
    line = run_small()
    run = line.pop("_run")
    assert line["correct"], line["checks"]
    assert line["checks"]["compared"]["value"] >= 1
    assert all(r.n == N_SMALL for r in run.requests)


def test_an_answer_nudged_in_decipher_is_caught(jax_settings, monkeypatch):
    import repro.api.client as client

    limit = small_cell()[3]["max_dlogdet"]

    def nudged(fn):
        def wrapper(*args, **kwargs):
            d = fn(*args, **kwargs)
            return dataclasses.replace(d, logabs=d.logabs + 2 * limit)
        return wrapper

    monkeypatch.setattr(client, "decipher", nudged(client.decipher))
    line = run_small()
    line.pop("_run")
    assert not line["correct"]
    assert line["checks"]["max_dlogdet"]["value"] > limit


def test_a_relay_without_its_hops_between_chips_is_caught(jax_settings, monkeypatch):
    from repro.distrib import spdc_pipeline

    assert len(jax.devices()) >= 4
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
    spdc_pipeline._compiled_pipeline.cache_clear()
    try:
        line = run_small()
    finally:
        spdc_pipeline._compiled_pipeline.cache_clear()
        jax.clear_caches()  # no later test may reuse the broken programs
    line.pop("_run")
    assert not line["correct"], line["checks"]


def test_a_traced_rehearsal_records_the_scatter_span(jax_settings, monkeypatch, tmp_path):
    """XLA:CPU's profiler has the host plane and no device plane, so the
    harness drops the trace after reading it; the spans are taken from
    the reduction it made."""
    seen = []
    real = tracing.from_xplane

    def keep(path, chips):
        seen.append(real(path, chips))
        return seen[-1]

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(tracing, "from_xplane", keep)
    line = run_small(traced=True)
    line.pop("_run")
    assert line["correct"], line["checks"]
    (trace,) = seen
    names = [name for _, _, name in sorted(trace.host) if name.startswith("spdc.")]
    assert names[:5] == ["spdc.pmop", "spdc.scatter", "spdc.sweep",
                         "spdc.verify", "spdc.decipher"]


# -- the readers, on hand-made traces --------------------------------------

MS = 1_000_000
OP = "%fusion.1 = f32[8]{0} fusion()"
PERMUTE = "%collective-permute-start.2 = (f32[8]{0}) collective-permute-start()"


def pod_trace():
    """A 2 s window (ns 1e9..3e9) on four chips. Each chip: 100 ms of the
    relay module, holding 60 ms of compute and 20 ms of collective
    permute, 10 ms of it beside compute. Host: two 30 ms scatter spans."""
    devices = []
    for _ in range(4):
        devices.append(Device(
            ops=[(1100 * MS, 1160 * MS, OP)],
            async_ops=[(1150 * MS, 1170 * MS, PERMUTE)],
            modules=[(1100 * MS, 1200 * MS, "jit__server_program(42)")],
        ))
    host = [(1050 * MS, 1080 * MS, "spdc.scatter"),
            (2050 * MS, 2080 * MS, "spdc.scatter")]
    return Trace((1000 * MS, 3000 * MS), devices, host)


def pod_run(trace, done=(1.5, 2.5)):
    run = Run(workload={"name": CELL}, config={}, mix={}, seconds=51.0, chips=4,
              t0=1.0, t1=52.0, trace=trace, t_traced_end=3.0,
              device_kind="TPU v5 lite")
    run.requests = [Req(idx=i, n=8192, due=1.0, sent=1.0, done=d, verified=True)
                    for i, d in enumerate(done)]
    return run


def read(metric, run):
    return harness.metric_module(metric).read(run)


def test_collective_ms_reads_the_permutes_per_answer():
    """Only the time in which every chip is in the permute and none
    computes counts: 1160..1170 ms, 10 ms over two answers."""
    assert pod_run(pod_trace()).trace.collective_s() == pytest.approx((0.02, 0.01))
    assert read("collective_ms.pod4", pod_run(pod_trace())) == pytest.approx(10 / 2)
    waits = pod_trace()  # a passive chip waits in its permute from 1110 ms
    waits.devices[2].async_ops = [(1110 * MS, 1170 * MS, PERMUTE)]
    for d in waits.devices:  # the relay's loop op spans everything
        d.ops.append((1100 * MS, 1200 * MS, "%while.3 = (f32[8]{0}) while()"))
    assert read("collective_ms.pod4", pod_run(waits)) == pytest.approx(10 / 2)
    late = pod_trace()  # one chip computes until 1165 ms
    late.devices[1].ops = [(1100 * MS, 1165 * MS, OP)]
    assert read("collective_ms.pod4", pod_run(late)) == pytest.approx(5 / 2)
    quiet = pod_trace()
    for d in quiet.devices:
        d.async_ops = []
    assert read("collective_ms.pod4", pod_run(quiet)) is None  # no relay hop
    assert read("collective_ms.pod4", pod_run(None)) is None
    assert read("collective_ms.pod4", pod_run(pod_trace(), done=())) is None


def test_lu_roofline_is_the_shapes_least_time_over_the_relays_device_seconds():
    from bench import workcount

    least, bound = workcount.lu_least_seconds(1, 8192, workcount.peaks("TPU v5 lite"))
    assert bound == "flops"
    # two answers; the relay module ran 0.1 s on each of four chips
    want = 100.0 * 2 * least / 0.4
    assert read("lu_roofline.pod4", pod_run(pod_trace())) == pytest.approx(want)
    assert 0 < want < 100
    other = pod_trace()
    for d in other.devices:
        d.modules = [(s, e, "jit__lu_sweep(7)") for s, e, _ in d.modules]
    assert read("lu_roofline.pod4", pod_run(other)) is None  # no relay program
    assert read("lu_roofline.pod4", pod_run(None)) is None


def test_scatter_s_reads_the_placement_span_per_answer():
    assert read("scatter_s.pod4", pod_run(pod_trace())) == pytest.approx(0.06 / 2)
    bare = pod_trace()
    bare.host = []
    assert read("scatter_s.pod4", pod_run(bare)) is None  # a program without it
    assert read("scatter_s.pod4", pod_run(None)) is None
