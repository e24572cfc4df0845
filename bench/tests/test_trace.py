"""The reduction from a trace to numbers: on a hand-made trace whose
answers are known, and on traces recorded on the chip (bench/testdata)."""
from pathlib import Path

import pytest

from bench import tracing
from bench.tracing import Device, Trace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def made():
    """Two devices over a 100 ns window. Device 0: a module [10, 50) holding
    ops [10, 20) and [15, 30) and a collective [40, 60) of which [40, 50)
    overlaps the op [45, 50); device 1: one op [0, 100)."""
    d0 = Device(ops=[(10, 20, "%fusion.1 = f32[] fusion()"), (15, 30, "%dot.2 = f32[] dot()"),
                     (45, 50, "%add = f32[] add()"),
                     (40, 60, "%collective-permute-done.3 = f32[] collective-permute-done()")],
                modules=[(10, 50, "jit__lu_sweep(123)"), (55, 70, "jit_other(9)")])
    d1 = Device(ops=[(0, 100, "%while.7 = () while()")], modules=[(0, 100, "jit__lu_sweep(5)")])
    return Trace((0, 100), [d0, d1], [(30, 40, "host.a"), (60, 100, "host.b")])


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.op_kind("%collective-permute-start.12 = (f32[2]) x()") == "collective-permute-start"
    assert tracing.op_kind("%copy-done = f32[8]{0} copy-done()") == "copy-done"
    assert tracing.module_name("jit__lu_sweep(16754406048427856801)") == "jit__lu_sweep"


def test_busy_idle_module_and_collective_time():
    t = made()
    # device 0 busy [10, 30) + [40, 60) = 40 ns, device 1 100 ns: mean 70
    assert t.busy_s == pytest.approx(70e-9)
    assert t.idle_share == pytest.approx(0.3)
    assert t.module_s("jit__lu_sweep") == pytest.approx(140e-9)
    total, exposed = t.collective_s()
    assert total == pytest.approx(10e-9) and exposed == pytest.approx(7.5e-9)


def test_breakdown_names_ops_and_gaps():
    b = made().breakdown()
    ops = dict(b["device_ops"])
    assert ops["jit__lu_sweep/while"] == pytest.approx(50e-9)
    assert ops["jit__lu_sweep/fusion"] == pytest.approx(5e-9)
    gaps = b["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 10e-9, 10e-9])
    assert gaps[0][0] == "host.b" and "host.a" in [g[0] for g in gaps]


def test_json_round_trip(tmp_path):
    t = made()
    t.write(tmp_path / "t.json.gz")
    back = Trace.read(tmp_path / "t.json.gz")
    assert back.busy_s == t.busy_s and back.collective_s() == t.collective_s()


def test_recorded_gateway_trace():
    """A 100 ms slice of a traced gw_mixed window on one TPU v5 lite: two
    sweeps, the host between them."""
    t = Trace.read(TESTDATA / "gw_mixed_v5e.json.gz")
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx(0.0073016)
    assert t.module_s("jit__lu_sweep") == pytest.approx(0.006416084)
    assert 0 < t.module_s("jit__lu_sweep") <= t.busy_s < t.window_s
    assert t.collective_s() == (0.0, 0.0)
    b = t.breakdown()
    assert b["device_ops"][0] == ["jit__lu_sweep/while", pytest.approx(0.005202704)]
    assert b["idle_gaps"][0] == ["PjitFunction(_lu_sweep)", pytest.approx(0.040243298)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
