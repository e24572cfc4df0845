"""The `relay_overlap.pod4` reader on hand-made traces of four chips whose
answers are known: chips that take turns, chips that compute together
for part of the time, and ops that are no work of the relay."""
import pytest

from bench import harness
from bench.record import Req, Run
from bench.tracing import Device, Trace

MS = 1_000_000
OP = "%fusion.1 = f32[8]{0} fusion()"
PERMUTE = "%collective-permute-start.2 = (f32[8]{0}) collective-permute-start()"
DONE = "%collective-permute-done.2 = f32[8]{0} collective-permute-done()"
LOOP = "%while.3 = (f32[8]{0}) while()"
BRANCH = "%conditional.4 = (f32[8]{0}) conditional()"
RELAY = "jit__server_program(42)"


def chip(ops, module=RELAY, extra=()):
    """A chip whose relay module spans 1000..1400 ms, with compute `ops`
    ((start_ms, end_ms) pairs), a loop and a branch around everything and
    `extra` ops of any name."""
    return Device(
        ops=[(s * MS, e * MS, OP) for s, e in ops]
        + [(1000 * MS, 1400 * MS, LOOP), (1000 * MS, 1400 * MS, BRANCH)]
        + [(s * MS, e * MS, name) for s, e, name in extra],
        async_ops=[],
        modules=[(1000 * MS, 1400 * MS, module)],
    )


def overlap(devices):
    run = Run(workload={"name": "pod4_n8192"}, config={}, mix={}, seconds=51.0,
              chips=4, t0=1.0, t1=52.0, t_traced_end=3.0, device_kind="TPU v5 lite",
              trace=Trace((900 * MS, 1500 * MS), devices, []))
    run.requests = [Req(idx=0, n=8192, due=1.0, sent=1.0, done=1.5, verified=True)]
    return harness.metric_module("relay_overlap.pod4").read(run)


def test_chips_that_take_turns_read_zero():
    turns = [chip([(1000 + 100 * c, 1100 + 100 * c)]) for c in range(4)]
    assert overlap(turns) == 0.0


def test_two_chips_together_for_half_of_the_compute_read_fifty():
    """Chip 0 computes 1000..1200 ms, chip 1 1100..1300 ms: 100 ms of 300
    together. Chip 2 adds 1300..1400 ms alone: 100 of 400. Chip 1 from
    1100 ms to chip 0's end: half of the 200 ms."""
    assert overlap([chip([(1000, 1200)]), chip([(1100, 1300)]),
                    chip([]), chip([])]) == pytest.approx(100 / 3)
    assert overlap([chip([(1000, 1200)]), chip([(1100, 1300)]),
                    chip([(1300, 1400)]), chip([])]) == pytest.approx(25.0)
    covered = [chip([(1000, 1200)]), chip([(1000, 1100)]),
               chip([(1100, 1200)]), chip([(1150, 1200)])]
    assert overlap(covered) == pytest.approx(100.0)
    assert overlap([chip([(1000, 1200)]), chip([(1100, 1200)]),
                    chip([]), chip([])]) == pytest.approx(50.0)


def test_hops_containers_and_other_modules_are_no_work():
    """A chip waiting in its collective-permute, or inside the loop and
    branch ops alone, computes nothing; nor does an op of another module
    (the client's PMOP on chip 0) or one outside the window."""
    waits = [chip([(1000, 1100)]), chip([], extra=[(1000, 1100, PERMUTE),
                                                   (1000, 1100, DONE)]),
             chip([]), chip([])]
    waits[1].async_ops = [(1000 * MS, 1100 * MS, PERMUTE)]
    assert overlap(waits) == 0.0
    pmop = [chip([(1000, 1100)]), chip([(1000, 1100)]), chip([]), chip([])]
    pmop[0].ops.append((1200 * MS, 1300 * MS, OP))
    pmop[0].modules.append((1200 * MS, 1300 * MS, "jit_cipher(7)"))
    pmop[0].modules[0] = (1000 * MS, 1150 * MS, RELAY)
    assert overlap(pmop) == pytest.approx(100.0)
    late = [chip([(1000, 1100)]), chip([(1000, 1100)]), chip([]), chip([])]
    late[2].ops.append((1600 * MS, 1700 * MS, OP))
    late[2].modules.append((1600 * MS, 1700 * MS, RELAY))
    assert overlap(late) == pytest.approx(100.0)


def test_a_trace_without_relay_compute_reads_nothing():
    idle = [chip([]) for _ in range(4)]
    assert overlap(idle) is None
    other = [chip([(1000, 1100)], module="jit__lu_sweep(3)") for _ in range(4)]
    assert overlap(other) is None  # no relay program
    run = Run(workload={"name": "pod4_n8192"}, config={}, mix={}, seconds=51.0,
              chips=4, t0=1.0, t1=52.0, trace=None, t_traced_end=3.0,
              device_kind="TPU v5 lite")
    assert harness.metric_module("relay_overlap.pod4").read(run) is None
