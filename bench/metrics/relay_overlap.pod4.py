"""Share of the relay's compute time, in %, in which two or more chips
compute at once.

Over the traced window, a chip computes where it runs an op of the relay
module (`jit__server_program`) that is neither a collective-permute nor
an op that holds a whole loop or branch (`while`, `conditional`, `call`).
The reading is the time in which at least two chips compute over the time
in which any chip does. Chips that take turns read 0%; ops of other
modules (the client's PMOP, verify) are not the relay's and do not count."""
from bench.tracing import clip, module_name, op_kind, subtract, union

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "relay", "answer_s"
#: the XLA module of the shard_map relay (`distrib/spdc_pipeline.py`)
MODULE = "jit__server_program"
#: the op the relay's ppermute lowers to
HOP = "collective-permute"
#: control-flow ops, which span the work they hold
CONTAINERS = ("while", "conditional", "call")


def compute_intervals(trace, device) -> list[tuple[int, int]]:
    """The merged intervals in which `device` runs a relay op that is
    neither a hop nor a container, clipped to the window."""
    relay = union((s, e) for s, e, m in device.modules if module_name(m) == MODULE)
    ops = union((s, e) for s, e, name in device.ops + device.async_ops
                if not op_kind(name).startswith(HOP)
                and op_kind(name) not in CONTAINERS)
    ops = clip(ops, *trace.window)
    return subtract(ops, subtract(ops, relay))


def overlap_ns(trace) -> tuple[int, int]:
    """(ns in which two or more chips compute, ns in which any chip does)."""
    edges = sorted(edge for d in trace.devices
                   for s, e in compute_intervals(trace, d)
                   for edge in ((s, 1), (e, -1)))
    both = anyone = live = 0
    for (t, step), (t_next, _) in zip(edges, edges[1:]):
        live += step
        if live >= 1:
            anyone += t_next - t
        if live >= 2:
            both += t_next - t
    return both, anyone


def read(run):
    if run.trace is None:
        return None
    both, anyone = overlap_ns(run.trace)
    return 100.0 * both / anyone if anyone > 0 else None
