"""Milliseconds per verified answer in the traced window during which
every chip of the cell is inside a collective-permute and no chip runs
any other op: the relay's ppermute hops between chips themselves.

A passive chip waits in its collective-permute while the active chip
computes; those waits are not counted, since some chip is computing
then. Ops that hold a whole loop or branch (`while`, `conditional`,
`call`) are not counted as computing."""
from bench.tracing import clip, length, op_kind, subtract, union

UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "relay", "answer_s"
#: the op the relay's ppermute lowers to
HOP = "collective-permute"
#: control-flow ops, which span the hops they hold
CONTAINERS = ("while", "conditional", "call")


def hop_intervals(trace) -> list[tuple[int, int]]:
    """The intervals in which every chip is in a collective-permute and
    none runs another op (containers aside)."""
    lo, hi = trace.window
    everyone, busy = None, []
    for d in trace.devices:
        hops = []
        for s, e, name in d.ops + d.async_ops:
            kind = op_kind(name)
            if kind.startswith(HOP):
                hops.append((s, e))
            elif kind not in CONTAINERS:
                busy.append((s, e))
        hops = clip(union(hops), lo, hi)
        everyone = hops if everyone is None else subtract(everyone, subtract(everyone, hops))
    return subtract(everyone or [], clip(union(busy), lo, hi))


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    total = length(hop_intervals(run.trace))
    return total / 1e6 / len(answers) if total > 0 else None
