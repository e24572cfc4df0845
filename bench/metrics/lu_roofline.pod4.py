"""Share of the relay LU's roofline, in %: the least time one chip needs
for the LUs of the traced window's verified answers (2n³/3 operations or
3n² words each, from the shape alone, `workcount.lu_least_seconds`) over
the relay program's device seconds summed over the cell's chips, so the
share is of all its chips' peaks."""
from bench import workcount

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "server LU", "answer_s"
#: the XLA module of the shard_map relay (`distrib/spdc_pipeline.py`)
MODULE = "jit__server_program"


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    device_s = run.trace.module_s(MODULE)
    if device_s <= 0:
        return None
    peak = workcount.peaks(run.device_kind)
    least = sum(workcount.lu_least_seconds(1, r.n, peak)[0] for r in answers)
    return 100.0 * least / device_s
