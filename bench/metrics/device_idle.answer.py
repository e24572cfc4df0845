"""Share of the traced window in which no op ran on the device, in %,
averaged over the cell's chips (1 − union of XLA op intervals / window)."""
UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "device", "answer_s"


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
