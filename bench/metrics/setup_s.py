"""Seconds from process start to the first timed request: imports, data,
warm-up and, on a first run, compilation."""
UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", None, None


def read(run):
    return run.setup_s
