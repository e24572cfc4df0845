"""Verified answers completed in the window, per second of the window."""
UNIT, SOURCE, LAYER, MOVES = "answers/s", "host_clock", None, None


def read(run):
    return len(run.answers_until(run.t1)) / run.seconds
