"""Seconds of the servers' LU as the client sees it (the `spdc.sweep`
span: dispatch up to the factors on the device, per-call compiles
included) per verified answer in the traced window."""
from bench.spans import span_seconds

UNIT, SOURCE, LAYER, MOVES = "s", "program_span", "server LU", "answer_s"


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    s = span_seconds(run.trace, "spdc.sweep")
    return None if s is None else s / len(answers)
