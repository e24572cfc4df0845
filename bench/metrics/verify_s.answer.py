"""Seconds of Authenticate (with recovery, when it fires) and Decipher
(the `spdc.verify` and `spdc.decipher` spans) per verified answer in the
traced window."""
from bench.spans import span_seconds

UNIT, SOURCE, LAYER, MOVES = "s", "program_span", "verify and decipher", "answer_s"


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    s = span_seconds(run.trace, ("spdc.verify", "spdc.decipher"))
    return None if s is None else s / len(answers)
