"""Milliseconds of Authenticate and Decipher (the `spdc.verify` and
`spdc.decipher` spans, once each per gateway flush) per verified answer
in the traced window."""
from bench.spans import span_seconds

UNIT, SOURCE, LAYER, MOVES = (
    "ms", "program_span", "verify and decipher", "verified_per_s"
)


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    s = span_seconds(run.trace, ("spdc.verify", "spdc.decipher"))
    return None if s is None else 1000.0 * s / len(answers)
