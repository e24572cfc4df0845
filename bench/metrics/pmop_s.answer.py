"""Seconds of client PMOP (the `spdc.pmop` span: seed, key, cipher,
equilibrate, border, up to the ciphertext on the device) per verified
answer in the traced window."""
from bench.spans import span_seconds

UNIT, SOURCE, LAYER, MOVES = "s", "program_span", "client PMOP", "answer_s"


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    s = span_seconds(run.trace, "spdc.pmop")
    return None if s is None else s / len(answers)
