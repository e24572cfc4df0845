"""Seconds of placing the ciphertext's block rows on their servers' chips
(the `spdc.scatter` span, which the shard_map transport opens before its
sweep) per verified answer in the traced window."""
from bench.spans import span_seconds

UNIT, SOURCE, LAYER, MOVES = "s", "program_span", "transport", "answer_s"


def read(run):
    answers = run.answers_until(run.t_end)
    if run.trace is None or not answers:
        return None
    s = span_seconds(run.trace, "spdc.scatter")
    return None if s is None else s / len(answers)
