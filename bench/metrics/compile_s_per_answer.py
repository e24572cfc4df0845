"""Seconds JAX spent tracing, lowering and compiling inside the measured
part of the window, per verified answer completed in it."""
UNIT, SOURCE, LAYER, MOVES = "s", "program_span", "host JAX compile", "answer_s"


def read(run):
    answers = run.answers_until(run.t_end)
    return run.compile_s / len(answers) if answers else None
