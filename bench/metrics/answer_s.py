"""Seconds per verified answer for one waiting client: from the window's
start to the end of the answer in flight when it closed, over the verified
answers in that time (so no answer is cut in two)."""
UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", None, None


def read(run):
    done = [r for r in run.requests if r.answered and r.verified]
    if not done:
        return None
    return (max(r.done for r in done) - run.t0) / len(done)
