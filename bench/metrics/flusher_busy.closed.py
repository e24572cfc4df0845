"""Share of the traced window in which the gateway's flusher was at work,
in %: the union of its spans (pack, the protocol's four phases, deliver)
over the window. Near 100% the single flusher sets the pace; well under
it, the clients' submit side does."""
from bench.spans import span_seconds

UNIT, SOURCE, LAYER, MOVES = "%", "program_span", "gateway", "verified_per_s"
FLUSHER = ("spdc.gateway.pack", "spdc.pmop", "spdc.sweep", "spdc.verify",
           "spdc.decipher", "spdc.gateway.deliver")


def read(run):
    if run.trace is None or not run.answers_until(run.t_end):
        return None
    s = span_seconds(run.trace, FLUSHER, merged=True)
    return None if s is None else 100.0 * s / run.trace.window_s
