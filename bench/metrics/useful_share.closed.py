"""Share of the rows a gateway sweep factors that belong to a request:
Σ batch / Σ padded_batch over the flushes (pad_batches fills the rest)."""
UNIT, SOURCE, LAYER, MOVES = "%", "program_counter", "gateway", "verified_per_s"


def read(run):
    evs = run.window_flushes
    padded = sum(ev.padded_batch for ev in evs)
    return 100.0 * sum(ev.batch for ev in evs) / padded if padded else None
