"""Passes over the slot table per burst: ``BatchStats.remove_rounds +
insert_rounds + forward_waves + evict_waves`` (one per removal round,
promotion round, forward wave and evict wave) of each burst's
``apply_batch`` call, read from ``repro.core.api.RECENT_CALLS``; mean
over the window. None where the program does not count its waves."""
from benchmarks.chip import phases as ph


def read(run):
    passes = ph.passes(run)
    if passes is None:
        return None
    return sum(passes) / len(passes)
