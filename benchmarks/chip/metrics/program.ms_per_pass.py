"""Device self time of the traced window in the phases that pass over
the slot table (``coremaint.remove.stats``, ``promote.forward``,
``promote.evict``, ``promote.stats``) over the passes its bursts ran."""
from benchmarks.chip import phases as ph


def read(run):
    passes = ph.passes(run)
    per = ph.phase_seconds(run)
    if per is None or passes is None or sum(passes) == 0:
        return None
    return 1e3 * sum(per.get(p, 0.0) for p in ph.PASS_PHASES) / sum(passes)
