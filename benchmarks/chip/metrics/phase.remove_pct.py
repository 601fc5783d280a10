"""Share of the traced window's device self time in the removal rounds'
statistics passes and drop decisions (``coremaint.remove.stats``)."""
from benchmarks.chip import phases as ph


def read(run):
    return ph.share_pct(run, ("remove.stats",))
