"""Share of the traced window's device self time in the promotion rounds'
EVICT waves (``coremaint.promote.evict``)."""
from benchmarks.chip import phases as ph


def read(run):
    return ph.share_pct(run, ("promote.evict",))
