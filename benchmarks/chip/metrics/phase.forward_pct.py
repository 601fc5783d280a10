"""Share of the traced window's device self time in the promotion rounds'
FORWARD waves (``coremaint.promote.forward``)."""
from benchmarks.chip import phases as ph


def read(run):
    return ph.share_pct(run, ("promote.forward",))
