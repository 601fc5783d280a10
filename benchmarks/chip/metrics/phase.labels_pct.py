"""Share of the traced window's device self time in k-order label
placement and the renumber gate (``coremaint.labels``)."""
from benchmarks.chip import phases as ph


def read(run):
    return ph.share_pct(run, ("labels",))
