"""Share of the traced window's device self time in the rest of the
promotion rounds: seeds and violator masks (``coremaint.promote.seed``)
and each round's closing statistics pass
(``coremaint.promote.stats``)."""
from benchmarks.chip import phases as ph


def read(run):
    return ph.share_pct(run, ("promote.seed", "promote.stats"))
