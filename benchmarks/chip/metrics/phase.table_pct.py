"""Share of the traced window's device self time in the batch program's
``coremaint.table`` scope: the table argsort, removal lookup and
tombstoning, dedup, membership, free-list allocation and slot writes."""
from benchmarks.chip import phases as ph


def read(run):
    return ph.share_pct(run, ("table",))
