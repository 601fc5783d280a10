"""Share of the traced window's device self time in HLO ``sort`` ops:
the per-burst table argsort and the per-round ``place_block`` lexsorts."""
from benchmarks.chip import trace as tr


def read(run):
    if run.trace is None:
        return None
    return tr.share_pct(run.trace, lambda op: op.opcode == "sort")
