"""Share of the traced window's device self time in XLA:TPU fusions of
kind ``kCustom``. In this program's traces these are its indexed
passes: gathers of per-vertex values by slot endpoints (``[window] <-
([n], [window])``) and the segment-sum scatter-adds back to vertices
(``[n] <- ([window], ...)``); the trace does not tell the two apart."""
from benchmarks.chip import trace as tr


def read(run):
    if run.trace is None:
        return None
    return tr.share_pct(run.trace, lambda op: op.kind == "kCustom")
