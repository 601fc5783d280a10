"""Share of the traced window in which no op ran on the device."""
from benchmarks.chip import trace as tr


def read(run):
    if run.trace is None or tr.busy_s(run.trace) == 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(run.trace) / tr.window_s(run.trace))
