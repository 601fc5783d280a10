"""Device busy time of the traced window over the fixpoint rounds that
its bursts ran."""
from benchmarks.chip import trace as tr


def read(run):
    rounds = sum(b.remove_rounds + b.insert_rounds for b in run.bursts)
    busy = tr.busy_s(run.trace) if run.trace is not None else 0
    if busy == 0 or rounds == 0:
        return None
    return 1e3 * busy / rounds
