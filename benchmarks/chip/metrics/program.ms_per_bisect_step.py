"""Device self time of the traced window in the weighted fixpoints'
phases (``coremaint.remove.stats`` and ``promote.stats``) over the
bisection steps its bursts ran."""
from benchmarks.chip import bisection
from benchmarks.chip import phases as ph


def read(run):
    steps = bisection.steps(run)
    per = ph.phase_seconds(run)
    if per is None or steps is None or sum(steps) == 0:
        return None
    return 1e3 * (per.get("remove.stats", 0.0)
                  + per.get("promote.stats", 0.0)) / sum(steps)
