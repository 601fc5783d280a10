"""Weighted h-index bisection steps per burst, removal plus promotion
(``BatchStats.remove_bisect_steps + insert_bisect_steps``), each one
support pass over the slot window; mean over the window. None where the
program does not count them."""
from benchmarks.chip import bisection


def read(run):
    steps = bisection.steps(run)
    if steps is None:
        return None
    return sum(steps) / len(steps)
