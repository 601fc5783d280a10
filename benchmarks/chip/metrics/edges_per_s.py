"""Edge updates applied per second: removals plus insertions that took
effect, summed over every burst of the window, over the window's whole
time (first dispatch to the last burst's completion)."""


def read(run):
    return sum(b.removed + b.inserted for b in run.bursts) / run.window_s
