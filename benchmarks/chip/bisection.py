"""The weighted fixpoint's bisection steps per burst of the window, from
the program's record of its calls (``phases.window_calls``). Each step
of ``graph_ops.weighted_h_index`` is one weighted support pass over the
slot window; the program sums them per phase into
``BatchStats.remove_bisect_steps`` and ``insert_bisect_steps``."""
from __future__ import annotations

from typing import Optional

from benchmarks.chip import phases as ph


def steps(run) -> Optional[list]:
    """``remove_bisect_steps + insert_bisect_steps`` per burst of the
    window; ``None`` where the program keeps no record of its calls or
    does not count the steps."""
    calls = ph.window_calls(run)
    if calls is None or not all(hasattr(c.stats, "remove_bisect_steps")
                                for c in calls):
        return None
    return [int(c.stats.remove_bisect_steps)
            + int(c.stats.insert_bisect_steps) for c in calls]
