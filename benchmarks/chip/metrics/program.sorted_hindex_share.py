"""Share of the window's weighted fixpoint rounds, removal plus
promotion, whose h-index came from one sort (``BatchStats.hindex_sorts``
over ``remove_rounds + insert_rounds``). None where the program does not
count them, as before the sort, or runs no weighted round."""
from benchmarks.chip import phases as ph


def read(run):
    calls = ph.window_calls(run)
    if calls is None or not all(
            getattr(c.stats, "hindex_sorts", None) is not None
            for c in calls):
        return None
    rounds = sum(int(c.stats.remove_rounds) + int(c.stats.insert_rounds)
                 for c in calls)
    if rounds == 0:
        return None
    return sum(int(c.stats.hindex_sorts) for c in calls) / rounds
