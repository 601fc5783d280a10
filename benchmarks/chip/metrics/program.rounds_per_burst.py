"""Fixpoint rounds per burst, removal plus promotion, from each burst's
``BatchStats.remove_rounds + insert_rounds``; mean over the window."""


def read(run):
    return sum(b.remove_rounds + b.insert_rounds
               for b in run.bursts) / len(run.bursts)
