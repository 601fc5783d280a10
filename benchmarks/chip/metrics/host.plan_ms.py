"""Mean host time of the ``apply_batch`` call, which returns once the
burst is planned and enqueued, before the device finishes it."""


def read(run):
    return 1e3 * sum(b.plan_s for b in run.bursts) / len(run.bursts)
