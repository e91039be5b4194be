"""Requests per launched batch over the window: the scheduler's
``sched_submits`` over its ``sched_batches`` (``ServingCounters``)."""


def read(run):
    a, b = run.counters_before, run.counters_after
    batches = b.get("sched_batches", 0) - a.get("sched_batches", 0)
    if batches <= 0:
        return None
    return (b["sched_submits"] - a["sched_submits"]) / batches
