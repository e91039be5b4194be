"""Mean time a request of the window waited in the scheduler, in ms: from
its submit to the start of its batch on a worker (waiting for the group
to fill, for the dispatcher, for a free worker), read from the
``sched_queue_wait_s`` counter of ``ServingCounters``.  None where the
program keeps no such counter."""


def read(run):
    a, b = run.counters_before, run.counters_after
    if "sched_queue_wait_s" not in b:
        return None

    def samples(c):
        return c.get("phases", {}).get("sched_queue_wait", {}).get("count", 0)

    n = samples(b) - samples(a)
    if n <= 0:
        return None
    return 1e3 * (b["sched_queue_wait_s"] - a.get("sched_queue_wait_s", 0.0)) / n
