"""PBQP solve time of the cell's buckets in set-up, in ms
(``ServingCounters.solve_s``: host time of a host computation)."""


def read(run):
    return 1e3 * run.solve_s
