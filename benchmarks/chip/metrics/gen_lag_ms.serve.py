"""99th percentile of how late the open-loop sender submitted each
request after its due time (host clock), in ms."""
from benchmarks.chip.harness import percentile


def read(run):
    p = percentile(run.window.gen_lag_s, 99)
    return None if p is None else 1e3 * p
