"""Median latency of every request of the window, from its due time to
its answer (host clock), in ms."""
from benchmarks.chip.harness import latency_ms


def read(run):
    return latency_ms(run, 50)
