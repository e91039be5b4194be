"""An open loop of single images at a fixed rate, submitted to the
``ContinuousScheduler``: independent users of one endpoint.

Parameters (traffic file): ``rate_per_s``, ``batch_window_s`` (the
scheduler's batching window) and ``warm_batches`` (the batch buckets
set-up warms).

Every seed sends the same number of requests with the same set of gaps
between them, the quantiles of an exponential distribution at the rate,
in an order drawn from the seed.  A request's latency runs from the time
it was due, so a stall of the sender counts against the requests it
delays; how late each send was is kept apart.
"""
import threading
import time

import numpy as np


def arrivals(rate, seconds, seed):
    """Offsets from the window's start at which requests are due."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = np.random.default_rng([seed, 2]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def warm(run):
    from repro.serving import ContinuousScheduler

    sched = ContinuousScheduler(run.server,
                                batch_window_s=run.traffic["batch_window_s"])
    run.closers.append(sched.close)
    run.state["scheduler"] = sched
    sched.prewarm([run.shape], batches=run.traffic["warm_batches"])
    for n in run.traffic["warm_batches"]:
        futs = [sched.submit(run.images.get(run.images.next_index()))
                for _ in range(n)]
        for f in futs:
            f.result()


def measure(run, seconds):
    sched = run.state["scheduler"]
    w = run.window
    offsets = arrivals(run.traffic["rate_per_s"], seconds, run.seed)
    n = len(offsets)
    finished = [0.0] * n
    futs, idx = [], []
    lock = threading.Lock()
    left = [n]
    all_done = threading.Event()

    def on_done(k):
        finished[k] = time.perf_counter()
        with lock:
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    w.t0 = time.perf_counter()
    due = w.t0 + offsets
    for k in range(n):
        with run.span("bench.make_request"):
            i = run.images.next_index()
            x = run.images.get(i)
        delay = due[k] - time.perf_counter()
        if delay > 0:
            with run.span("bench.await_due"):
                time.sleep(delay)
        sent = time.perf_counter()
        w.gen_lag_s.append(sent - due[k])
        with run.span("bench.submit"):
            fut = sched.submit(x)
        fut.add_done_callback(lambda f, k=k: on_done(k))
        futs.append(fut)
        idx.append(i)
    w.attempted = n
    all_done.wait(timeout=max(0.0, due[-1] + 60.0 - time.perf_counter()))
    for k, (i, fut) in enumerate(zip(idx, futs)):
        if not finished[k] or fut.exception() is not None:
            w.failed += 1
            w.errors.append(repr(fut.exception()) if finished[k]
                            else "no answer within 60 s of the window")
            continue
        w.latencies_s.append(finished[k] - due[k])
        w.done.append((i, run.served(fut.result())))
    w.t1 = max([due[-1]] + finished)
