"""One client calls ``PlanServer.infer`` with one fresh image, back to
back: the latency path, and the only one that runs the unbatched
executable.

Parameters (traffic file): none.
"""
import time


def warm(run):
    run.server.compiled_for(run.shape, 1)
    for _ in range(3):
        run.server.infer(run.images.get(run.images.next_index()))


def measure(run, seconds):
    w = run.window
    w.t0 = time.perf_counter()
    end = w.t0 + seconds
    while time.perf_counter() < end:
        with run.span("bench.make_request"):
            i = run.images.next_index()
            x = run.images.get(i)
        t = time.perf_counter()
        w.attempted += 1
        try:
            with run.span("bench.infer"):
                out = run.server.infer(x)
        except Exception as exc:  # a failed call fails its request
            w.failed += 1
            w.errors.append(repr(exc))
            continue
        w.latencies_s.append(time.perf_counter() - t)
        w.done.append((i, run.served(out)))
    w.t1 = time.perf_counter()
