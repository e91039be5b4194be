"""One client calls ``PlanServer.infer_batch`` with ``batch`` fresh images,
back to back: the throughput path.  The call is synchronous, so each
image is made in place (``Images.request``).

Parameters (traffic file): ``batch``.
"""
import time


def warm(run):
    batch = run.traffic["batch"]
    run.server.compiled_for(run.shape, batch)
    for _ in range(2):
        run.server.infer_batch([run.images.request(run.images.next_index())
                                for _ in range(batch)])


def measure(run, seconds):
    batch = run.traffic["batch"]
    w = run.window
    w.t0 = time.perf_counter()
    end = w.t0 + seconds
    while time.perf_counter() < end:
        with run.span("bench.make_request"):
            idx = [run.images.next_index() for _ in range(batch)]
            xs = [run.images.request(i) for i in idx]
        t = time.perf_counter()
        w.attempted += batch
        try:
            with run.span("bench.infer_batch"):
                outs = run.server.infer_batch(xs)
        except Exception as exc:  # a failed call fails its requests
            w.failed += batch
            w.errors.append(repr(exc))
            continue
        done = time.perf_counter()
        for i, out in zip(idx, outs):
            w.done.append((i, run.served(out)))
            w.latencies_s.append(done - t)
    w.t1 = time.perf_counter()
