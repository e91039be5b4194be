#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, once, to find the highest
rate the served stack sustains.

    python3 benchmarks/chip/sweep.py --workload googlenet.serve \\
        --rates 100 200 400 800 --seconds 10 --seed 1

Sets the cell up as ``run.py`` does (``harness.set_up``), then offers
each rate for ``--seconds`` in turn and prints one JSON line per rate:
completions per second against the offer, latency p50 and p99 from the
due time, the sender's lag, requests per launched batch, and the p50
latency of the window's first and last fifth of requests (a backlog that
grows shows as a last fifth far slower than the first).  The cell's
traffic file then holds 0.8 x the highest rate that keeps up.  Needs a
TPU, like ``run.py``.
"""
import argparse
import json
import sys

import run  # sets up the import path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    peak = run.chip_peak(cell.chips)
    if peak is None:
        return run.NO_CHIP
    r = harness.set_up(cell, args.seed, args.seconds, False, peak)
    try:
        for rate in args.rates:
            cell.traffic["rate_per_s"] = rate
            r.window = harness.Window()
            before = r.server.stats()
            cell.driver.measure(r, args.seconds)
            after = r.server.stats()
            w = r.window
            lat = np.asarray(w.latencies_s) * 1e3
            fifth = max(1, len(lat) // 5)
            batches = after["sched_batches"] - before["sched_batches"]
            print(json.dumps({
                "rate_per_s": rate, "attempted": w.attempted,
                "failed": w.failed,
                "completed_per_s": len(w.done) / w.seconds,
                "p50_ms": float(np.percentile(lat, 50)),
                "p99_ms": float(np.percentile(lat, 99)),
                "first_fifth_p50_ms": float(np.median(lat[:fifth])),
                "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
                "gen_lag_p99_ms": 1e3 * float(np.percentile(w.gen_lag_s, 99)),
                "batch_fill": (after["sched_submits"]
                               - before["sched_submits"]) / max(1, batches),
                "setup_phases_s": r.phases_s,
            }), flush=True)
    finally:
        harness.tear_down(r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
