#!/usr/bin/env python3
"""Readings that the output check's limits are set from, on the chip.

    python3 benchmarks/chip/control.py --workload googlenet.b8 \\
        --program-seeds 101 102 ... --control-seeds 201 202 203

For each program seed it runs the cell as ``run.py`` does, with a short
window, and prints the number the check compares (``logit_err``: the
served answers against the reference).  For each control seed it puts
the reference, computed at each other precision of
``reference.PRECISIONS``, in the program's place on the same kind of
sample, and prints the same number: ``int8``, the control a step below
the configurations' bfloat16 compute, and the two bfloat16 readings.
All seeds run in one process.  The limit of a configuration lies
between the largest program reading and the smallest control reading.

Needs a TPU, like ``run.py``.
"""
import argparse
import json
import sys
import time

import run  # sets up the import path


def control_errs(cell, seed: int, precisions, n: int):
    """Per precision: the largest error of the reference computed at that
    precision, over ``n`` of the seed's request images."""
    import numpy as np

    from benchmarks.chip import harness, reference

    shape = tuple(cell.config["input_chw"])
    images = harness.Images(shape, seed)
    x = np.stack([images.get(i) for i in range(n)])
    params = reference.init_params(cell.layers, shape, seed)
    ref = reference.logits(cell.layers, params, x)
    out = {}
    for p in precisions:
        lo = reference.logits(cell.layers, params, x, precision=p)
        probs = np.exp(reference.log_softmax(lo)).astype(np.float32)
        out[p] = float(reference.logit_err(probs, ref).max())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness, reference

    cell = harness.load_cell(args.workload)
    peak = run.chip_peak(cell.chips)
    if peak is None:
        return run.NO_CHIP
    lower = [p for p in reference.PRECISIONS if p != "float32"]
    for seed in args.program_seeds:
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False, t0,
                               peak_flops=peak)
        print(json.dumps({"workload": cell.name, "side": "program",
                          "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "logit_err": res["checks"]["logit_err"]["value"],
                          "metrics": res["metrics"]}), flush=True)
    for seed in args.control_seeds:
        errs = control_errs(cell, seed, lower, harness.SAMPLE)
        print(json.dumps({"workload": cell.name, "side": "control",
                          "seed": seed, "logit_err": errs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
