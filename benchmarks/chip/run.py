#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload googlenet.b8 --seed 7 \\
        --seconds 20 --trace 0

Resolves the cell by name from ``BENCHMARK.json`` and the files under
``benchmarks/chip`` (see ``harness.py``), builds the served stack, warms
every shape the cell's traffic uses, measures ``--seconds`` of traffic
and checks a sample of the answers against the plain reference.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its
limit, which also end standard error.

Needs a TPU with as many chips as the cell asks for: on any other device
it exits with 3 before building anything and prints no result.  JAX's
compilation cache lives in ``<checkout>/.jax_cache`` and Python's
bytecode in ``<checkout>/.pycache``, so only a cell's first run in a
checkout compiles.  ``setup_s`` runs from the moment JAX has found the
chip; the process's start before it (imports, the TPU runtime's start)
is reported apart, with set-up's steps, under ``setup_phases_s``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
# Python keeps the bytecode of what it compiles in the checkout, as JAX
# keeps its executables there: only a checkout's first run compiles the
# sources of JAX, NumPy and the program
sys.pycache_prefix = str(ROOT / ".pycache")
sys.dont_write_bytecode = False
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
NO_CHIP = 3


def chip_peak(chips: int):
    """Start JAX with its compilation cache in the checkout, every
    executable kept.  Returns the peak bf16 FLOP/s of one chip, or None,
    with a message, where JAX finds no TPU or fewer than ``chips``."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"needs {chips} TPU chip(s); JAX finds {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return None
    from benchmarks.chip import harness
    return harness.peak_flops(devs[0].device_kind)


def _finite(v):
    return v if not isinstance(v, float) or math.isfinite(v) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.load_cell(args.workload)

    t_jax = time.perf_counter()
    peak = chip_peak(cell.chips)
    if peak is None:
        return NO_CHIP
    t_ready = time.perf_counter()

    result = harness.run_cell(cell, args.seed % 2**63, args.seconds,
                              bool(args.trace), t_ready,
                              trace_dir=TRACE_DIR, peak_flops=peak,
                              phases={"imports": t_jax - T_START,
                                      "tpu_start": t_ready - t_jax})
    for c in result["checks"].values():
        for k in ("value", "limit"):
            c[k] = _finite(c[k])
    print(json.dumps(result), flush=True)
    print("setup phases (s): " + json.dumps(result["setup_phases_s"]),
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
