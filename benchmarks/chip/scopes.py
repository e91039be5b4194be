#!/usr/bin/env python3
"""Device time by PBQP node and layout edge, and device idle inside the
program's own spans, from one traced window of a cell.

    python3 benchmarks/chip/scopes.py --workload googlenet.b1 --seed 7 \\
        --seconds 5 --out OUT [--keep DIR]

Runs the cell as ``run.py --trace 1`` does (set-up, then one window under
``jax.profiler``), asks the server which PBQP node (``node:<id>``) or
layout conversion edge (``edge:<src>-><dst>``) each instruction of its
executables belongs to (``PlanServer.op_scopes``), and writes
``<out>/<workload>.json``: device time per scope beside the chosen
primitive and the cost model's prediction, the share of busy time that
falls in a scope, the biggest ``copy`` instructions with their scope, and
the device's idle time inside each of the program's span names.  With
``--keep`` the trace (gzipped) and the scope map are also written to
``DIR``: the pair ``tests/bench_chip/test_chipbench_scopes.py`` reduces.

Needs a TPU, like ``run.py``; the reductions below run anywhere.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys
import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import trace_reduce  # noqa: E402

#: the program's host spans around one request (docs/observability.md)
PROGRAM_SPANS = ("infer", "infer_batch", "prepare", "execute", "dispatch",
                 "fetch", "guard", "crop", "sched.dispatch", "sched.batch")
TOP_COPIES = 10

Interval = Tuple[float, float]


@dataclass
class Trace:
    """The traced window (ns), each device op in it as
    ``(start, end, instruction)``, the busiest device's busy intervals,
    and every host span as ``(start, end, name)``."""
    window: Interval
    ops: List[Tuple[float, float, str]]
    busy: List[Interval]
    host: List[Tuple[float, float, str]]


def instruction(hlo: str) -> str:
    """``"%fusion.3 = bf16[8]{0} fusion(...)"`` -> ``"fusion.3"``."""
    return trace_reduce.op_name(hlo).split(" ", 1)[0]


def load(path: pathlib.Path) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) into a :class:`Trace`,
    clipped to the harness's window span."""
    from jax.profiler import ProfileData

    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (s, s + d)
                    elif d > 0:
                        host.append((s, s + d, ev.name))
        elif plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            ops = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                    instruction(ev.name))
                   for line in plane.lines
                   if line.name == trace_reduce.OPS_LINE
                   for ev in line.events]
            if ops:
                devices[plane.name] = ops
    if window is None or not devices:
        raise ValueError(f"{path}: no {trace_reduce.WINDOW_SPAN!r} span "
                         f"or no device op")
    w0, w1 = window
    clipped = {n: [(max(s, w0), min(e, w1), i) for s, e, i in ops
                   if e > w0 and s < w1] for n, ops in devices.items()}
    busy = {n: trace_reduce.merge([(s, e) for s, e, _ in ops])
            for n, ops in clipped.items()}
    busiest = max(busy, key=lambda n: sum(e - s for s, e in busy[n]))
    return Trace(window, clipped[busiest], busy[busiest],
                 [h for h in host if h[1] > w0 and h[0] < w1])


def op_seconds(trace: Trace) -> Dict[str, float]:
    """Device seconds per instruction in the window."""
    out: Dict[str, float] = defaultdict(float)
    for s, e, name in trace.ops:
        out[name] += (e - s) / 1e9
    return dict(out)


def by_scope(op_s: Dict[str, float], op_scopes: Dict[str, str]
             ) -> Dict[str, float]:
    """Device seconds per scope; what no scope claims is ``None``'s."""
    out: Dict[Optional[str], float] = defaultdict(float)
    for name, t in op_s.items():
        out[op_scopes.get(name)] += t
    return dict(out)


def attributed_share(op_s: Dict[str, float],
                     op_scopes: Dict[str, str]) -> float:
    """Share of device op time that falls in a ``node:`` or ``edge:``
    scope."""
    total = sum(op_s.values())
    return sum(t for n, t in op_s.items() if n in op_scopes) / total


def transform_share(op_s: Dict[str, float],
                    op_scopes: Dict[str, str]) -> float:
    """Share of device op time in ``edge:`` scopes: the plan's layout
    conversions."""
    total = sum(op_s.values())
    return sum(t for n, t in op_s.items()
               if op_scopes.get(n, "").startswith("edge:")) / total


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_in_spans(trace: Trace, names=PROGRAM_SPANS) -> Dict[str, float]:
    """Seconds of the window in which the device is idle and the host is
    inside a span of each name (the union of that name's spans, on every
    thread)."""
    idle = trace_reduce.gaps(trace.busy, trace.window)
    out = {}
    for name in names:
        spans = trace_reduce.merge([(s, e) for s, e, n in trace.host
                                    if n == name])
        if spans:
            out[name] = _overlap(idle, spans) / 1e9
    return out


def span_counts(trace: Trace, names=PROGRAM_SPANS) -> Dict[str, int]:
    counts: Dict[str, int] = defaultdict(int)
    for _, _, n in trace.host:
        if n in names:
            counts[n] += 1
    return dict(counts)


def report(trace: Trace, op_scopes: Dict[str, str],
           plans: Dict[int, Dict], requests: int) -> Dict:
    """Everything ``<workload>.json`` holds, from a trace and a scope map.
    ``plans`` maps each batch size served to ``{"choices": {node id:
    primitive}, "predicted_s": {scope: seconds a call}}``."""
    op_s = op_seconds(trace)
    busy = sum(e - s for s, e in trace.busy) / 1e9
    scoped = by_scope(op_s, op_scopes)
    choice, predicted = {}, {}
    for plan in plans.values():
        choice.update(plan["choices"])
        predicted.update(plan["predicted_s"])
    rows = sorted(((s, t) for s, t in scoped.items() if s),
                  key=lambda kv: -kv[1])
    copies = sorted(((n, t) for n, t in op_s.items()
                     if n.startswith("copy")), key=lambda kv: -kv[1])
    return {
        "window_s": (trace.window[1] - trace.window[0]) / 1e9,
        "busy_s": busy,
        "requests": requests,
        "attributed_share": attributed_share(op_s, op_scopes),
        "transform_share": transform_share(op_s, op_scopes),
        "unattributed_s": scoped.get(None, 0.0),
        "scopes": [{"scope": s, "device_s": t,
                    "primitive": choice.get(s.split(":", 1)[1]),
                    "predicted_s_per_call": predicted.get(s)}
                   for s, t in rows],
        "copies": [[n, t, op_scopes.get(n)] for n, t in
                   copies[:TOP_COPIES]],
        "idle_in_spans_s": idle_in_spans(trace),
        "span_counts": span_counts(trace),
        "edges_in_plan": sorted({s for plan in plans.values()
                                 for s in plan["edges"]}),
    }


def plan_summary(server, shape, nb: int) -> Dict:
    """The plan served for batch ``nb``: each node's primitive, each
    scope's predicted seconds a call, the conversion edges it has."""
    from repro.core.plan import edge_scope, node_scope
    from repro.obs.drift import plan_predictions

    sel = server.plan_for(shape, nb)
    pred = plan_predictions(sel, server.cost)
    return {
        "choices": {nid: ch.primitive.name for nid, ch in sel.choices.items()
                    if ch.primitive is not None},
        "predicted_s": {**{node_scope(n): t for n, t in pred["node"].items()},
                        **{edge_scope(*e): t
                           for e, t in pred["edge"].items()}},
        "edges": [edge_scope(*e) for e, c in sel.conversions.items() if c],
    }


def batches(cell) -> List[int]:
    t = cell.traffic
    return list(t.get("warm_batches", [t.get("batch", 1)]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    ap.add_argument("--keep", type=pathlib.Path)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import harness
    from benchmarks.chip.run import NO_CHIP, TRACE_DIR, chip_peak

    cell = harness.load_cell(args.workload)
    peak = chip_peak(cell.chips)
    if peak is None:
        return NO_CHIP
    run = harness.set_up(cell, args.seed % 2**63, args.seconds, True, peak)
    try:
        trace_reduce.clear(TRACE_DIR)
        jax.profiler.start_trace(
            str(TRACE_DIR), profiler_options=trace_reduce.profiler_options())
        try:
            with run.span(trace_reduce.WINDOW_SPAN):
                cell.driver.measure(run, args.seconds)
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        op_scopes = run.server.op_scopes()
        scopes_s = time.perf_counter() - t0
        plans = {nb: plan_summary(run.server, run.shape, nb)
                 for nb in batches(cell)}
    finally:
        harness.tear_down(run)
    path = trace_reduce.find(TRACE_DIR)
    out = report(load(path), op_scopes, plans, len(run.window.done))
    out.update(workload=args.workload, seed=args.seed,
               op_scopes_s=scopes_s, failed=run.window.failed,
               harness=trace_reduce.reduce(path))
    args.out.mkdir(parents=True, exist_ok=True)
    (args.out / f"{args.workload}.json").write_text(json.dumps(out, indent=1))
    if args.keep is not None:
        args.keep.mkdir(parents=True, exist_ok=True)
        stem = args.keep / args.workload.replace(".", "_")
        pathlib.Path(f"{stem}.xplane.pb.gz").write_bytes(
            gzip.compress(path.read_bytes(), mtime=0))
        pathlib.Path(f"{stem}.op_scopes.json").write_text(
            json.dumps(op_scopes, sort_keys=True))
    trace_reduce.clear(TRACE_DIR)
    print(json.dumps({k: out[k] for k in (
        "workload", "requests", "busy_s", "attributed_share",
        "transform_share", "idle_in_spans_s", "op_scopes_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
