"""From a profiler trace to device busy and idle time, top device
operations, and the host spans that cover the device's idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation the chip ran.  Host planes (``/host:CPU``) hold one line per
thread with the program's and JAX's host spans, among them the
benchmark's own ``bench.*`` annotations.  All events share one clock.

The traced window is the host span :data:`WINDOW_SPAN`.  Busy time is
the union of the operations' intervals inside it, averaged over the
devices that ran any; the idle gaps are the stretches of the busiest
device's window that no operation covers.
"""
from __future__ import annotations

import heapq
import pathlib
import shutil
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: host span the harness opens around its measured window
WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[float, float]


def op_name(hlo: str) -> str:
    """``"%fusion.3 = bf16[8,64]{1,0} fusion(...)"`` -> ``"fusion.3 bf16[8,64]"``:
    the instruction and the shape it writes."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}".strip()


def profiler_options():
    """Host spans and device operations, without the Python tracer (it
    records every Python call and would slow the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def clear(trace_dir: pathlib.Path) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def find(trace_dir: pathlib.Path) -> pathlib.Path:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """Stretches of ``window`` that no interval of ``busy`` covers."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def covering(host: List[Tuple[float, float, str]],
             points: List[float]) -> List[Optional[str]]:
    """For each time in ``points``, the name of the innermost host span
    (the latest-started one) that covers it, or None."""
    host = sorted(host)
    order = sorted(range(len(points)), key=points.__getitem__)
    names: List[Optional[str]] = [None] * len(points)
    active: List[Tuple[float, float, str]] = []  # heap by -start
    j = 0
    for k in order:
        t = points[k]
        while j < len(host) and host[j][0] <= t:
            s, e, name = host[j]
            heapq.heappush(active, (-s, e, name))
            j += 1
        while active and active[0][1] < t:  # ended: no longer covers
            heapq.heappop(active)
        if active:
            names[k] = active[0][2]
    return names


def reduce(path: pathlib.Path) -> Dict:
    """Busy and window seconds, top device ops and idle gaps by host span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = (s, s + d)
                    elif d > 0:
                        host.append((s, s + d, ev.name))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                    op_name(ev.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices[plane.name] = ops
    if not devices:
        raise ValueError(f"{path}: no device ran an operation")
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    w0, w1 = window

    busy_per_device = {}
    op_time: Dict[str, float] = defaultdict(float)
    for name, ops in devices.items():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            op_time[n] += e - s
        busy_per_device[name] = merge([(s, e) for s, e, _ in clipped])
    busiest = max(busy_per_device, key=lambda n: sum(
        e - s for s, e in busy_per_device[n]))
    busy_s = sum(sum(e - s for s, e in iv) for iv in
                 busy_per_device.values()) / len(busy_per_device) / 1e9

    idle = gaps(busy_per_device[busiest], window)
    names = covering([h for h in host if h[1] > w0 and h[0] < w1],
                     [(s + e) / 2 for s, e in idle])
    idle_time: Dict[str, float] = defaultdict(float)
    for (s, e), n in zip(idle, names):
        idle_time[n or "no host span"] += e - s

    def top(d: Dict[str, float]) -> List[List]:
        return [[n, t / 1e9] for n, t in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "devices": len(busy_per_device),
            "device_ops": top(op_time), "idle_gaps": top(idle_time),
            "ops": sum(len(v) for v in devices.values())}
