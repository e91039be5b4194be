"""The chip benchmark's harness: one run of one cell, from the files alone.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them,
or to one metric, sits in a file of its own that is found by its name:

* ``configs/<config>.json``: the net's published sizes, the program's
  net builder and bucket policy, and the limit of the output check;
  ``configs/<config>.py`` beside it turns the sizes into the reference's
  layer list (``reference.py``);
* ``traffic/<mix>.json``: a driver name and its parameters;
* ``drivers/<driver>.py``: ``warm(run)`` and ``measure(run, seconds)``;
* ``metrics/<metric>.py``: ``read(run)``, the number or None.

A run builds the served stack as a library user would, warms every shape
its traffic uses, measures a window, reads its metrics, and then checks a
sample of what the window served against the plain reference.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import pathlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import reference

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: a ``--trace 1`` run measures at most this long, all of it under the
#: profiler: a longer trace would be too large to reduce inside a run
TRACE_SECONDS = 5.0
#: finished requests compared with the reference after each window
SAMPLE = 64
#: distinct base images; request ``i`` is base ``i % POOL`` with one pixel
#: set from ``i // POOL``, so no two requests of a run send the same bytes
POOL = 64
#: JAX's event for an executable built, by compiling or from the cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# cells
# ----------------------------------------------------------------------
@dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    layers: List[Dict]
    traffic: Dict
    driver: object
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve workload ``name`` from ``root/BENCHMARK.json`` and the files
    under ``root/benchmarks/chip``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    chip = root / "benchmarks" / "chip"
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg_path = root / cfg_entry["file"]
    config = json.loads(cfg_path.read_text())
    layers = load_module(cfg_path.with_suffix(".py"),
                         f"chipbench_config_{w['config']}").layers(config)
    traffic = json.loads((chip / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    driver = load_module(chip / "drivers" / f"{traffic['driver']}.py",
                         f"chipbench_driver_{traffic['driver']}")

    def metrics(kind: str) -> List[Metric]:
        return [Metric(m["name"], m["unit"],
                       load_module(chip / "metrics" / f"{m['name']}.py",
                                   f"chipbench_metric_{m['name']}").read)
                for m in bench[kind]
                if name in m.get("workloads", [name])]

    return Cell(name, int(w["chips"]), config, layers, traffic, driver,
                metrics("end_to_end"), metrics("per_layer"))


# ----------------------------------------------------------------------
# inputs
# ----------------------------------------------------------------------
class Images:
    """Request images drawn from the seed: ``get(i)`` is request ``i``'s."""

    def __init__(self, shape: Tuple[int, int, int], seed: int) -> None:
        rng = np.random.default_rng([seed, 1])
        self.pool = rng.normal(size=(POOL, *shape)).astype(np.float32)
        self._next = 0

    def next_index(self) -> int:
        i = self._next
        self._next += 1
        return i

    def get(self, i: int) -> np.ndarray:
        x = self.pool[i % POOL].copy()
        x[0, 0, 0] = 1e-3 * (i // POOL)
        return x

    def request(self, i: int) -> np.ndarray:
        """Request ``i``'s image, the bytes of ``get(i)``, made in place in
        the pool: for a synchronous call, which is done with it before
        request ``i + POOL`` is made.  It allocates nothing, so the host's
        page faults on fresh buffers stay off a closed loop's window."""
        x = self.pool[i % POOL]
        x[0, 0, 0] = 1e-3 * (i // POOL)
        return x


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
@dataclass
class Window:
    """What a driver's measured window did."""
    t0: float = 0.0
    t1: float = 0.0
    attempted: int = 0
    failed: int = 0
    #: (request index, served output) of every finished request
    done: List[Tuple[int, np.ndarray]] = field(default_factory=list)
    #: seconds from each finished request's due time to its answer
    latencies_s: List[float] = field(default_factory=list)
    #: open loop: seconds each send ran behind its due time
    gen_lag_s: List[float] = field(default_factory=list)
    errors: List[str] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class CompileCounter:
    """Executables JAX builds (compiled or loaded from the cache) while
    it is open."""

    def __init__(self) -> None:
        self.count = 0

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@dataclass
class Run:
    """The state of one run, handed to drivers and metric readers."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    server: object = None
    images: Optional[Images] = None
    shape: Tuple[int, int, int] = (0, 0, 0)
    #: resources a driver opens; closed after the window, newest first
    closers: List[Callable] = field(default_factory=list)
    #: what a driver keeps between ``warm`` and ``measure``
    state: Dict = field(default_factory=dict)
    setup_s: float = 0.0
    #: seconds of each step: those before set-up that the caller gives
    #: (``run.py``: imports, the TPU runtime's start), ``build_stack``,
    #: the driver's warm-up, and the PBQP solves in it
    phases_s: Dict[str, float] = field(default_factory=dict)
    solve_s: float = 0.0
    window: Window = field(default_factory=Window)
    counters_before: Dict = field(default_factory=dict)
    counters_after: Dict = field(default_factory=dict)
    compiles_in_window: int = 0
    #: ``trace_reduce.reduce`` of the traced window, or None
    trace_summary: Optional[Dict] = None
    flops_per_image: float = 0.0
    peak_flops: float = 0.0

    @property
    def traffic(self) -> Dict:
        return self.cell.traffic

    def span(self, name: str):
        """A host span on the profiler's clock while tracing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def served(self, out: Dict[str, np.ndarray]) -> np.ndarray:
        """The one output of a served request, as a flat vector."""
        (v,) = out.values()
        return np.asarray(v, np.float32).reshape(-1)


def build_stack(run: Run) -> None:
    """``PlanServer`` over the configuration's net with the program's
    defaults for the device: the analytic cost model, no disk tier for
    plans (every run solves its PBQPs), weights from the seed."""
    from repro.convnets import NETWORKS
    from repro.core.costs import AnalyticCostModel
    from repro.serving import BucketPolicy, PlanServer

    cfg = run.cell.config
    build = NETWORKS[cfg["net"]]
    shape = tuple(build(cfg["scale"]).nodes["data"].out_shape)
    if shape != tuple(cfg["input_chw"]):
        raise ValueError(f"{cfg['net']} at scale {cfg['scale']} takes "
                         f"{shape}, the configuration says "
                         f"{cfg['input_chw']}")
    run.shape = shape
    run.server = PlanServer(lambda s: build(cfg["scale"]),
                            AnalyticCostModel(),
                            policy=BucketPolicy(**cfg["policy"]),
                            params_seed=run.seed)
    run.closers.append(run.server.close)
    run.images = Images(shape, run.seed)


def check(run: Run) -> Dict[str, Dict[str, float]]:
    """Compare a sample of the window's answers with the reference.

    Returns each number compared with its limit."""
    cfg = run.cell.config
    w = run.window
    idx = reference.sample(len(w.done), SAMPLE, run.seed)
    err = np.inf
    if idx:
        picked = [w.done[k] for k in idx]
        images = np.stack([run.images.get(i) for i, _ in picked])
        params = reference.init_params(run.cell.layers, run.shape, run.seed)
        ref = reference.logits(run.cell.layers, params, images)
        err = float(reference.logit_err(np.stack([o for _, o in picked]),
                                        ref).max())
    return {
        "logit_err": {"value": err, "limit": cfg["check"]["logit_err"]},
        "failed": {"value": w.failed, "limit": 0},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def device_info() -> Dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def set_up(cell: Cell, seed: int, seconds: float, trace: bool,
           peak_flops: float = 0.0,
           phases: Optional[Dict[str, float]] = None) -> Run:
    """Build the cell's served stack and warm every shape its traffic
    uses.  ``run.phases_s`` keeps how long each step took, after the
    ``phases`` that came before set-up."""
    from . import flops

    run = Run(cell, seed, seconds, trace)
    run.phases_s.update(phases or {})
    run.flops_per_image = flops.model_flops(cell.layers,
                                            tuple(cell.config["input_chw"]))
    run.peak_flops = peak_flops
    t0 = time.perf_counter()
    try:
        build_stack(run)
        t1 = time.perf_counter()
        run.phases_s["build"] = t1 - t0
        cell.driver.warm(run)
        run.phases_s["warm"] = time.perf_counter() - t1
    except BaseException:
        tear_down(run)
        raise
    run.counters_before = run.server.stats()
    run.solve_s = float(run.counters_before["solve_s"])
    run.phases_s["solve"] = run.solve_s
    return run


def tear_down(run: Run) -> None:
    """Close what set-up opened and free the program's executables."""
    for close in reversed(run.closers):
        close()
    run.closers.clear()
    run.server = None
    run.state.clear()
    gc.collect()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, trace_dir: Optional[pathlib.Path] = None,
             peak_flops: float = 0.0,
             phases: Optional[Dict[str, float]] = None) -> Dict:
    """One run: set-up, window, metrics, then the output check.

    ``t_start`` is when JAX had found the chip, on
    ``time.perf_counter``'s clock; set-up runs from there to the
    window's first request.  ``phases`` are the steps before it, which
    the result reports beside set-up's own.  Returns the result object
    that ``run.py`` prints.
    """
    from . import trace_reduce

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    run = None
    try:
        run = set_up(cell, seed, seconds, trace, peak_flops, phases)
        with CompileCounter() as compiles:
            if trace:
                import jax
                trace_reduce.clear(trace_dir)
                jax.profiler.start_trace(
                    str(trace_dir),
                    profiler_options=trace_reduce.profiler_options())
            run.setup_s = time.perf_counter() - t_start
            try:
                with run.span(trace_reduce.WINDOW_SPAN):
                    cell.driver.measure(run, seconds)
            finally:
                if trace:
                    jax.profiler.stop_trace()
        run.compiles_in_window = compiles.count
        run.counters_after = run.server.stats()
        device = device_info()
    finally:
        if run is not None:
            tear_down(run)  # the executables go before the reference runs
    if trace:
        run.trace_summary = trace_reduce.reduce(trace_reduce.find(trace_dir))
        trace_reduce.clear(trace_dir)
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    checks = check(run)
    result = {"correct": passed(checks),
              "attempted": run.window.attempted,
              "failed": run.window.failed,
              "metrics": metrics,
              "device": device,
              "setup_phases_s": run.phases_s}
    if run.window.errors:
        result["errors"] = run.window.errors[:5]
    if trace:
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    result["checks"] = checks
    return result


def peak_flops(device_kind: str,
               path: pathlib.Path = HERE / "peaks.json") -> float:
    """Peak bf16 FLOP/s of one chip of this kind; an unknown kind is an
    error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return float(table[device_kind]["bf16_flops_per_s"])


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), None if empty."""
    return float(np.percentile(values, q)) if values else None


def latency_ms(run: Run, q: float) -> Optional[float]:
    """The ``q``-th percentile of the window's request latencies, in ms."""
    p = percentile(run.window.latencies_s, q)
    return None if p is None else 1e3 * p


def idle_share(run: Run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, in % (``trace_reduce.py``); None without a trace."""
    t = run.trace_summary
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
