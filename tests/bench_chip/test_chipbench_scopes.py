"""``scopes.py``'s reductions on a small trace recorded on a TPU v5e (a few
``PlanServer.infer`` calls of GoogLeNet at 224x224, with the program's
spans, and beside it the served executable's ``op_scopes`` map), on
hand-made intervals, and the ``queue_wait_ms.serve`` reader."""
import json
import shutil
import time

import pytest

from benchmarks.chip import harness, scopes, trace_reduce

TRACE = harness.HERE / "testdata" / "googlenet_b1_calls.xplane.pb.gz"
OP_SCOPES = harness.HERE / "testdata" / "googlenet_b1_calls.op_scopes.json"


@pytest.fixture(scope="module")
def trace():
    return scopes.load(TRACE)


@pytest.fixture(scope="module")
def op_scopes():
    return json.loads(OP_SCOPES.read_text())


def test_scopes_claim_the_busy_time(trace, op_scopes):
    """Nearly all device time belongs to a PBQP node or a layout edge."""
    op_s = scopes.op_seconds(trace)
    assert sum(op_s.values()) == pytest.approx(
        sum(e - s for s, e in trace.busy) / 1e9, rel=1e-9)
    assert scopes.attributed_share(op_s, op_scopes) >= 0.95
    assert all(v.split(":", 1)[0] in ("node", "edge")
               for v in op_scopes.values())
    assert len(trace.ops) == 2384
    assert sum(op_s.values()) == pytest.approx(0.002041581, rel=1e-9)
    per_scope = scopes.by_scope(op_s, op_scopes)
    assert sum(per_scope.values()) == pytest.approx(sum(op_s.values()))
    # the stem's 3x3 conv (a Pallas Winograd F(4,3)) leads this plan
    top = max(per_scope, key=per_scope.get)
    assert (top, per_scope[top]) == ("node:conv2",
                                     pytest.approx(0.000707085, rel=1e-9))
    # this plan has no conversion edge: its copies are XLA's, each
    # attributed to the node it feeds
    assert scopes.transform_share(op_s, op_scopes) == 0.0
    assert op_scopes["copy.297"] == "node:conv1"


def test_program_spans_on_the_device_clock(trace):
    counts = scopes.span_counts(trace)
    calls = counts["infer"]
    assert calls >= 2
    for name in ("prepare", "execute", "dispatch", "fetch", "guard",
                 "crop"):
        assert counts[name] == calls
    idle = scopes.idle_in_spans(trace)
    window_idle = (trace.window[1] - trace.window[0]) / 1e9 - sum(
        e - s for s, e in trace.busy) / 1e9
    # the device waits for the host inside fetch (the copy back) and
    # no span's idle exceeds the window's
    assert idle["fetch"] == pytest.approx(0.006108075, rel=1e-9)
    assert all(0 <= t <= window_idle + 1e-12 for t in idle.values())


def test_harness_reduce_reads_the_new_trace():
    """The harness's own reduction reads this trace too, and agrees on
    the busy time."""
    import gzip
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = harness.pathlib.Path(d) / "t.xplane.pb"
        path.write_bytes(gzip.decompress(TRACE.read_bytes()))
        summary = trace_reduce.reduce(path)
    assert summary["busy_s"] == pytest.approx(
        sum(e - s for s, e in scopes.load(TRACE).busy) / 1e9, rel=1e-9)
    assert len(summary["device_ops"]) == trace_reduce.TOP


def test_overlap_and_idle_on_hand_made_intervals():
    assert scopes._overlap([(0, 4), (6, 9)], [(3, 7), (8, 20)]) == 3
    t = scopes.Trace(window=(0, 100), ops=[(10, 30, "a"), (50, 60, "b")],
                     busy=[(10, 30), (50, 60)],
                     host=[(0, 20, "fetch"), (25, 55, "fetch"),
                           (40, 45, "guard"), (70, 80, "other")])
    # idle: [0,10), [30,50), [60,100); fetch covers [0,20) and [25,55)
    assert scopes.idle_in_spans(t) == {"fetch": 30 / 1e9, "guard": 5 / 1e9}
    op_s = scopes.op_seconds(t)
    assert scopes.attributed_share(op_s, {"a": "node:x"}) == pytest.approx(
        2 / 3)
    assert scopes.transform_share(op_s, {"a": "node:x", "b": "edge:x->y"}) \
        == pytest.approx(1 / 3)


def test_instruction_name():
    assert scopes.instruction(
        "%copy.403 = bf16[8,3,224,224]{0,1,3,2:T(4,128)} copy(f32[8] %x)") \
        == "copy.403"


# ----------------------------------------------------------------------
# queue_wait_ms.serve
# ----------------------------------------------------------------------
def _reader():
    return harness.load_module(
        harness.HERE / "metrics" / "queue_wait_ms.serve.py",
        "chipbench_metric_queue_wait_test").read


def test_queue_wait_reader_is_silent_on_a_parent_shaped_run():
    run = harness.Run(cell=None, seed=1, seconds=1.0, trace=True)
    run.counters_before = {"sched_submits": 0, "phases": {}}
    run.counters_after = {"sched_submits": 10, "phases": {}}
    assert _reader()(run) is None


def test_queue_wait_reader_on_a_tiny_serve_run(tmp_path):
    """A whole ``googlenet.serve`` window on the CPU at 32x32: the reader
    gives the mean wait of the window's requests, in ms."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    cfg_path = chip / "configs" / "googlenet.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(scale=1 / 7, input_chw=[3, 32, 32])
    cfg_path.write_text(json.dumps(cfg))
    traffic_path = chip / "traffic" / "poisson_open.json"
    traffic = json.loads(traffic_path.read_text())
    traffic["rate_per_s"] = 40
    traffic_path.write_text(json.dumps(traffic))

    cell = harness.load_cell("googlenet.serve", root)
    assert "queue_wait_ms.serve" in {m.name for m in cell.per_layer}
    run = harness.set_up(cell, 2**31 + 29, 1.0, False)
    try:
        t0 = time.perf_counter()
        cell.driver.measure(run, 1.0)
        run.counters_after = run.server.stats()
    finally:
        harness.tear_down(run)
    wait_ms = _reader()(run)
    n = len(run.window.done)
    assert n > 0 and wait_ms is not None
    # no request waited longer than its whole latency, nor than the run
    assert 0 < wait_ms <= 1e3 * max(run.window.latencies_s)
    assert wait_ms <= 1e3 * (time.perf_counter() - t0)
