"""``trace_reduce`` on a small trace recorded on a TPU v5e (three
``infer_batch`` calls of GoogLeNet at batch 8), and on hand-made
intervals."""
import gzip

import pytest

from benchmarks.chip import harness, trace_reduce

TRACE = harness.HERE / "testdata" / "googlenet_b8_3calls.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(TRACE.read_bytes()))
    return trace_reduce.reduce(path)


def test_busy_and_window(summary):
    assert summary["devices"] == 1
    assert summary["ops"] == 1509
    assert summary["busy_s"] == pytest.approx(0.013229799, rel=1e-9)
    assert summary["window_s"] == pytest.approx(0.028755428, rel=1e-9)


def test_top_device_ops(summary):
    ops = summary["device_ops"]
    assert len(ops) == trace_reduce.TOP
    # the LRNs' channel-window sums lead this GoogLeNet batch-8 plan
    assert ops[0] == ["reduce_window_sum.15 f32[8,192,56,56]",
                      pytest.approx(0.003857388, rel=1e-9)]
    assert ops[1][0] == "reduce_window_sum.14 f32[8,64,56,56]"
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)


def test_idle_gaps_by_host_span(summary):
    gaps = dict((n, t) for n, t in summary["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.007361676,
                                                          rel=1e-9)
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_op_name():
    assert trace_reduce.op_name(
        "%copy.403 = bf16[8,3,224,224]{0,1,3,2:T(4,128)} copy(f32[8] %x)") \
        == "copy.403 bf16[8,3,224,224]"


def test_merge_gaps_and_covering():
    busy = trace_reduce.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert trace_reduce.gaps(busy, (0, 12)) == [(3, 5), (9, 12)]
    host = [(0, 12, "outer"), (2, 6, "inner"), (8, 11, "other")]
    assert trace_reduce.covering(host, [4, 7, 10, 13]) == \
        ["inner", "outer", "other", None]
