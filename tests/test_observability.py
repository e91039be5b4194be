"""Observability acceptance tests (ISSUE: close the loop).

Pins down the three pillars end to end: the metrics registry is
exactly-once under a threaded hammer and its percentiles are correct;
trace spans nest correctly through the serving stack (including the
``infer_batch`` coalescing path and the cross-stack ``queue_wait``
region); and the drift detector flags a deliberately staled profile,
recalibrates ONLY the flagged entries, rotates every plan-cache key
through the content hash, and re-converges.
"""
import json
import math
import threading

import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.costs import AnalyticCostModel
from repro.core.plan import compile_plan
from repro.core.selection import select_pbqp
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer, configure, get_tracer
from repro.serving import BucketPolicy, PlanServer, conv_tower
from repro.serving.metrics import COUNT_FIELDS, TIME_FIELDS, ServingCounters
from repro.serving.towers import conv_stack

CM = AnalyticCostModel()
POLICY = BucketPolicy(min_hw=8, max_hw=64)

#: bounded primitive pool for the recalibration-loop tests — see
#: repro.obs.drift.RestrictedCostModel
ALLOWED = ("direct_lax_chw_chw_oihw", "direct_lax_hwc_hwc_hwio",
           "wino2d_f2x3_chw")


def _server(**kw):
    kw.setdefault("policy", POLICY)
    kw.setdefault("lru_capacity", 4)
    return PlanServer(lambda s: conv_tower(s, depth=2, width=8), CM, **kw)


@pytest.fixture
def sink():
    """Route the global tracer into a list for the test, then disable."""
    records = []
    configure(records, enabled=True)
    try:
        yield records
    finally:
        configure(enabled=False)


def _by_name(records, name):
    return [r for r in records if r["name"] == name]


# ---------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_hammer_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("hits")
        n_threads, per_thread = 8, 5000

        def worker():
            for _ in range(per_thread):
                c.add()

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * per_thread
        assert isinstance(c.value, int)

    def test_histogram_hammer_exact(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        n_threads, per_thread = 8, 2000

        def worker(i):
            for j in range(per_thread):
                h.record(1e-6 * (i * per_thread + j + 1))

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == n_threads * per_thread
        assert sum(h.counts) == h.count

    def test_percentiles(self):
        h = Histogram()
        for ms in range(1, 101):          # 1..100 ms, uniform
            h.record(ms * 1e-3)
        assert h.percentile(0) == pytest.approx(1e-3)
        assert h.percentile(100) == pytest.approx(0.1)
        # geometric buckets estimate within a factor of the bucket width
        assert h.percentile(50) == pytest.approx(0.05, rel=0.5)
        assert h.percentile(95) >= h.percentile(50)
        q = h.quantiles()
        assert set(q) == {"p50", "p95", "p99"}

    def test_percentile_single_sample_is_exact(self):
        h = Histogram()
        h.record(3.3e-3)
        for p in (0, 50, 99, 100):
            assert h.percentile(p) == pytest.approx(3.3e-3)

    def test_empty_histogram_nan(self):
        h = Histogram()
        assert math.isnan(h.percentile(50))
        assert h.snapshot()["count"] == 0

    def test_labels_key_distinct_metrics(self):
        reg = MetricsRegistry()
        reg.counter("x", phase="a").add(1)
        reg.counter("x", phase="b").add(2)
        snap = reg.snapshot()
        assert snap['x{phase="a"}'] == 1
        assert snap['x{phase="b"}'] == 2
        # same labels -> same underlying metric
        assert reg.counter("x", phase="a") is reg.counter("x", phase="a")

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("requests").add(3)
        reg.histogram("lat_seconds", phase="execute").record(2e-3)
        text = reg.prometheus_text()
        assert "# TYPE requests counter" in text
        assert "requests 3" in text
        assert "# TYPE lat_seconds summary" in text
        assert 'lat_seconds{phase="execute",quantile="0.50"}' in text
        assert 'lat_seconds_count{phase="execute"} 1' in text
        assert text.endswith("\n")


# ---------------------------------------------------------------------
# serving counters on the registry
# ---------------------------------------------------------------------
class TestServingCounters:
    def test_snapshot_compat(self):
        c = ServingCounters()
        c.add(requests=2, solves=1, solve_s=0.5, plan_mem_hits=1,
              plan_misses=1)
        s = c.snapshot()
        for f in COUNT_FIELDS:
            assert isinstance(s[f], int), f
        for f in TIME_FIELDS:
            assert isinstance(s[f], float), f
        assert s["requests"] == 2 and s["solves"] == 1
        assert s["solve_s"] == pytest.approx(0.5)
        assert s["plan_hits"] == 1 and s["plan_hit_rate"] == 0.5
        assert c.requests == 2  # attribute reads still work

    def test_unknown_field_raises(self):
        with pytest.raises(AttributeError):
            ServingCounters().add(bogus=1)
        with pytest.raises(AttributeError):
            ServingCounters().bogus

    def test_threaded_hammer_no_lost_increments(self):
        c = ServingCounters()
        n_threads, per_thread = 8, 2000

        def worker():
            for _ in range(per_thread):
                c.add(requests=1, exec_hits=1, execute_s=1e-5)

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = n_threads * per_thread
        s = c.snapshot()
        assert s["requests"] == total
        assert s["exec_hits"] == total
        assert s["execute_s"] == pytest.approx(total * 1e-5)
        assert c.phase_quantiles()["execute"]["count"] == total

    def test_phase_quantiles_bucket_split(self):
        c = ServingCounters()
        c.add(execute_s=1e-3, _bucket="8x8x1")
        c.add(execute_s=2e-3, _bucket="16x16x1")
        q = c.phase_quantiles()
        assert q["execute"]["count"] == 2
        assert q["execute[bucket=8x8x1]"]["count"] == 1
        assert q["execute[bucket=16x16x1]"]["count"] == 1
        for v in q.values():
            assert {"count", "p50", "p95", "p99"} <= set(v)


# ---------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------
class TestTracer:
    def test_disabled_is_null(self):
        tr = Tracer()  # default: disabled, no sink
        with tr.span("x") as sp:
            assert sp is NULL_SPAN
            sp.set(ignored=1)
        tr.emit("y", 0.0, 1.0)

    def test_nesting_and_attrs(self):
        records = []
        tr = Tracer(records, enabled=True)
        with tr.span("outer", a=1) as outer:
            with tr.span("inner") as inner:
                inner.set(b=2)
            tr.emit("event", 1.0, 1.5, c=3)
        assert [r["name"] for r in records] == ["inner", "event", "outer"]
        inner_r, event_r, outer_r = records
        assert outer_r["parent"] is None and outer_r["a"] == 1
        assert inner_r["parent"] == outer_r["span"] and inner_r["b"] == 2
        assert event_r["parent"] == outer_r["span"]
        assert event_r["dur_s"] == pytest.approx(0.5)
        assert inner_r["trace"] == event_r["trace"] == outer_r["trace"]

    def test_sibling_spans_share_trace(self):
        records = []
        tr = Tracer(records, enabled=True)
        with tr.span("root"):
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
        a, b, root = records
        assert a["parent"] == b["parent"] == root["span"]

    def test_jsonl_file_sink(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        tr = Tracer(path, enabled=True)
        with tr.span("x", k="v"):
            pass
        tr.flush()
        recs = [json.loads(line)
                for line in path.read_text().splitlines()]
        assert recs[0]["name"] == "x" and recs[0]["k"] == "v"
        assert {"trace", "span", "parent", "t0", "dur_s"} <= set(recs[0])


    @pytest.mark.parametrize("capturing", [False, True])
    @pytest.mark.parametrize("jsonl", [False, True])
    def test_profiler_annotation_while_capturing(self, monkeypatch,
                                                 capturing, jsonl):
        """A span opens a profiler annotation exactly while a capture
        runs, with or without the JSONL sink, and the JSONL records do
        not change."""
        from repro.obs import trace as trace_mod
        opened = []

        class FakeAnnotation:
            @staticmethod
            def is_enabled():
                return capturing

            def __init__(self, name, **meta):
                self.name, self.meta, self.closed = name, dict(meta), False
                opened.append(self)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self.closed = True

            def set_metadata(self, **meta):
                self.meta.update(meta)

        monkeypatch.setattr(trace_mod, "_annotation_cls", FakeAnnotation)
        records = []
        tr = Tracer(records, enabled=jsonl)
        with tr.span("outer", a=1) as outer:
            with tr.span("inner") as inner:
                inner.set(b=2)
            tr.emit("event", 1.0, 1.5)
        assert (outer is NULL_SPAN) == (not capturing and not jsonl)
        if capturing:
            assert [(o.name, o.closed) for o in opened] == \
                [("outer", True), ("inner", True)]
            assert opened[0].meta == {"a": 1, "event_s": 0.5}
            assert opened[1].meta == {"b": 2}
        else:
            assert opened == []
        if jsonl:
            assert [r["name"] for r in records] == ["inner", "event",
                                                    "outer"]
            inner_r, event_r, outer_r = records
            assert set(outer_r) == {"name", "trace", "span", "parent",
                                    "t0", "dur_s", "a"}
            assert inner_r["b"] == 2 and "b" not in outer_r
            assert inner_r["parent"] == event_r["parent"] == outer_r["span"]
        else:
            assert records == []


# ---------------------------------------------------------------------
# spans through the serving stack
# ---------------------------------------------------------------------
class TestServingSpans:
    def test_infer_cold_span_tree(self, sink):
        srv = _server()
        try:
            srv.infer(np.zeros((3, 12, 12), np.float32))
        finally:
            srv.close()
        names = {r["name"] for r in sink}
        assert {"infer", "plan", "pbqp.solve", "compile", "execute",
                "crop"} <= names
        infer = _by_name(sink, "infer")[0]
        plan = _by_name(sink, "plan")[0]
        solve = _by_name(sink, "pbqp.solve")[0]
        assert plan["parent"] == infer["span"]
        assert plan["source"] == "solve"
        assert solve["parent"] == plan["span"]
        assert {"nodes", "edges", "cost", "bb", "prunes"} <= set(solve)
        for name in ("execute", "crop", "compile"):
            r = _by_name(sink, name)[0]
            assert r["parent"] == infer["span"]
            assert r["trace"] == infer["trace"]

    def test_infer_warm_plan_source_mem(self, sink):
        srv = _server()
        try:
            x = np.zeros((3, 12, 12), np.float32)
            srv.infer(x)
            sink.clear()
            srv.infer(x)
        finally:
            srv.close()
        # hot bucket: no plan lookup at all (executable LRU hit), no
        # solve, no compile — just the request spans
        names = [r["name"] for r in sink]
        assert names.count("infer") == 1
        assert "pbqp.solve" not in names and "compile" not in names
        # evicting the executable but keeping the plan shows the
        # plan-tier memory hit
        srv2 = _server()
        try:
            srv2.plan_for(x.shape)
            sink.clear()
            srv2.infer(x)
            plan = _by_name(sink, "plan")[0]
            assert plan["source"] == "mem"
        finally:
            srv2.close()

    def test_coalesced_flush_span_tree(self, sink):
        srv = _server()
        try:
            imgs = [np.zeros((3, 12, 12), np.float32) for _ in range(3)]
            futs = [srv.enqueue(x) for x in imgs]
            served = srv.flush()
            assert served == 3
            for f in futs:
                assert f.result() is not None
        finally:
            srv.close()
        flush = _by_name(sink, "flush")[0]
        batch = _by_name(sink, "infer_batch")[0]
        waits = _by_name(sink, "queue_wait")
        execs = _by_name(sink, "execute")
        assert flush["requests"] == 3
        assert batch["parent"] == flush["span"]
        assert batch["requests"] == 3
        # 3 same-bucket images coalesce into ONE executable invocation
        assert batch["invocations"] == 1
        assert len(execs) == 1 and execs[0]["coalesced"] == 3
        assert execs[0]["parent"] == batch["span"]
        # queue_wait: opened in enqueue(), closed (and parented) in flush
        assert len(waits) == 3
        for w in waits:
            assert w["parent"] == flush["span"]
            assert w["trace"] == flush["trace"]
            assert w["dur_s"] >= 0.0

    def test_request_phase_spans(self, sink):
        """``prepare`` under the request span; ``dispatch``, ``fetch``
        and ``guard`` under ``execute``, on both request paths."""
        srv = _server()
        try:
            srv.infer(np.zeros((3, 12, 12), np.float32))
            srv.infer_batch([np.zeros((3, 12, 12), np.float32)] * 2)
        finally:
            srv.close()
        for root_name in ("infer", "infer_batch"):
            root = _by_name(sink, root_name)[0]
            mine = [r for r in sink if r["trace"] == root["trace"]]
            execute = [r for r in mine if r["name"] == "execute"][-1]
            prepare = [r for r in mine if r["name"] == "prepare"]
            assert prepare and all(r["parent"] == root["span"]
                                   for r in prepare)
            for name in ("dispatch", "fetch", "guard"):
                (r,) = [r for r in mine if r["name"] == name
                        and r["parent"] == execute["span"]]
                assert r["dur_s"] >= 0.0

    def test_spans_reach_a_profiler_capture(self, tmp_path):
        """With the JSONL sink off, a ``jax.profiler`` capture holds the
        request's spans on its host planes."""
        import jax
        from jax.profiler import ProfileData

        srv = _server(guard_outputs=True)
        x = np.zeros((3, 12, 12), np.float32)
        try:
            srv.infer(x)  # solve + compile outside the capture
            jax.profiler.start_trace(str(tmp_path))
            try:
                srv.infer(x)
            finally:
                jax.profiler.stop_trace()
        finally:
            srv.close()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        names = {ev.name for plane in ProfileData.from_file(str(path)).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert {"infer", "prepare", "execute", "dispatch", "fetch", "guard",
                "crop"} <= names

    def test_stats_phases_percentiles(self, sink):
        srv = _server()
        try:
            srv.infer(np.zeros((3, 12, 12), np.float32))
            s = srv.stats()
        finally:
            srv.close()
        phases = s["phases"]
        assert {"solve", "compile", "execute"} <= set(phases)
        for q in phases.values():
            assert q["count"] >= 1
            assert {"p50", "p95", "p99"} <= set(q)
        # per-bucket split for the executed bucket
        assert any(k.startswith("execute[bucket=") for k in phases)
        assert "serving_latency_seconds" in srv.metrics_text()


# ---------------------------------------------------------------------
# the continuous scheduler's spans and queue wait
# ---------------------------------------------------------------------
class TestSchedulerTracing:
    def test_queue_wait_one_sample_per_request(self, sink, monkeypatch):
        """``sched_queue_wait_s`` gains one sample per request, each
        between 0 and that request's ``request_s``; each batch runs
        under a ``sched.batch`` span with its size and reason."""
        import threading as th

        from repro.serving import ContinuousScheduler
        srv = _server()
        seen = []
        add = srv.counters.add

        def spy(_bucket=None, **kw):
            for k in ("sched_queue_wait_s", "request_s"):
                if k in kw:
                    seen.append((th.get_ident(), k, kw[k]))
            add(_bucket=_bucket, **kw)

        monkeypatch.setattr(srv.counters, "add", spy)
        sched = ContinuousScheduler(srv, batch_window_s=0.005)
        n = 7
        try:
            sched.prewarm([(3, 12, 12)], batches=(1, 2, 4))
            seen.clear()
            sink.clear()
            futs = [sched.submit(np.zeros((3, 12, 12), np.float32))
                    for _ in range(n)]
            for f in futs:
                f.result(timeout=60)
            s = sched.stats()
        finally:
            sched.close()
            srv.close()
        waits = [(t, v) for t, k, v in seen if k == "sched_queue_wait_s"]
        lats = [(t, v) for t, k, v in seen if k == "request_s"]
        assert len(waits) == len(lats) == n
        # a worker records a batch's waits, then its latencies, in the
        # group's order: pair them per thread
        for tid in {t for t, _ in waits}:
            mine_w = [v for t, v in waits if t == tid]
            mine_l = [v for t, v in lats if t == tid]
            assert all(0.0 <= w <= r for w, r in zip(mine_w, mine_l))
        assert s["phases"]["sched_queue_wait"]["count"] >= n
        batches = _by_name(sink, "sched.batch")
        assert sum(b["size"] for b in batches) == n
        assert {b["reason"] for b in batches} <= {"full", "deadline",
                                                  "window"}
        for r in _by_name(sink, "infer_batch"):
            assert r["parent"] in {b["span"] for b in batches}
        assert _by_name(sink, "sched.dispatch")


# ---------------------------------------------------------------------
# compile counter (satellite: thread-safe, registry-backed)
# ---------------------------------------------------------------------
class TestCompileCount:
    def test_concurrent_compiles_counted_exactly(self):
        """Every XLA compile JAX reports lands in the process-wide
        counter exactly once, from any thread."""
        import jax
        import jax.numpy as jnp

        before = plan_mod.xla_compile_stats()
        n_threads = 6

        def worker(k):
            jax.jit(lambda x: x * k + 1).lower(
                jax.ShapeDtypeStruct((4,), jnp.float32)).compile()

        ts = [threading.Thread(target=worker, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        after = plan_mod.xla_compile_stats()
        assert after["xla_compiles"] == before["xla_compiles"] + n_threads
        assert after["xla_compile_s"] > before["xla_compile_s"]


# ---------------------------------------------------------------------
# named scopes per PBQP node and conversion edge
# ---------------------------------------------------------------------
def _alternating_plan(ops: bool):
    """Pointwise convs alternating HWC and CHW primitives, so every edge
    carries a conversion chain; with ``ops`` a relu after each."""
    from repro.core.graph import Net, relu
    from repro.core.primitives import registry
    from repro.core.selection import select_fixed

    by_name = {p.name: p for p in registry()}
    net = Net("alt")
    x = net.input("data", (8, 12, 12))
    for i in range(4):
        x = net.conv(f"conv{i}", x, k=1, m=8)
        if ops:
            x = net.op(f"relu{i}", [x], relu())
    pick = {n.id: by_name["pw_gemm_n_hwc" if i % 2 == 0 else
                          "pw_gemm_n_chw"]
            for i, n in enumerate(net.conv_nodes())}
    return select_fixed(net, CM, pick, "alt"), net.init_params(0)


class TestOpScopes:
    def test_every_node_and_edge_named_and_nothing_else(self):
        sel, params = _alternating_plan(ops=False)
        cnet = compile_plan(sel, params)
        scopes = cnet.scopes()
        edges = {f"edge:{s}->{d}" for (s, d), c in sel.conversions.items()
                 if c}
        assert len(edges) == 4  # data->conv0 and three between convs
        assert scopes == edges | {f"node:conv{i}" for i in range(4)}
        got = cnet.op_scopes((8, 12, 12))
        assert set(got.values()) == scopes

    def test_scopes_only_name_the_plan(self):
        """With op nodes XLA fuses some scopes away; whatever is named
        belongs to the plan, every scope is opened, and the batched
        executable is mapped too."""
        import jax
        sel, params = _alternating_plan(ops=True)
        for batch, shape in ((1, (8, 12, 12)), (4, (4, 8, 12, 12))):
            s = sel if batch == 1 else select_pbqp(
                sel.net.with_batch(batch), CM)
            cnet = compile_plan(s, s.net.init_params(0), batch=batch)
            got = cnet.op_scopes(shape)
            assert got and set(got.values()) <= cnet.scopes()
            lowered = cnet.fn.lower(
                jax.ShapeDtypeStruct(shape, np.float32),
                cnet.params).as_text(debug_info=True)
            assert all(sc in lowered for sc in cnet.scopes())

    def test_scopes_change_metadata_only(self, monkeypatch):
        import contextlib
        import re

        import jax
        sel, params = _alternating_plan(ops=True)

        def program(cnet):
            text = cnet.fn.lower(jax.ShapeDtypeStruct((8, 12, 12),
                                                      np.float32),
                                 cnet.params).compile().as_text()
            text = text.split("\nFileNames")[0]
            return re.sub(r", metadata=\{[^}]*\}", "", text)

        scoped = program(compile_plan(sel, params))
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        assert program(compile_plan(sel, params)) == scoped

    def test_hlo_scopes_from_bodies_and_consumers(self):
        """A fusion without a scope takes its body's; an unscoped copy
        takes its consumer's; nothing outside ``scopes`` is named."""
        hlo = "\n".join([
            "HloModule m",
            "",
            "%fused_computation.1 (p: f32[4]) -> f32[4] {",
            "  %p = f32[4]{0} parameter(0)",
            '  ROOT %n.1 = f32[4]{0} negate(%p), '
            'metadata={op_name="jit(run)/vmap(node:c1)/neg"}',
            "}",
            "",
            "ENTRY %main (x: f32[4]) -> f32[4] {",
            '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
            '  %copy.2 = f32[4]{0} copy(%x), metadata={op_name="x"}',
            "  %fusion.3 = f32[4]{0} fusion(%copy.2), kind=kLoop, "
            "calls=%fused_computation.1",
            '  %t.4 = f32[4]{0} transpose(%fusion.3), dimensions={0}, '
            'metadata={op_name="jit(run)/edge:c1->c2/transpose"}',
            '  ROOT %s.5 = f32[4]{0} sine(%t.4), '
            'metadata={op_name="jit(run)/node:other/sin"}',
            "}",
        ])
        got = plan_mod.hlo_op_scopes(hlo, {"node:c1", "edge:c1->c2"})
        assert got == {"x": "node:c1", "copy.2": "node:c1",
                       "fusion.3": "node:c1", "t.4": "edge:c1->c2"}

    def test_server_op_scopes_cover_live_executables(self):
        srv = _server()
        try:
            srv.infer(np.zeros((3, 12, 12), np.float32))
            srv.infer_batch([np.zeros((3, 12, 12), np.float32)] * 2)
            got = srv.op_scopes()
            scopes = set()
            for n in (1, 2):
                scopes |= srv.compiled_for((3, 12, 12), n).scopes()
        finally:
            srv.close()
        assert got and set(got.values()) <= scopes
        assert any(v.startswith("node:") for v in got.values())


# ---------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------
class TestInstrumentedNet:
    def test_outputs_identical_and_timings_complete(self):
        from repro.obs.drift import InstrumentedNet

        net = conv_stack((3, 12, 12), depth=2, width=8)
        sel = select_pbqp(net, CM)
        cnet = compile_plan(sel, net.init_params(0))
        inst = InstrumentedNet(cnet)
        x = np.random.default_rng(0).normal(
            size=(3, 12, 12)).astype(np.float32)
        ref = {k: np.asarray(v) for k, v in cnet(x).items()}
        outs, timings = inst(x)
        assert set(outs) == set(ref)
        for k in ref:
            np.testing.assert_allclose(outs[k], ref[k],
                                       rtol=1e-4, atol=1e-5)
        conv_ids = {n.id for n in net.conv_nodes()}
        assert conv_ids <= set(timings["node"])
        assert all(t > 0 for t in timings["node"].values())
        assert set(timings["edge"]) <= set(sel.conversions)
        assert timings["unmodeled_s"] >= 0.0


class TestDriftDetector:
    def _plan(self):
        net = conv_stack((3, 12, 12), depth=2, width=8)
        sel = select_pbqp(net, CM)
        return net, sel

    def _synthetic(self, pred, scale):
        return {"node": {nid: s * scale for nid, s in
                         pred["node"].items()},
                "edge": {}, "unmodeled_s": 0.0}

    def test_predictions_itemize_objective(self):
        from repro.obs.drift import plan_predictions

        net, sel = self._plan()
        pred = plan_predictions(sel, CM)
        total = sum(pred["node"].values()) + sum(pred["edge"].values())
        assert total == pytest.approx(sel.predicted_cost, rel=1e-6)

    def test_flags_only_drifted_entries(self):
        from repro.obs.drift import DriftDetector, plan_predictions

        net, sel = self._plan()
        pred = plan_predictions(sel, CM)
        det = DriftDetector(CM, threshold=2.0)
        det.observe(sel, self._synthetic(pred, 1.0))
        assert det.flagged() == []
        assert det.plan_within_threshold()

        det4 = DriftDetector(CM, threshold=2.0)
        det4.observe(sel, self._synthetic(pred, 4.0))
        flagged = {e.nid for e in det4.flagged()}
        assert flagged == {n.id for n in net.conv_nodes()}
        assert det4.plan_ratio() == pytest.approx(4.0, rel=1e-6)
        assert not det4.plan_within_threshold()
        rows = det4.report()
        assert rows[0]["flagged"] and rows[0]["ratio"] == \
            pytest.approx(4.0, rel=1e-6)
        rec = det4.recommendation()
        assert rec["recalibrate"] and set(rec["flagged"]) == flagged

    def test_ewma_converges_to_new_level(self):
        from repro.obs.drift import DriftDetector, plan_predictions

        net, sel = self._plan()
        pred = plan_predictions(sel, CM)
        det = DriftDetector(CM, alpha=0.5, threshold=2.0)
        det.observe(sel, self._synthetic(pred, 1.0))
        for _ in range(12):
            det.observe(sel, self._synthetic(pred, 4.0))
        assert all(e.ratio() == pytest.approx(4.0, rel=1e-2)
                   for e in det.entries.values())

    def test_recalibrate_writes_only_flagged(self):
        from repro.calibrate.profile import HardwareProfile
        from repro.obs.drift import DriftDetector, plan_predictions

        net, sel = self._plan()
        pred = plan_predictions(sel, CM)
        det = DriftDetector(CM, threshold=2.0)
        det.observe(sel, self._synthetic(pred, 4.0))
        profile = HardwareProfile.new()
        h0 = profile.content_hash()
        updated = det.recalibrate(profile)
        assert updated == [e.profile_key for e in det.flagged()
                           if e.profile_key]
        assert len(updated) == len({n.id for n in net.conv_nodes()})
        # the invalidation chain: new entries -> new content hash
        assert profile.content_hash() != h0
        for e in det.flagged():
            assert profile.get(e.profile_key) == pytest.approx(
                e.ewma_observed_s / max(e.per_image_div, 1))
        # nothing flagged -> nothing written, hash stable
        det_ok = DriftDetector(CM, threshold=2.0)
        det_ok.observe(sel, self._synthetic(pred, 1.0))
        h1 = profile.content_hash()
        assert det_ok.recalibrate(profile) == []
        assert profile.content_hash() == h1

    def test_rejects_mesh_plans_without_mesh_axes(self):
        from repro.obs.drift import plan_predictions

        net, sel = self._plan()
        # Choice is a frozen dataclass; forge a dp placement in place
        object.__setattr__(next(iter(sel.choices.values())),
                           "placement", "dp")
        with pytest.raises(ValueError, match="mesh-less"):
            plan_predictions(sel, CM)

    @pytest.mark.parametrize("mesh_axes", [
        {"data": 2, "model": 4}, {"stage": 4}])
    def test_itemizes_placed_plans_with_mesh_axes(self, mesh_axes):
        """With mesh_axes, a placement-solved plan itemizes into node
        compute + edge transforms + collective terms that sum back to
        the solver's objective exactly — the placement ledger comes
        from the same PlacementPricing the solver priced with."""
        from repro.obs.drift import plan_predictions
        from repro.serving.towers import bottleneck_tower, uniform_stack

        if "stage" in mesh_axes:
            net = uniform_stack((8, 8, 8), depth=6).with_batch(8)
        else:
            net = bottleneck_tower((4, 16, 16)).with_batch(8)
        sel = select_pbqp(net, CM, mesh_axes=mesh_axes)
        assert any(c.placement != "rep" for c in sel.choices.values())
        pred = plan_predictions(sel, CM, mesh_axes=mesh_axes)
        assert pred["collective"], "placed plan must itemize collectives"
        total = (sum(pred["node"].values()) +
                 sum(pred["edge"].values()) +
                 sum(pred["collective"].values()))
        assert total == pytest.approx(sel.predicted_cost, rel=1e-9)

    def test_report_rows_carry_placement(self):
        from repro.obs.drift import DriftDetector, plan_predictions

        net, sel = self._plan()
        det = DriftDetector(CM, threshold=2.0)
        det.observe(sel, self._synthetic(
            plan_predictions(sel, CM), 1.0))
        rows = det.report()
        assert rows
        assert all(r["placement"] == "rep" for r in rows
                   if r["kind"] == "node")


class TestDriftEndToEnd:
    """The full workflow: calibrate -> stale -> flag -> recalibrate."""

    def test_recalibration_loop_closes_the_loop(self):
        from repro.calibrate.model import CalibratedCostModel
        from repro.calibrate.profile import HardwareProfile
        from repro.obs.drift import (
            DriftDetector, InstrumentedNet, RestrictedCostModel,
            recalibration_loop,
        )
        from repro.serving.bucketing import bucket_key
        from repro.serving.plan_cache import plan_key

        shape = (3, 16, 16)
        net = conv_stack(shape, depth=2, width=8)
        params = net.init_params(0)
        x = np.random.default_rng(0).normal(size=shape).astype(np.float32)
        threshold, runs = 2.0, 2

        # calibrate from instrumented traffic to a fixed point
        profile = HardwareProfile.new()
        base = recalibration_loop(net, params, x, profile,
                                  allowed=ALLOWED, threshold=threshold,
                                  runs=runs)
        assert base["converged"]
        assert base["detector"].plan_within_threshold()

        # stale the profile: converged node entries 8x too fast — the
        # underpriced entries *attract* the next solve
        hash_before = profile.content_hash()
        perturbed = {}
        for e in base["detector"].entries.values():
            if e.kind != "node":
                continue
            old = profile.get(e.profile_key)
            profile.put(e.profile_key,
                        (old if old is not None else e.predicted_s) / 8.0)
            perturbed[e.nid] = e.profile_key
        assert profile.content_hash() != hash_before

        cost = RestrictedCostModel(CalibratedCostModel(profile), ALLOWED)
        sel = select_pbqp(net, cost)
        inst = InstrumentedNet(compile_plan(sel, params))
        det = DriftDetector(cost, threshold=threshold)
        for _ in range(runs):
            _, tm = inst(x)
            det.observe(sel, tm)
        flagged = det.flagged()
        # every perturbed node is flagged...
        assert set(perturbed) <= {e.nid for e in flagged}
        assert not det.plan_within_threshold()

        # ...and recalibration touches ONLY flagged entries
        hash_stale = profile.content_hash()
        updated = det.recalibrate(profile)
        assert set(updated) <= {e.profile_key for e in flagged}
        assert set(perturbed.values()) <= set(updated)

        # content hash rotation invalidates every cached plan key
        bkey = bucket_key(shape, 1)
        v_stale = CalibratedCostModel.__name__ + hash_stale
        v_fresh = CalibratedCostModel.__name__ + profile.content_hash()
        assert plan_key(net.fingerprint(), bkey, v_stale) != \
            plan_key(net.fingerprint(), bkey, v_fresh)

        # re-converge: the re-solved plan predicts within threshold
        post = recalibration_loop(net, params, x, profile,
                                  allowed=ALLOWED, threshold=threshold,
                                  runs=runs, max_rounds=4)
        assert post["converged"]
        assert post["detector"].plan_within_threshold()

    def test_calibrated_model_version_tracks_profile(self):
        from repro.calibrate.model import CalibratedCostModel
        from repro.calibrate.profile import HardwareProfile
        from repro.obs.drift import RestrictedCostModel

        profile = HardwareProfile.new()
        cm = CalibratedCostModel(profile)
        v0 = cm.version()
        profile.put("prim::direct_lax_chw_chw_oihw::whatever", 1e-3)
        assert CalibratedCostModel(profile).version() != v0
        r = RestrictedCostModel(CalibratedCostModel(profile), ALLOWED)
        assert "+allow=" in r.version()
