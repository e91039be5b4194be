"""Batch-aware selection and batched serving (PR acceptance criteria).

Covers the batch axis end to end: Scenario.n and key back-compat,
batched analytic pricing (weight/setup amortization), the N=1 -> N=8
selection flip, batch bucketing, batched executables, the
PlanServer.infer_batch path with its one-solve-one-compile-per-
(bucket, batch) property (the CI smoke job runs this file), output
cropping back to request extent, the micro-batching admission queue,
and the serve loop coalescing a tick's images into one invocation.
"""
import numpy as np
import pytest

from repro.core.costs import AnalyticCostModel
from repro.core.plan import compile_plan
from repro.core.scenario import Scenario
from repro.core.selection import select_pbqp
from repro.serving import (
    BucketPolicy, PlanServer, bucket_key, bucket_scenario, conv_stack,
    conv_tower,
)

CM = AnalyticCostModel()
POLICY = BucketPolicy(min_hw=8, max_hw=64)
SCN = Scenario(c=8, h=16, w=16, stride=1, k=3, m=16)


class TestScenarioBatch:
    def test_default_batch_is_paper_setting(self):
        assert SCN.n == 1

    def test_key_backward_compatible(self):
        """n=1 keys must not change: calibration profiles and persisted
        plans from before the batch axis stay valid."""
        assert SCN.key() == "c8h16w16s1k3m16p1float32"
        assert SCN.with_(n=1).key() == SCN.key()
        assert SCN.with_(n=8).key() == SCN.key() + "n8"

    def test_macs_scale_with_batch(self):
        assert SCN.with_(n=4).macs == 4 * SCN.macs
        assert SCN.with_(n=4).in_shape_nchw == (4, 8, 16, 16)

    def test_bad_batch_rejected(self):
        with pytest.raises(ValueError):
            SCN.with_(n=0)


class TestBatchedCosts:
    def test_batched_cost_amortizes_per_invocation_work(self):
        """Per-image cost strictly falls with N for every family: the
        per-invocation setup (and weight traffic) amortizes."""
        from repro.core.primitives import primitives_for
        for prim in primitives_for(SCN):
            c1 = CM.primitive_cost(prim, SCN)
            c8 = CM.primitive_cost(prim, SCN.with_(n=8))
            if not np.isfinite(c1):
                continue
            assert c8 > c1          # a batch costs more in total...
            assert c8 / 8 < c1      # ...but less per image

    def test_with_batch_is_copy_on_write(self):
        # a memoizing net_builder may hand the server one shared Net
        # per shape: with_batch must never mutate it (cached plans
        # reference it), and with_batch(n) at the current n is free
        net = conv_tower((4, 32, 32), depth=2, width=8)
        fp1 = net.fingerprint()
        assert net.with_batch(1) is net
        net8 = net.with_batch(8)
        assert net8 is not net
        assert all(nd.scn.n == 8 for nd in net8.conv_nodes())
        assert all(nd.scn.n == 1 for nd in net.conv_nodes())
        assert net.fingerprint() == fp1 != net8.fingerprint()
        assert net8.order == net.order  # ids line up for warm starts
        assert net8.with_batch(1).fingerprint() == fp1

    def test_selection_flips_with_batch(self):
        """ACCEPTANCE: select_pbqp picks a different primitive for at
        least one tower node when N goes 1 -> 8 (analytic model)."""
        picks = {}
        for n in (1, 8):
            net = conv_tower((4, 32, 32), depth=2, width=8).with_batch(n)
            sel = select_pbqp(net, CM)
            assert sel.optimal
            picks[n] = {nd.id: sel.choices[nd.id].primitive.name
                        for nd in net.conv_nodes()}
        assert picks[1] != picks[8], picks

    def test_version_tracks_setup_constants(self):
        from repro.core.costs import CPU_SPEC, HardwareSpec
        spec = HardwareSpec(
            name=CPU_SPEC.name, peak_flops=CPU_SPEC.peak_flops,
            mem_bw=CPU_SPEC.mem_bw, family_eff=dict(CPU_SPEC.family_eff),
            family_setup={**CPU_SPEC.family_setup, "im2": 1.0})
        assert AnalyticCostModel(spec).version() != CM.version()


class TestBatchBucketing:
    def test_bucket_n_pow2(self):
        assert POLICY.bucket_n(1) == 1
        assert POLICY.bucket_n(3) == 4
        assert POLICY.bucket_n(8) == 8
        assert POLICY.bucket_n(9) == 16

    def test_bucket_n_never_rounds_down(self):
        # like the spatial axes: above the ceiling the request wins —
        # clamping down would price/compile a smaller batch than runs
        p = BucketPolicy(max_n=8)
        assert p.bucket_n(6) == 8
        assert p.bucket_n(100) == 100
        with pytest.raises(ValueError):
            p.bucket_n(0)

    def test_bucket_key_batch_suffix(self):
        assert bucket_key((4, 32, 32)) == "c4h32w32"
        assert bucket_key((4, 32, 32), 1) == "c4h32w32"
        assert bucket_key((4, 32, 32), 8) == "c4h32w32n8"

    def test_bucket_scenario_buckets_batch(self):
        b = bucket_scenario(SCN.with_(n=3), POLICY)
        assert b.n == 4
        assert bucket_scenario(b, POLICY) == b


class TestBatchedCompile:
    def test_batched_executable_matches_per_image_runs(self):
        net = conv_stack((4, 16, 16), depth=2, width=8)
        sel = select_pbqp(net, CM)
        params = net.init_params(0)
        single = compile_plan(sel, params)
        batched = compile_plan(sel, params, batch=4)
        assert batched.batch == 4
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(4, 4, 16, 16)).astype(np.float32)
        outb = batched(xs)
        for i in range(4):
            o1 = single(xs[i])
            for k in o1:
                np.testing.assert_allclose(
                    np.asarray(outb[k][i]), np.asarray(o1[k]),
                    rtol=2e-3, atol=2e-3)

    def test_bad_batch_rejected(self):
        net = conv_stack((4, 16, 16), depth=1, width=8)
        sel = select_pbqp(net, CM)
        with pytest.raises(ValueError):
            compile_plan(sel, net.init_params(0), batch=0)


def _server(builder=None, **kw):
    kw.setdefault("policy", POLICY)
    kw.setdefault("lru_capacity", 8)
    builder = builder or (lambda s: conv_tower(s, depth=2, width=8))
    return PlanServer(builder, CM, **kw)


class TestInferBatch:
    def test_one_solve_one_compile_per_bucket_and_batch(self):
        """CI smoke property: N in {1, 4} over one spatial bucket costs
        exactly one solve + one compile per (net, bucket, batch-bucket),
        asserted via ServingCounters."""
        srv = _server()
        rng = np.random.default_rng(0)
        xs = [rng.normal(size=(3, 20 + i, 20)).astype(np.float32)
              for i in range(4)]                  # one bucket, nb=4
        outs = srv.infer_batch(xs)
        assert len(outs) == 4
        srv.infer(xs[0])                          # same bucket, nb=1
        s = srv.stats()
        assert s["requests"] == 5
        assert s["solves"] == 2                   # (bucket, 4), (bucket, 1)
        assert s["compiles"] == 2
        assert s["batch_calls"] == 1
        assert s["coalesced"] == 3
        # a second batched wave is pure execution
        srv.infer_batch(xs)
        s = srv.stats()
        assert s["solves"] == 2 and s["compiles"] == 2
        assert s["exec_hits"] >= 1
        srv.close()

    def test_batched_outputs_match_sequential(self):
        srv = _server(lambda s: conv_stack(s, depth=2, width=8))
        rng = np.random.default_rng(1)
        xs = [rng.normal(size=(4, int(rng.integers(10, 30)),
                               int(rng.integers(10, 30))))
              .astype(np.float32) for _ in range(6)]  # mixed buckets
        seq = [srv.infer(x) for x in xs]
        bat = srv.infer_batch(xs)
        for i in range(len(xs)):
            assert set(seq[i]) == set(bat[i])
            for k in seq[i]:
                assert seq[i][k].shape == bat[i][k].shape
                np.testing.assert_allclose(bat[i][k], seq[i][k],
                                           rtol=2e-3, atol=2e-3)
        srv.close()

    def test_groups_larger_than_max_n_are_chunked(self):
        srv = _server(policy=BucketPolicy(min_hw=8, max_hw=64, max_n=4))
        xs = [np.zeros((3, 20, 20), np.float32)] * 6
        outs = srv.infer_batch(xs)
        assert len(outs) == 6
        s = srv.stats()
        assert s["batch_calls"] == 2              # nb=4 chunk + nb=2 chunk
        assert s["coalesced"] == 4                # 3 in chunk 1, 1 in chunk 2
        assert s["solves"] == 2 and s["compiles"] == 2
        srv.close()

    def test_infer_works_when_batch_bucket_of_one_exceeds_one(self):
        """Regression: a policy whose batch bucket for n=1 is > 1
        (linear batch mode) hands infer a batched executable; the image
        must ride row 0, not crash the vmapped program."""
        srv = _server(lambda s: conv_stack(s, depth=1, width=8),
                      policy=BucketPolicy(min_hw=8, max_hw=64,
                                          batch="linear", batch_step=4))
        rng = np.random.default_rng(4)
        x = rng.normal(size=(4, 16, 16)).astype(np.float32)
        out = srv.infer(x)
        (v,) = out.values()
        assert v.shape == (8, 16, 16)
        # row-0 embedding matches the batched path's answer
        ref = srv.infer_batch([x])[0]
        for k in ref:
            np.testing.assert_allclose(out[k], ref[k], rtol=2e-3,
                                       atol=2e-3)
        srv.close()

    def test_empty_batch(self):
        srv = _server()
        assert srv.infer_batch([]) == []
        srv.close()


class TestStagedInput:
    """Each call's input is written once, packed (``packed_shape``), into
    a staging buffer the server keeps for the next call."""

    @pytest.mark.parametrize("chw", [(3, 32, 32), (3, 30, 30)])
    @pytest.mark.parametrize("batch", [1, 4])
    def test_packed_entry_is_the_walker_bit_for_bit(self, chw, batch):
        """3x32x32 fills its packed rows; 3x30x30 leaves a zero tail."""
        from repro.core.plan import packed_shape, unpack_images
        net = conv_stack(chw, depth=2, width=8).with_batch(batch)
        cnet = compile_plan(select_pbqp(net, CM), net.init_params(0),
                            batch=batch)
        assert cnet.in_shape == packed_shape(chw, batch) == \
            ((24, 128) if batch == 1 else (batch, 24, 128))
        x = np.random.default_rng(5).normal(
            size=chw if batch == 1 else (batch, *chw)).astype(np.float32)
        xin = cnet.pack(x)
        view = unpack_images(xin, chw, batch)
        assert np.shares_memory(view, xin)
        np.testing.assert_array_equal(view, x)
        assert not xin.reshape(batch, -1)[:, int(np.prod(chw)):].any()
        want = cnet.fn(x, cnet.params)
        for got in (cnet(x), cnet(xin)):
            assert set(got) == set(want)
            for k in want:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))

    @staticmethod
    def _inputs(monkeypatch):
        """Every input the executables are called with, copied."""
        from repro.core.plan import CompiledNet
        seen = []
        sound = CompiledNet.__call__

        def spy(self, x):
            seen.append((self, np.array(x)))
            return sound(self, x)
        monkeypatch.setattr(CompiledNet, "__call__", spy)
        return seen

    @staticmethod
    def _assert_same(got, want):
        for g, w in zip(got, want, strict=True):
            assert set(g) == set(w)
            for k in w:
                np.testing.assert_array_equal(g[k], w[k])

    def test_partial_batch_after_a_full_one_reads_zero_rows(
            self, monkeypatch):
        builder = lambda s: conv_stack(s, depth=2, width=8)
        rng = np.random.default_rng(6)
        full = [rng.normal(size=(4, 16, 16)).astype(np.float32)
                for _ in range(4)]
        part = [rng.normal(size=(4, 16, 16)).astype(np.float32)
                for _ in range(3)]
        srv = _server(builder)
        fresh = _server(builder)
        seen = self._inputs(monkeypatch)
        try:
            srv.infer_batch(full)
            got = srv.infer_batch(part)       # batch bucket 4, one row free
            assert srv.stats()["staging_hits"] == 1
            cnet, xin = seen[-1]
            rows = cnet.images(xin)
            np.testing.assert_array_equal(rows[:3], np.stack(part))
            assert not rows[3].any()
            assert not xin.reshape(4, -1)[:, rows[0].size:].any()
            self._assert_same(got, fresh.infer_batch(part))
        finally:
            srv.close()
            fresh.close()

    def test_smaller_image_in_a_pow2_bucket_reads_zero_padding(
            self, monkeypatch):
        builder = lambda s: conv_stack(s, depth=2, width=8)
        rng = np.random.default_rng(7)
        big = rng.normal(size=(3, 30, 30)).astype(np.float32)
        small = rng.normal(size=(3, 20, 17)).astype(np.float32)
        srv = _server(builder)
        fresh = _server(builder)
        seen = self._inputs(monkeypatch)
        try:
            assert srv.policy.bucket(big.shape) == \
                srv.policy.bucket(small.shape) == (4, 32, 32)
            srv.infer(big)
            got = srv.infer(small)
            assert srv.stats()["staging_hits"] == 1
            cnet, xin = seen[-1]
            img = cnet.images(xin)
            np.testing.assert_array_equal(img[:3, :20, :17], small)
            img[:3, :20, :17] = 0
            assert not xin.any()
            self._assert_same([got], [fresh.infer(small)])
        finally:
            srv.close()
            fresh.close()

    def test_a_new_buffer_is_written_whole(self, monkeypatch):
        """A new buffer holds whatever the allocator left: every value
        no image fills is written zero, the tail past C*H*W of each
        packed row (3x30x30 fills 2700 of 3072) included."""
        real = np.empty

        def dirty(shape, dtype=float, *a, **k):
            out = real(shape, dtype, *a, **k)
            if out.dtype == np.float32:
                out.fill(np.nan)
            return out
        monkeypatch.setattr(np, "empty", dirty)
        seen = self._inputs(monkeypatch)
        srv = _server(lambda s: conv_stack(s, depth=1, width=8),
                      policy=BucketPolicy(spatial="exact", channel="exact",
                                          min_hw=8, max_hw=64))
        xs = [np.full((3, 30, 30), i + 1.0, np.float32) for i in range(3)]
        try:
            srv.infer_batch(xs)               # batch bucket 4
        finally:
            srv.close()
        cnet, xin = seen[-1]
        assert xin.shape == (4, 24, 128)
        rows = cnet.images(xin)
        np.testing.assert_array_equal(rows[:3], np.stack(xs))
        rows[:3] = 0
        assert not xin.any()

    @pytest.mark.parametrize("threads", [2, 12])
    def test_threads_share_a_bucket(self, threads):
        """Concurrent callers of one bucket get one thread's answers: no
        buffer is written by one call while another reads it.  A server
        whose pool has a worker per caller allocates at most one buffer
        per caller."""
        import sys
        import threading
        rng = np.random.default_rng(8)
        waves = [[rng.normal(size=(3, 16, 16)).astype(np.float32)
                  for _ in range(4)] for _ in range(4 * threads)]
        one = _server()
        srv = _server(max_workers=threads)
        switch = sys.getswitchinterval()
        try:
            want = [one.infer_batch(w) for w in waves]
            srv.infer_batch(waves[0])         # solve + compile
            got = [None] * len(waves)

            def client(k):
                for i in range(k, len(waves), threads):
                    got[i] = srv.infer_batch(waves[i])
            ts = [threading.Thread(target=client, args=(k,))
                  for k in range(threads)]
            sys.setswitchinterval(1e-5)
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(switch)
            one.close()
            srv.close()
        for g, w in zip(got, want, strict=True):
            self._assert_same(g, w)
        s = srv.stats()
        assert s["staging_misses"] <= threads
        assert s["staging_hits"] + s["staging_misses"] == len(waves) + 1

    def test_staging_counters(self):
        srv = _server()
        x = np.zeros((3, 16, 16), np.float32)
        try:
            for _ in range(3):
                srv.infer_batch([x] * 4)
            for _ in range(2):
                srv.infer(x)
            s = srv.stats()
            # one buffer per executable input shape, reused after
            assert (s["staging_misses"], s["staging_hits"]) == (2, 3)
            text = srv.metrics_text()
        finally:
            srv.close()
        assert "staging_hits" in text and "staging_misses" in text


class TestOutputCropping:
    def test_infer_crops_to_request_extent(self):
        """Bucketed output slices match an exact run on the unpadded
        shape (satellite fix: infer used to return bucket-shaped
        outputs, leaking padding)."""
        builder = lambda s: conv_stack(s, depth=1, width=8)
        srv = _server(builder)
        rng = np.random.default_rng(2)
        x = rng.normal(size=(4, 20, 20)).astype(np.float32)  # bucket 32x32
        out = srv.infer(x)
        # reference: the same net compiled at the request's own shape
        # (identical weights: conv params depend only on C, K, M)
        net = builder((4, 20, 20))
        ref = compile_plan(select_pbqp(net, CM),
                           net.init_params(srv.params_seed))(x)
        for nid, v in ref.items():
            assert out[nid].shape == np.asarray(v).shape == (8, 20, 20)
            np.testing.assert_allclose(out[nid], np.asarray(v),
                                       rtol=2e-3, atol=2e-3)
        srv.close()

    def test_deep_stack_crops_shape_and_interior(self):
        """Depth >= 2: the crop restores the request's shape, and the
        interior matches the exact run.  Border columns of deep layers
        legitimately see bucket padding (conv bias makes the padded
        region nonzero after layer 1) — pad-and-crop bucketing trades
        exact borders for executable reuse, like any padded batching."""
        builder = lambda s: conv_stack(s, depth=2, width=8)
        srv = _server(builder)
        rng = np.random.default_rng(2)
        x = rng.normal(size=(4, 20, 20)).astype(np.float32)
        out = srv.infer(x)
        net = builder((4, 20, 20))
        ref = compile_plan(select_pbqp(net, CM),
                           net.init_params(srv.params_seed))(x)
        for nid, v in ref.items():
            v = np.asarray(v)
            assert out[nid].shape == v.shape == (16, 20, 20)
            np.testing.assert_allclose(out[nid][:, 1:-1, 1:-1],
                                       v[:, 1:-1, 1:-1],
                                       rtol=2e-3, atol=2e-3)
        srv.close()

    def test_exact_bucket_request_is_untouched(self):
        srv = _server(lambda s: conv_stack(s, depth=1, width=8))
        x = np.zeros((4, 32, 32), np.float32)     # already a bucket shape
        out = srv.infer(x)
        (v,) = out.values()
        assert v.shape == (8, 32, 32)
        srv.close()

    def test_global_outputs_pass_through(self):
        # conv_tower ends in GAP+FC: output shape is request-independent
        srv = _server()
        o1 = srv.infer(np.zeros((3, 20, 20), np.float32))
        o2 = srv.infer(np.zeros((3, 27, 31), np.float32))
        assert {k: v.shape for k, v in o1.items()} == \
            {k: v.shape for k, v in o2.items()}
        srv.close()


class TestMicroBatchQueue:
    def test_flush_coalesces_same_bucket(self):
        srv = _server(lambda s: conv_stack(s, depth=1, width=8))
        rng = np.random.default_rng(3)
        xs = [rng.normal(size=(4, 18, 18)).astype(np.float32)
              for _ in range(3)]
        futs = [srv.enqueue(x) for x in xs]
        assert srv.flush() == 3
        s = srv.stats()
        assert s["batch_calls"] == 1 and s["requests"] == 3
        for x, fut in zip(xs, futs):
            out = fut.result(timeout=60)
            ref = srv.infer(x)
            for k in ref:
                np.testing.assert_allclose(out[k], ref[k],
                                           rtol=2e-3, atol=2e-3)
        srv.close()

    def test_flush_empty_queue(self):
        srv = _server()
        assert srv.flush() == 0
        srv.close()

    def test_close_cancels_unflushed_futures(self):
        # a waiter on an enqueued-but-never-flushed future must not
        # hang when the server shuts down
        from concurrent.futures import CancelledError
        srv = _server()
        fut = srv.enqueue(np.zeros((3, 16, 16), np.float32))
        srv.close()
        assert fut.cancelled()
        with pytest.raises(CancelledError):
            fut.result(timeout=1)
        # and late producers fail loudly instead of queueing forever
        with pytest.raises(RuntimeError, match="closed"):
            srv.enqueue(np.zeros((3, 16, 16), np.float32))


class TestServeLoopCoalescing:
    def test_tick_images_share_one_invocation(self):
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import init_params
        from repro.runtime import Request, ServeLoop

        cfg = get_config("tinyllama-1.1b").scaled_down(
            n_layers=2, d_model=64, d_ff=128, vocab=256)
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        srv = _server()
        loop = ServeLoop(cfg, params, max_batch=2, max_seq=64,
                         plan_server=srv, image_tokens=3)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=2,
                        pixels=rng.normal(size=(3, 18, 18))
                        .astype(np.float32))
                for i in range(2)]
        loop.run(reqs)
        for r in reqs:
            assert r.done and r.pixels is None
            assert len(r.prompt) == 4 + 3
        s = srv.stats()
        # both images admitted in tick 1: ONE batched tower invocation
        assert s["batch_calls"] == 1
        assert s["requests"] == 2
        assert s["coalesced"] == 1
        assert s["solves"] == 1 and s["compiles"] == 1
        loop.close()
        srv.close()
