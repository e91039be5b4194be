"""PlanServer round-trip and bucketing tests (acceptance criteria)."""
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.costs import AnalyticCostModel
from repro.serving import (
    BucketPolicy, PlanServer, bucket_key, bucket_shape, conv_tower,
)

CM = AnalyticCostModel()
POLICY = BucketPolicy(min_hw=8, max_hw=64)


def _server(tmp_path=None, **kw):
    kw.setdefault("policy", POLICY)
    kw.setdefault("lru_capacity", 4)
    return PlanServer(lambda s: conv_tower(s, depth=2, width=8), CM,
                      cache_dir=tmp_path, **kw)


class TestBucketing:
    def test_pow2_rounds_up(self):
        assert bucket_shape((3, 20, 20), POLICY) == (4, 32, 32)
        assert bucket_shape((4, 32, 32), POLICY) == (4, 32, 32)
        assert bucket_shape((5, 33, 17), POLICY) == (8, 64, 32)

    def test_clamps(self):
        assert bucket_shape((1, 2, 2), POLICY) == (1, 8, 8)
        # above the ceiling the request wins: round to the request, never crop
        assert bucket_shape((3, 100, 100), POLICY) == (4, 100, 100)

    def test_linear_mode(self):
        p = BucketPolicy(spatial="linear", channel="linear",
                         spatial_step=24, channel_step=4)
        assert bucket_shape((3, 25, 49), p) == (4, 48, 72)

    def test_exact_mode(self):
        p = BucketPolicy(spatial="exact", channel="exact")
        assert bucket_shape((3, 21, 37), p) == (3, 21, 37)

    def test_bad_shape_rejected(self):
        with pytest.raises(ValueError):
            bucket_shape((0, 4, 4), POLICY)
        with pytest.raises(ValueError):
            bucket_shape((3, 4), POLICY)  # type: ignore[arg-type]

    def test_bucket_key_stable(self):
        assert bucket_key((4, 32, 32)) == "c4h32w32"


class TestPlanServerRoundTrip:
    def test_same_bucket_one_solve_one_compile(self):
        """Acceptance: two requests in the same bucket trigger exactly one
        PBQP solve and one compile, asserted via counters."""
        srv = _server()
        x0 = plan_mod.xla_compile_stats()["xla_compiles"]
        srv.infer(np.random.default_rng(0)
                  .normal(size=(3, 20, 20)).astype(np.float32))
        x1 = srv.stats()["xla_compiles"]
        srv.infer(np.random.default_rng(1)
                  .normal(size=(3, 24, 28)).astype(np.float32))
        s = srv.stats()
        assert s["requests"] == 2
        assert s["solves"] == 1
        assert s["compiles"] == 1
        # the first request built the bucket's executable; the second,
        # an LRU hit, made XLA build nothing
        assert x1 > x0
        assert s["xla_compiles"] == x1
        assert s["exec_hits"] == 1 and s["exec_misses"] == 1
        assert s["buckets"] == 1
        srv.close()

    def test_output_shape_independent_of_request_shape_in_bucket(self):
        srv = _server()
        o1 = srv.infer(np.zeros((3, 20, 20), np.float32))
        o2 = srv.infer(np.zeros((3, 27, 31), np.float32))
        assert {k: v.shape for k, v in o1.items()} == \
            {k: v.shape for k, v in o2.items()}
        srv.close()

    def test_second_bucket_warm_starts(self):
        # 20 -> bucket (4,32,32); 40 -> bucket (4,64,64): same topology,
        # so the second solve is seeded by the first bucket's optimum
        srv = _server()
        srv.infer(np.zeros((3, 20, 20), np.float32))
        srv.infer(np.zeros((3, 40, 40), np.float32))
        s = srv.stats()
        assert s["solves"] == 2
        assert s["warm_solves"] == 1
        assert s["buckets"] == 2
        srv.close()

    def test_disk_persistence_across_servers(self, tmp_path):
        srv = _server(tmp_path)
        srv.infer(np.zeros((3, 20, 20), np.float32))
        assert srv.stats()["disk_plans"] == 1
        srv.close()
        # a new process-equivalent: fresh server, same cache dir
        srv2 = _server(tmp_path)
        srv2.infer(np.zeros((3, 18, 22), np.float32))  # same bucket
        s = srv2.stats()
        assert s["solves"] == 0
        assert s["plan_disk_hits"] == 1
        assert s["compiles"] == 1  # executables are not persistable
        srv2.close()

    def test_cost_version_bump_invalidates_disk(self, tmp_path):
        srv = _server(tmp_path)
        srv.infer(np.zeros((3, 20, 20), np.float32))
        srv.close()
        from repro.core.costs import TPU_V5E_SPEC
        srv2 = PlanServer(lambda s: conv_tower(s, depth=2, width=8),
                          AnalyticCostModel(TPU_V5E_SPEC),
                          policy=POLICY, cache_dir=tmp_path)
        srv2.plan_for((3, 20, 20))
        s = srv2.stats()
        assert s["plan_disk_hits"] == 0
        assert s["solves"] == 1  # re-solved under the new cost model
        srv2.close()

    def test_lru_eviction_recompiles_but_reuses_plan(self):
        srv = _server(lru_capacity=1)
        srv.infer(np.zeros((3, 16, 16), np.float32))
        srv.infer(np.zeros((3, 48, 48), np.float32))  # evicts bucket 1
        srv.infer(np.zeros((3, 16, 16), np.float32))  # recompile, plan hit
        s = srv.stats()
        assert s["exec_evictions"] >= 1
        assert s["compiles"] == 3
        assert s["solves"] == 2          # plans survived the eviction
        assert s["plan_mem_hits"] == 1
        srv.close()

    def test_prefetch_async(self):
        srv = _server()
        fut = srv.prefetch((3, 20, 20))
        cnet = fut.result(timeout=120)
        assert cnet is srv.compiled_for((3, 20, 20))  # now a hit
        s = srv.stats()
        assert s["solves"] == 1 and s["compiles"] == 1
        srv.close()

    def test_plan_predictions_are_finite_and_optimal(self):
        srv = _server()
        sel = srv.plan_for((3, 20, 20))
        assert np.isfinite(sel.predicted_cost)
        assert sel.optimal
        srv.close()


class TestServeLoopVisionBridge:
    def test_pixels_become_prompt_tokens(self):
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
            assert r.done and len(r.tokens) == 2
            assert r.pixels is None
            assert len(r.prompt) == 4 + 3  # vision tokens prepended
            assert np.all(r.prompt[:3] < cfg.vocab)
        s = srv.stats()
        assert s["requests"] == 2 and s["solves"] == 1 \
            and s["compiles"] == 1
        loop.close()
        srv.close()


class TestNearestPlan:
    """Warm-start source selection (PlanServer._nearest_plan)."""

    def test_empty_cache_returns_none(self):
        srv = _server()
        assert srv._nearest_plan((4, 32, 32, 1)) is None
        srv.close()

    def test_exact_hit_is_distance_zero(self):
        srv = _server()
        sel = srv.plan_for((3, 16, 16))           # bucket (4, 16, 16), n=1
        assert srv._nearest_plan((4, 16, 16, 1)) is sel
        srv.close()

    def test_picks_nearest_in_log_shape_space(self):
        srv = _server()
        near = srv.plan_for((3, 16, 16))          # (4, 16, 16, 1)
        far = srv.plan_for((3, 60, 60))           # (4, 64, 64, 1)
        assert near is not far
        # query (4, 16, 16, 2): distance 1 to `near` (batch axis only),
        # distance 5 to `far` (two spatial doublings x2 + batch)
        assert srv._nearest_plan((4, 16, 16, 2)) is near
        # and the batch axis is one more axis of the metric: a batched
        # query near the big bucket prefers the big bucket
        assert srv._nearest_plan((4, 64, 64, 2)) is far
        srv.close()


class TestConcurrencyStress:
    def test_mixed_paths_under_eviction_lose_nothing(self):
        """Threaded hammer across every request path while the LRU
        churns: every issued request resolves exactly once with the
        correct output, and the counters account for every request."""
        import threading

        srv = PlanServer(lambda s: conv_tower(s, depth=2, width=4), CM,
                         policy=POLICY, lru_capacity=2)
        rng = np.random.default_rng(7)
        shapes = [(3, 12, 12), (3, 16, 16), (3, 20, 20)]  # buckets 16, 32
        imgs = [rng.normal(size=s).astype(np.float32) for s in shapes]
        # references (and the nb=1 warm-up) before the storm
        refs = [srv.infer(x) for x in imgs]
        base_requests = len(imgs)

        issued = [0]
        results = []          # (img_idx, output_dict)
        errors = []
        lock = threading.Lock()

        def record(i, out):
            with lock:
                results.append((i, out))

        def worker(tid):
            trng = np.random.default_rng(100 + tid)
            ops = ["infer", "batch", "queue", "prefetch"] * 2
            trng.shuffle(ops)
            try:
                for op in ops:
                    i = int(trng.integers(len(imgs)))
                    j = int(trng.integers(len(imgs)))
                    if op == "infer":
                        with lock:
                            issued[0] += 1
                        record(i, srv.infer(imgs[i]))
                    elif op == "batch":
                        with lock:
                            issued[0] += 2
                        out = srv.infer_batch([imgs[i], imgs[j]])
                        record(i, out[0])
                        record(j, out[1])
                    elif op == "queue":
                        with lock:
                            issued[0] += 1
                        fut = srv.enqueue(imgs[i])
                        srv.flush()  # drains everyone's pending, not just ours
                        record(i, fut.result(timeout=120))
                    else:
                        srv.prefetch(shapes[i],
                                     n=2 if i % 2 else 1).result(timeout=120)
            except BaseException as exc:  # noqa: BLE001 — surface in main
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert not errors, errors

        # no lost or duplicated results: one output per issued request
        assert len(results) == issued[0]
        for i, out in results:
            for k in refs[i]:
                np.testing.assert_allclose(out[k], refs[i][k],
                                           rtol=2e-3, atol=2e-3)
        s = srv.stats()
        assert s["requests"] == issued[0] + base_requests
        # capacity 2 with >= 4 live (bucket, batch) specs must churn
        assert s["exec_evictions"] >= 1
        # the plan tier never evicts: recompiles reuse solved plans
        assert s["solves"] <= 2 * 2  # 2 spatial buckets x 2 batch buckets
        srv.close()
