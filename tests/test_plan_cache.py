"""Plan-cache tests: hit/miss accounting, disk round-trip, invalidation."""
import json

import numpy as np
import pytest

from repro.core.costs import (
    CPU_SPEC, AnalyticCostModel, HardwareSpec, ProfiledCostModel,
)
from repro.core.selection import select_pbqp
from repro.serving import (
    LRU, PlanDiskCache, conv_tower, plan_key, selection_from_payload,
    selection_to_payload,
)

CM = AnalyticCostModel()


def _small_selection():
    net = conv_tower((4, 16, 16), depth=2, width=8)
    return net, select_pbqp(net, CM, exact=True)


class TestSerialization:
    def test_disk_round_trip(self, tmp_path):
        net, sel = _small_selection()
        cache = PlanDiskCache(tmp_path)
        key = plan_key(net.fingerprint(), "c4h16w16", CM.version())
        cache.put(key, selection_to_payload(sel))
        back = selection_from_payload(cache.get(key), net)
        assert back.predicted_cost == pytest.approx(sel.predicted_cost)
        assert back.optimal == sel.optimal
        assert back.strategy == sel.strategy
        assert set(back.choices) == set(sel.choices)
        for nid, ch in sel.choices.items():
            b = back.choices[nid]
            assert (ch.primitive.name if ch.primitive else None) == \
                (b.primitive.name if b.primitive else None)
            assert (ch.l_in, ch.l_out) == (b.l_in, b.l_out)
        assert back.conversions == sel.conversions

    def test_payload_is_json(self):
        _, sel = _small_selection()
        payload = selection_to_payload(sel)
        json.dumps(payload)  # must be pure-JSON serializable

    @pytest.mark.parametrize("mesh_axes,want_kinds", [
        ({"data": 2, "model": 4}, {"dp", "tp"}),
        ({"stage": 4}, {"pp"}),
    ])
    def test_structured_placements_round_trip(self, tmp_path, mesh_axes,
                                              want_kinds):
        """tp and pp<stage> placements survive the JSON disk tier as
        their canonical strings and come back as structured Placement
        instances (the PR's headline cache-round-trip criterion)."""
        from repro.core.selection import Placement
        from repro.serving.towers import bottleneck_tower, uniform_stack

        if "stage" in mesh_axes:
            net = uniform_stack((8, 8, 8), depth=6).with_batch(8)
        else:
            net = bottleneck_tower((4, 16, 16)).with_batch(8)
        sel = select_pbqp(net, CM, mesh_axes=mesh_axes)
        kinds = {Placement.parse(c.placement).kind
                 for c in sel.choices.values()}
        assert want_kinds <= kinds, kinds
        cache = PlanDiskCache(tmp_path)
        key = plan_key(net.fingerprint(), "b8", CM.version())
        cache.put(key, selection_to_payload(sel))
        # the disk tier is real JSON: force a serialize/parse cycle
        back = selection_from_payload(
            json.loads(json.dumps(cache.get(key))), net)
        assert back.predicted_cost == pytest.approx(sel.predicted_cost)
        for nid, ch in sel.choices.items():
            b = back.choices[nid]
            assert b.placement == ch.placement
            assert isinstance(b.placement, Placement)
            assert Placement.parse(b.placement).stage == \
                Placement.parse(ch.placement).stage

    def test_unknown_primitive_rejected(self):
        net, sel = _small_selection()
        payload = selection_to_payload(sel)
        nid = next(n for n, v in payload["choices"].items()
                   if v[0] is not None)
        payload["choices"][nid][0] = "no_such_primitive"
        with pytest.raises(KeyError):
            selection_from_payload(payload, net)

    def test_schema_mismatch_rejected(self):
        net, sel = _small_selection()
        payload = selection_to_payload(sel)
        payload["schema"] = -1
        with pytest.raises(ValueError):
            selection_from_payload(payload, net)


def _payload(**kw):
    """A schema-valid cache payload (get() treats others as corrupt)."""
    from repro.serving.plan_cache import PLAN_SCHEMA
    return {"schema": PLAN_SCHEMA, **kw}


class TestDiskCacheAccounting:
    def test_hit_miss_counters(self, tmp_path):
        cache = PlanDiskCache(tmp_path)
        assert cache.get("abc") is None
        assert (cache.hits, cache.misses) == (0, 1)
        cache.put("abc", _payload(x=1))
        assert cache.get("abc") == _payload(x=1)
        assert (cache.hits, cache.misses) == (1, 1)
        assert len(cache) == 1

    def test_corrupt_file_is_a_miss(self, tmp_path):
        cache = PlanDiskCache(tmp_path)
        cache.put("abc", _payload(x=1))
        (tmp_path / "plan_abc.json").write_text("{not json")
        assert cache.get("abc") is None
        assert cache.misses == 1
        assert cache.corrupt == 1
        assert not (tmp_path / "plan_abc.json").exists()  # deleted
        # and a subsequent put repairs the entry
        cache.put("abc", _payload(x=2))
        assert cache.get("abc") == _payload(x=2)

    def test_stale_schema_is_a_miss(self, tmp_path):
        cache = PlanDiskCache(tmp_path)
        cache.put("abc", {"schema": 1, "x": 1})   # ancient format
        assert cache.get("abc") is None
        assert cache.corrupt == 1

    def test_concurrent_puts_same_key(self, tmp_path):
        """Satellite fix: writers used to share one plan_<key>.tmp name,
        so concurrent puts of the same key could race a partial file
        into place or crash on each other's renamed tmp.  With
        per-writer tmp names every interleaving leaves a valid JSON
        payload from one of the writers and no tmp litter."""
        import threading

        cache = PlanDiskCache(tmp_path)
        errors = []

        def writer(i):
            try:
                for _ in range(50):
                    cache.put("shared",
                              _payload(writer=i, x=list(range(64))))
            except BaseException as e:  # noqa: BLE001 - record any crash
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        payload = cache.get("shared")
        assert payload is not None and payload["x"] == list(range(64))
        assert not list(tmp_path.glob("*.tmp"))  # no leftover tmp files


class TestKeyInvalidation:
    def test_cost_model_version_changes_key(self):
        """Bumping the cost model must invalidate persisted plans."""
        net, _ = _small_selection()
        fp, bk = net.fingerprint(), "c4h16w16"
        base = plan_key(fp, bk, AnalyticCostModel().version())
        other_spec = HardwareSpec(
            name=CPU_SPEC.name, peak_flops=CPU_SPEC.peak_flops * 2,
            mem_bw=CPU_SPEC.mem_bw, family_eff=dict(CPU_SPEC.family_eff))
        assert plan_key(fp, bk, AnalyticCostModel(other_spec).version()) \
            != base
        assert plan_key(fp, bk, ProfiledCostModel(
            cache_path="/tmp/x.json").version()) != base

    def test_version_is_stable(self):
        assert AnalyticCostModel().version() == \
            AnalyticCostModel().version()

    def test_net_fingerprint_tracks_shape_and_topology(self):
        a = conv_tower((4, 16, 16), depth=2, width=8)
        b = conv_tower((4, 16, 16), depth=2, width=8)
        assert a.fingerprint() == b.fingerprint()
        assert conv_tower((4, 32, 32), depth=2, width=8).fingerprint() \
            != a.fingerprint()
        assert conv_tower((4, 16, 16), depth=3, width=8).fingerprint() \
            != a.fingerprint()

    def test_bucket_changes_key(self):
        net, _ = _small_selection()
        v = CM.version()
        assert plan_key(net.fingerprint(), "c4h16w16", v) != \
            plan_key(net.fingerprint(), "c4h32w32", v)


class TestDeviceDefaults:
    """The analytic model and the Pallas interpret switch follow the
    device this process runs on, and refuse one they do not know."""

    @staticmethod
    def _on(monkeypatch, platform, kind):
        import types

        import jax
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])

    def test_cpu(self):
        from repro.kernels.common import use_interpret
        cm = AnalyticCostModel()
        assert cm.spec is CPU_SPEC and not cm.include_tpu_only
        assert use_interpret()

    def test_v5e(self, monkeypatch):
        from repro.core.costs import TPU_V5E_SPEC
        from repro.kernels.common import use_interpret
        self._on(monkeypatch, "tpu", "TPU v5 lite")
        cm = AnalyticCostModel()
        assert cm.spec is TPU_V5E_SPEC and cm.include_tpu_only
        assert not use_interpret()

    @pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9"),
                                               ("gpu", "H100")])
    def test_unknown_device_raises(self, monkeypatch, platform, kind):
        from repro.kernels.common import use_interpret
        self._on(monkeypatch, platform, kind)
        with pytest.raises(ValueError, match="no HardwareSpec"):
            AnalyticCostModel()
        if platform != "tpu":
            with pytest.raises(RuntimeError, match="Pallas"):
                use_interpret()


class TestLRU:
    def test_hit_miss_eviction(self):
        lru = LRU(2)
        assert lru.get("a") is None
        lru.put("a", 1)
        lru.put("b", 2)
        assert lru.get("a") == 1      # refreshes "a"
        lru.put("c", 3)               # evicts "b" (least recent)
        assert lru.get("b") is None
        assert lru.get("a") == 1 and lru.get("c") == 3
        assert lru.evictions == 1
        assert (lru.hits, lru.misses) == (3, 2)
        assert len(lru) == 2

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            LRU(0)
