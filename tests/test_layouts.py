"""Tests for layouts and the DT (data-layout transformation) graph."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.layouts import (
    ALL_LAYOUTS, CHW, HWC, HCW, HWC8, DTGraph, default_dt_graph,
)


class TestLayoutRoundTrip:
    @pytest.mark.parametrize("layout", ALL_LAYOUTS, ids=lambda l: l.name)
    def test_roundtrip(self, layout):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 5, 7)).astype(np.float32)
        mem = layout.to_memory(x)
        back = layout.from_memory(mem)
        np.testing.assert_array_equal(back, x)

    def test_hwc_memory_order(self):
        x = np.arange(2 * 3 * 4).reshape(2, 3, 4)
        mem = HWC.to_memory(x)
        assert mem.shape == (3, 4, 2)
        assert mem[1, 2, 0] == x[0, 1, 2]

    def test_blocked_layout_shape(self):
        x = np.zeros((16, 5, 7), np.float32)
        mem = HWC8.to_memory(x)
        assert mem.shape == (5, 7, 2, 8)

    def test_blocked_layout_requires_divisible(self):
        with pytest.raises(ValueError):
            HWC8.to_memory(np.zeros((10, 5, 7), np.float32))


class TestConvertLayoutRoundTrip:
    """The traced (jnp) layout converter: a->b->a is the identity for
    every ordered pair in ALL_LAYOUTS (blocked HWC8 included)."""

    PAIRS = [(a.name, b.name) for a in ALL_LAYOUTS for b in ALL_LAYOUTS]

    @pytest.mark.parametrize("src,dst", PAIRS,
                             ids=[f"{a}->{b}" for a, b in PAIRS])
    def test_roundtrip_identity(self, src, dst):
        from repro.core.layouts import LAYOUT_BY_NAME
        from repro.core.primitives import convert_layout
        rng = np.random.default_rng(1)
        x = rng.normal(size=(16, 5, 7)).astype(np.float32)  # C % 8 == 0
        mem = LAYOUT_BY_NAME[src].to_memory(x)
        back = convert_layout(convert_layout(mem, src, dst), dst, src)
        np.testing.assert_allclose(np.asarray(back), mem, rtol=0, atol=0)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 3), st.integers(1, 9), st.integers(1, 9))
    def test_roundtrip_identity_any_shape(self, cb, h, w):
        """Random shapes (C a multiple of 8 so HWC8 legs stay legal)."""
        from repro.core.primitives import convert_layout
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8 * cb, h, w)).astype(np.float32)
        for a in ALL_LAYOUTS:
            for b in ALL_LAYOUTS:
                mem = a.to_memory(x)
                back = convert_layout(convert_layout(mem, a.name, b.name),
                                      b.name, a.name)
                np.testing.assert_allclose(np.asarray(back), mem,
                                           rtol=0, atol=0)

    def test_convert_matches_reference(self):
        """convert_layout(a->b) == from_memory/to_memory composition."""
        from repro.core.primitives import convert_layout
        rng = np.random.default_rng(2)
        x = rng.normal(size=(16, 6, 9)).astype(np.float32)
        for a in ALL_LAYOUTS:
            for b in ALL_LAYOUTS:
                got = convert_layout(a.to_memory(x), a.name, b.name)
                np.testing.assert_allclose(np.asarray(got), b.to_memory(x),
                                           rtol=0, atol=0)

    def test_chw_hwc_pallas_pad_crop(self):
        """The tiled CHW<->HWC kernels agree with the layout reference at
        spatial extents that force padding + cropping."""
        from repro.kernels.layout_transform import chw_to_hwc, hwc_to_chw
        rng = np.random.default_rng(3)
        x = rng.normal(size=(16, 11, 13)).astype(np.float32)  # odd H/W
        mem = np.asarray(chw_to_hwc(x))
        np.testing.assert_allclose(mem, HWC.to_memory(x), rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(hwc_to_chw(mem)), x,
                                   rtol=0, atol=0)


class TestDTGraph:
    def test_direct_edge_cost(self):
        g = default_dt_graph()
        d, idx = g.cost_matrix((64, 32, 32))
        assert d[idx["CHW"], idx["HWC"]] > 0
        assert np.isfinite(d[idx["CHW"], idx["HWC"]])
        assert d[idx["CHW"], idx["CHW"]] == 0

    def test_chain_required(self):
        """HWC -> HCW has no direct routine: must chain via CHW."""
        g = default_dt_graph()
        chain = g.shortest_chain("HWC", "HCW", (64, 32, 32))
        assert chain is not None
        assert chain[0] == "HWC" and chain[-1] == "HCW"
        assert len(chain) >= 3  # at least one intermediate hop
        d, idx = g.cost_matrix((64, 32, 32))
        # chain cost equals sum of its direct hops
        hop_cost = sum(
            d[idx[a], idx[b]] for a, b in zip(chain, chain[1:]))
        assert d[idx["HWC"], idx["HCW"]] == pytest.approx(hop_cost)

    def test_unreachable_is_infinite(self):
        g = DTGraph()
        g.add_transform("A", "B", lambda s, d: 1.0)
        g.add_layout("Z")
        d, idx = g.cost_matrix((4, 4, 4))
        assert np.isinf(d[idx["A"], idx["Z"]])
        assert g.shortest_chain("A", "Z", (4, 4, 4)) is None

    def test_one_way_transform(self):
        g = DTGraph()
        g.add_transform("A", "B", lambda s, d: 1.0)
        d, idx = g.cost_matrix((4, 4, 4))
        assert np.isfinite(d[idx["A"], idx["B"]])
        assert np.isinf(d[idx["B"], idx["A"]])

    @settings(max_examples=30, deadline=None)
    @given(st.lists(
        st.tuples(st.integers(0, 5), st.integers(0, 5), st.floats(0.1, 10)),
        min_size=1, max_size=20))
    def test_apsp_triangle_inequality(self, edges):
        g = DTGraph()
        for i in range(6):
            g.add_layout(f"L{i}")
        for s, t, c in edges:
            if s != t:
                g.add_transform(f"L{s}", f"L{t}", lambda sh, dt, c=c: c)
        d, idx = g.cost_matrix((4, 4, 4))
        n = len(g.layouts)
        for i in range(n):
            for j in range(n):
                for k in range(n):
                    assert d[i, j] <= d[i, k] + d[k, j] + 1e-9

    @settings(max_examples=20, deadline=None)
    @given(st.lists(
        st.tuples(st.integers(0, 4), st.integers(0, 4), st.floats(0.1, 10)),
        min_size=1, max_size=12))
    def test_chain_realises_apsp_cost(self, edges):
        g = DTGraph()
        for i in range(5):
            g.add_layout(f"L{i}")
        costs = {}
        for s, t, c in edges:
            if s != t and (s, t) not in costs:
                costs[(s, t)] = c
                g.add_transform(f"L{s}", f"L{t}", lambda sh, dt, c=c: c)
        d, idx = g.cost_matrix((4, 4, 4))
        for i in range(5):
            for j in range(5):
                chain = g.shortest_chain(f"L{i}", f"L{j}", (4, 4, 4))
                if np.isinf(d[idx[f"L{i}"], idx[f"L{j}"]]):
                    assert chain is None or i == j
                else:
                    assert chain is not None
                    tot = sum(costs.get((int(a[1]), int(b[1])), np.inf)
                              for a, b in zip(chain, chain[1:]))
                    assert tot == pytest.approx(
                        d[idx[f"L{i}"], idx[f"L{j}"]], rel=1e-9)
