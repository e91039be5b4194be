"""Unit + property tests for the PBQP solver (the paper's core engine)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import pbqp
from repro.core.pbqp import PBQP, Infeasible, brute_force, solve


def _paper_example() -> PBQP:
    """The linear conv1-conv2-conv3 example of Figure 2 of the paper.

    Three primitives A/B/C per node; edge costs model data layout
    transformations (0 on the diagonal = same layout).
    """
    pb = PBQP()
    pb.add_node("conv1", [10.0, 4.0, 8.0])   # A, B, C
    pb.add_node("conv2", [20.0, 12.0, 3.0])
    pb.add_node("conv3", [12.0, 5.0, 7.0])
    # large off-diagonal transition costs: switching layouts is expensive
    T = np.array([
        [0.0, 9.0, 30.0],
        [9.0, 0.0, 30.0],
        [30.0, 30.0, 0.0],
    ])
    pb.add_edge("conv1", "conv2", T)
    pb.add_edge("conv2", "conv3", T)
    return pb


class TestBasics:
    def test_single_node(self):
        pb = PBQP()
        pb.add_node("a", [3.0, 1.0, 2.0])
        sol = solve(pb)
        assert sol.cost == 1.0
        assert sol.assignment == {"a": 1}
        assert sol.optimal

    def test_paper_figure2(self):
        pb = _paper_example()
        sol = solve(pb)
        bf = brute_force(pb)
        assert sol.cost == pytest.approx(bf.cost)
        # The paper's point: conv2's huge win with C drags conv1/conv3 to
        # co-adapt; naive per-node minima (B, C, B) cost 4+3+5+60 = 72,
        # the optimum is strictly cheaper.
        naive = pb.evaluate({"conv1": 1, "conv2": 2, "conv3": 1})
        assert sol.cost < naive

    def test_infeasible(self):
        pb = PBQP()
        pb.add_node("a", [1.0, 2.0])
        pb.add_node("b", [1.0, 2.0])
        pb.add_edge("a", "b", np.full((2, 2), np.inf))
        with pytest.raises(Infeasible):
            solve(pb)

    def test_infinite_edges_route_around(self):
        # a--b--c chain; a=0 forces b=1 (a0-b0 illegal), then b=1 makes
        # c's best become index 0 despite c preferring 1 locally.
        pb = PBQP()
        pb.add_node("a", [0.0, 100.0])
        pb.add_node("b", [5.0, 6.0])
        pb.add_node("c", [10.0, 0.0])
        pb.add_edge("a", "b", np.array([[np.inf, 0.0], [0.0, 0.0]]))
        pb.add_edge("b", "c", np.array([[0.0, 0.0], [0.0, np.inf]]))
        sol = solve(pb)
        assert sol.assignment == {"a": 0, "b": 1, "c": 0}
        assert sol.cost == pytest.approx(0 + 6 + 10)

    def test_parallel_edges_sum(self):
        pb = PBQP()
        pb.add_node("a", [0.0, 0.0])
        pb.add_node("b", [0.0, 0.0])
        M = np.array([[1.0, 2.0], [3.0, 4.0]])
        pb.add_edge("a", "b", M)
        pb.add_edge("b", "a", M.T)  # same edge again, reversed orientation
        sol = solve(pb)
        assert sol.cost == pytest.approx(2.0)

    def test_self_loop_folds_to_diagonal(self):
        pb = PBQP()
        pb.add_node("a", [0.0, 0.0])
        pb.add_edge("a", "a", np.array([[5.0, 99.0], [99.0, 1.0]]))
        sol = solve(pb)
        assert sol.cost == pytest.approx(1.0)
        assert sol.assignment["a"] == 1

    def test_edge_unknown_node_rejected(self):
        pb = PBQP()
        pb.add_node("a", [0.0, 0.0])
        with pytest.raises(ValueError, match="unknown node"):
            pb.add_edge("a", "ghost", np.zeros((2, 2)))
        with pytest.raises(ValueError, match="unknown node"):
            pb.add_edge("ghost", "a", np.zeros((2, 2)))
        # the self-loop path used to KeyError instead of this ValueError
        with pytest.raises(ValueError, match="unknown node"):
            pb.add_edge("ghost", "ghost", np.zeros((2, 2)))

    def test_self_loop_shape_validated(self):
        pb = PBQP()
        pb.add_node("a", [0.0, 0.0])
        with pytest.raises(ValueError, match="incompatible"):
            pb.add_edge("a", "a", np.zeros((3, 3)))
        with pytest.raises(ValueError, match="incompatible"):
            pb.add_edge("a", "a", np.zeros((2, 3)))

    def test_fully_infeasible_degree3_raises(self):
        """Regression: a fully-infeasible instance whose nodes all have
        degree >= 3 enters branch-and-bound with every branch infinite;
        the fallback must leave a *total* assignment behind and raise
        Infeasible (never KeyError out of pb.evaluate)."""
        def build():
            pb = PBQP()
            for i in range(4):
                pb.add_node(i, [1.0, 2.0])
            for i in range(4):
                for j in range(i + 1, 4):
                    pb.add_edge(i, j, np.full((2, 2), np.inf))
            return pb

        with pytest.raises(Infeasible):
            solve(build(), exact=True)
        # warm-started path: the (infinite-cost) warm assignment must
        # disable the bound and still end in Infeasible
        with pytest.raises(Infeasible):
            pbqp.solve_warm(build(), {i: 0 for i in range(4)}, exact=True)
        # branch node with an all-infinite cost vector, feasible-looking
        # edges: same contract
        pb = build()
        pb.add_node("u", [np.inf, np.inf])
        for i in range(4):
            pb.add_edge("u", i, np.zeros((2, 2)))
        with pytest.raises(Infeasible):
            solve(pb, exact=True)

    def test_dag_diamond(self):
        """Inception-style diamond (Figure 3): split + join."""
        pb = PBQP()
        for n in ["pre", "b1", "b2", "post"]:
            pb.add_node(n, [1.0, 1.0, 1.0])
        T = np.where(np.eye(3), 0.0, 50.0)
        pb.add_edge("pre", "b1", T)
        pb.add_edge("pre", "b2", T)
        pb.add_edge("b1", "post", T)
        pb.add_edge("b2", "post", T)
        sol = solve(pb)
        # all four nodes must agree on one layout
        vals = set(sol.assignment.values())
        assert len(vals) == 1
        assert sol.cost == pytest.approx(4.0)


# ----------------------------------------------------------------------
# random instances vs brute force
# ----------------------------------------------------------------------
def _random_instance(draw) -> PBQP:
    n = draw(st.integers(2, 6))
    pb = PBQP()
    doms = []
    for i in range(n):
        k = draw(st.integers(1, 4))
        doms.append(k)
        costs = [draw(st.floats(0, 100)) for _ in range(k)]
        pb.add_node(i, costs)
    # random edge set
    for i in range(n):
        for j in range(i + 1, n):
            if draw(st.booleans()):
                M = np.array(
                    [[draw(st.sampled_from([0.0, 1.0, 5.0, 25.0, np.inf]))
                      for _ in range(doms[j])] for _ in range(doms[i])]
                )
                pb.add_edge(i, j, M)
    return pb


@st.composite
def pbqp_instances(draw):
    return _random_instance(draw)


class TestAgainstBruteForce:
    @settings(max_examples=150, deadline=None)
    @given(pbqp_instances())
    def test_exact_matches_brute_force(self, pb):
        try:
            bf = brute_force(pb)
        except Infeasible:
            with pytest.raises(Infeasible):
                solve(pb, exact=True)
            return
        sol = solve(pb, exact=True)
        assert sol.optimal
        assert sol.cost == pytest.approx(bf.cost)
        # the reported assignment must actually achieve the reported cost
        assert pb.evaluate(sol.assignment) == pytest.approx(sol.cost)

    @settings(max_examples=80, deadline=None)
    @given(pbqp_instances())
    def test_heuristic_is_feasible_and_bounded_below_by_opt(self, pb):
        try:
            bf = brute_force(pb)
        except Infeasible:
            return  # heuristic may or may not detect; exact path covers it
        try:
            sol = solve(pb, exact=False)
        except Infeasible:
            return  # RN may paint itself into an illegal corner; acceptable
        assert sol.cost >= bf.cost - 1e-9
        assert pb.evaluate(sol.assignment) == pytest.approx(sol.cost)


class TestScale:
    def test_long_chain_exact_and_fast(self):
        """VGG-like deep chains reduce entirely via RI — O(n)."""
        rng = np.random.default_rng(0)
        pb = PBQP()
        n, k = 200, 8
        for i in range(n):
            pb.add_node(i, rng.uniform(1, 100, size=k))
        for i in range(n - 1):
            pb.add_edge(i, i + 1, rng.uniform(0, 50, size=(k, k)))
        sol = solve(pb)
        assert sol.optimal
        assert sol.stats["RN"] == 0
        assert np.isfinite(sol.cost)

    def test_dense_core_exact_via_bb(self):
        """K5 with random costs needs branch-and-bound; must match BF."""
        rng = np.random.default_rng(1)
        pb = PBQP()
        n, k = 5, 3
        for i in range(n):
            pb.add_node(i, rng.uniform(1, 100, size=k))
        for i in range(n):
            for j in range(i + 1, n):
                pb.add_edge(i, j, rng.uniform(0, 50, size=(k, k)))
        sol = solve(pb, exact=True)
        bf = brute_force(pb)
        assert sol.cost == pytest.approx(bf.cost)
        assert sol.optimal

    def test_googlenet_shaped_graph(self):
        """Chain of inception-like diamonds (degree-3/4 joins)."""
        rng = np.random.default_rng(2)
        pb = PBQP()
        k = 6
        prev = "stem"
        pb.add_node(prev, rng.uniform(1, 100, size=k))
        T = lambda: rng.uniform(0, 30, size=(k, k)) * (1 - np.eye(k))
        for blk in range(9):
            branches = [f"i{blk}b{t}" for t in range(4)]
            join = f"i{blk}join"
            for b in branches:
                pb.add_node(b, rng.uniform(1, 100, size=k))
                pb.add_edge(prev, b, T())
            pb.add_node(join, rng.uniform(0, 1, size=k))
            for b in branches:
                pb.add_edge(b, join, T())
            prev = join
        sol = solve(pb, exact=True)
        assert np.isfinite(sol.cost)
        assert sol.optimal
