"""Property-test hardening pass over the solver stack.

Three invariant families, each stated once as a plain checker and driven
two ways — by hypothesis (random structured instances, shrinking on
failure) and by a seeded ``np.random`` smoke loop over fixed instances:

1. **Exactness** — the reduction + branch-and-bound solver agrees with
   exhaustive enumeration on every instance small enough to enumerate
   (<= 6 nodes, <= 4 choices), including instances with infinite
   (illegal) entries and infeasible ones.
2. **Warm-start purity** — ``solve_warm`` is a pure acceleration: for
   ANY warm assignment (the previous optimum, a random one, garbage
   ids, or None) the returned cost is identical to a cold exact solve.
3. **Plan legality** — ``select_pbqp`` never emits an unrealizable
   plan: every edge whose endpooints disagree on layout carries a
   materialized conversion chain (or fused realization) in the result,
   and the reported cost is finite and optimal.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.pbqp import PBQP, Infeasible, brute_force, solve, \
    solve_warm

# ----------------------------------------------------------------------
# instance generation (shared shape: hypothesis draws and np.random both
# produce <= 6 nodes x <= 4 choices with a 5-valued edge-cost alphabet)
# ----------------------------------------------------------------------
_EDGE_COSTS = (0.0, 1.0, 5.0, 25.0, np.inf)


def _build(doms, node_costs, edge_matrices) -> PBQP:
    pb = PBQP()
    for i, costs in enumerate(node_costs):
        pb.add_node(i, costs)
    for (i, j), M in edge_matrices.items():
        pb.add_edge(i, j, M)
    return pb


@st.composite
def pbqp_instances(draw):
    n = draw(st.integers(2, 6))
    doms = [draw(st.integers(1, 4)) for _ in range(n)]
    node_costs = [[draw(st.floats(0, 100)) for _ in range(k)]
                  for k in doms]
    edges = {}
    for i in range(n):
        for j in range(i + 1, n):
            if draw(st.booleans()):
                edges[(i, j)] = np.array(
                    [[draw(st.sampled_from(_EDGE_COSTS))
                      for _ in range(doms[j])] for _ in range(doms[i])])
    return _build(doms, node_costs, edges)


def random_pbqp(rng: np.random.Generator) -> PBQP:
    """Same distribution as :func:`pbqp_instances`, seeded numpy draw —
    the seeded smoke loop and failure reproduction both use it."""
    n = int(rng.integers(2, 7))
    doms = [int(rng.integers(1, 5)) for _ in range(n)]
    node_costs = [rng.uniform(0, 100, size=k) for k in doms]
    edges = {}
    for i in range(n):
        for j in range(i + 1, n):
            if rng.integers(2):
                edges[(i, j)] = rng.choice(
                    _EDGE_COSTS, size=(doms[i], doms[j]))
    return _build(doms, node_costs, edges)


# ----------------------------------------------------------------------
# the invariants, stated once
# ----------------------------------------------------------------------
def check_exact_matches_brute(pb: PBQP) -> None:
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


def check_warm_matches_cold(pb: PBQP, rng: np.random.Generator) -> None:
    """Every flavour of warm seed yields the cold-exact cost."""
    try:
        cold = solve(pb, exact=True)
    except Infeasible:
        for warm in (None, {u: 0 for u in pb._costs}):
            with pytest.raises(Infeasible):
                solve_warm(pb, warm, exact=True)
        return
    seeds = [
        None,                                        # no seed at all
        dict(cold.assignment),                       # the optimum itself
        {u: int(rng.integers(pb.domain(u)))          # a random legal one
         for u in pb._costs},
        {u: 999 for u in pb._costs},                 # out-of-range
        {"not-a-node": 0},                           # wrong node set
    ]
    for warm in seeds:
        ws = solve_warm(pb, warm, exact=True)
        assert ws.cost == pytest.approx(cold.cost), f"warm={warm}"
        assert pb.evaluate(ws.assignment) == pytest.approx(ws.cost)
    # the optimum as seed must be recognised as usable and distance 0
    exact_seed = solve_warm(pb, dict(cold.assignment), exact=True)
    assert exact_seed.stats["WARM"] == 1
    assert exact_seed.stats["WARM_DIST"] == 0


def check_selection_legal(shape, depth: int, width: int,
                          mesh_axes=None, batch: int = 1) -> None:
    """select_pbqp output is realizable: every layout-mismatched edge
    carries a conversion chain (or fused realization).  With
    ``mesh_axes`` the placement axis joins the domain: pipeline stage
    boundaries are exempt from the no-conversion-on-matching-layouts
    rule (they wire through logical CHW regardless of the endpoint
    layouts), stage assignments must be monotone, and sharded kinds
    must be ones the mesh offers."""
    from repro.core.costs import AnalyticCostModel
    from repro.core.selection import (Placement, placements_for,
                                      select_pbqp)
    from repro.serving.towers import conv_tower, uniform_stack

    if mesh_axes and "stage" in mesh_axes:
        # the stage axis only matters on a pipelineable net
        net = uniform_stack(shape, depth=depth)
    else:
        net = conv_tower(shape, depth=depth, width=width)
    if batch > 1:
        net = net.with_batch(batch)
    sel = select_pbqp(net, AnalyticCostModel(), exact=True,
                      mesh_axes=mesh_axes)
    assert sel.optimal
    assert np.isfinite(sel.predicted_cost)
    assert set(sel.choices) == set(net.order)
    offered = set(placements_for(net, mesh_axes))
    pl = {nid: Placement.parse(sel.choices[nid].placement)
          for nid in net.order}
    for nid in net.order:
        assert str(pl[nid]) in offered or pl[nid].kind != "pp", pl[nid]
        if pl[nid].kind != "pp":
            assert str(pl[nid]) in offered, pl[nid]
    for (src, dst) in net.edges():
        lo = sel.choices[src].l_out
        li = sel.choices[dst].l_in
        pu, pv = pl[src], pl[dst]
        # pipeline membership is all-or-nothing and stage-monotone
        assert (pu.kind == "pp") == (pv.kind == "pp")
        if pu.kind == "pp":
            assert pv.stage >= pu.stage, f"backward hop {src}->{dst}"
            if pv.stage != pu.stage:
                # stage boundary: wired through CHW; a conversion
                # chain, when present, must pass through it
                chain = sel.conversions.get((src, dst))
                if lo == "CHW" and li == "CHW":
                    assert chain is None or "CHW" in chain
                else:
                    assert chain is not None and "CHW" in chain, \
                        f"stage boundary {src}->{dst} not CHW-wired"
                continue
        if lo == li:
            assert (src, dst) not in sel.conversions
        else:
            assert (src, dst) in sel.conversions \
                or (src, dst) in sel.fusions, \
                f"unrealized layout break on {src}->{dst} ({lo}->{li})"
            chain = sel.conversions.get((src, dst))
            if chain is not None:
                assert len(chain) >= 1


# ----------------------------------------------------------------------
# hypothesis drivers
# ----------------------------------------------------------------------
class TestSolverProperties:
    @settings(max_examples=120, deadline=None)
    @given(pbqp_instances())
    def test_exact_matches_brute_force(self, pb):
        check_exact_matches_brute(pb)

    @settings(max_examples=60, deadline=None)
    @given(pbqp_instances(), st.integers(0, 2**31 - 1))
    def test_warm_start_cost_identical_to_cold(self, pb, seed):
        check_warm_matches_cold(pb, np.random.default_rng(seed))


#: placement domains the property sweep draws from — every mesh kind
#: the solver offers, plus the meshless baseline
_MESH_DRAWS = (None, {"data": 2}, {"data": 4}, {"data": 2, "model": 2},
               {"model": 4}, {"stage": 2}, {"stage": 3})


class TestSelectionProperties:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 8), st.integers(10, 28), st.integers(10, 28),
           st.integers(1, 4), st.integers(2, 8))
    def test_plans_legal_under_legalize(self, c, h, w, depth, width):
        check_selection_legal((c, h, w), depth, width)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 8), st.integers(10, 28), st.integers(10, 28),
           st.integers(1, 4), st.integers(2, 8),
           st.sampled_from(_MESH_DRAWS), st.sampled_from((1, 4, 8)))
    def test_plans_legal_with_placements(self, c, h, w, depth, width,
                                         axes, batch):
        check_selection_legal((c, h, w), depth, width,
                              mesh_axes=axes, batch=batch)


# ----------------------------------------------------------------------
# seeded smoke loop: the same checkers over fixed numpy draws (makes any
# hypothesis failure trivially reproducible from its numpy seed).
# ----------------------------------------------------------------------
class TestSeededSmoke:
    def test_exact_and_warm_seeded(self):
        rng = np.random.default_rng(1234)
        for _ in range(40):
            pb = random_pbqp(rng)
            check_exact_matches_brute(pb)
        for _ in range(15):
            pb = random_pbqp(rng)
            check_warm_matches_cold(pb, rng)

    def test_selection_legal_seeded(self):
        rng = np.random.default_rng(99)
        for _ in range(4):
            check_selection_legal(
                (int(rng.integers(2, 9)), int(rng.integers(10, 29)),
                 int(rng.integers(10, 29))),
                depth=int(rng.integers(1, 5)),
                width=int(rng.integers(2, 9)))

    def test_selection_legal_with_placements_seeded(self):
        rng = np.random.default_rng(7)
        for _ in range(4):
            axes = _MESH_DRAWS[int(rng.integers(len(_MESH_DRAWS)))]
            check_selection_legal(
                (int(rng.integers(2, 9)), int(rng.integers(10, 29)),
                 int(rng.integers(10, 29))),
                depth=int(rng.integers(1, 5)),
                width=int(rng.integers(2, 9)),
                mesh_axes=axes,
                batch=int(rng.choice((1, 4, 8))))
