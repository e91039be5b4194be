"""Autotune subsystem: spaces, pruning, catalog, registry extension.

Covers the PR-level invariants:

* every declared parameter space enumerates only valid configurations
  and generated variants compute the same convolution as the reference
  oracle (interpret mode);
* the registry extension mechanism is cached, invalidates correctly,
  rejects duplicate names, and rotates every ``CostModel.version()``;
* dominance pruning is sound (a pruned variant is never the per-bucket
  winner anywhere — property-tested) and order-free (stable under
  permutation of the measurement/candidate order);
* the catalog round-trips through JSON, installs/uninstalls, and
  refuses stale parameter spaces;
* the tuner is resumable and budget-capped, the CLI dry-runs, and
  anytime PBQP honours a solve deadline on the widened registry.
"""
import json
import pathlib
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.autotune import (
    Candidate, VariantCatalog, generate_variants, kernel_spaces,
    plan_only, prune_dominated, spaces, tune, variant_name,
)
from repro.calibrate.sweep import scenario_grid, scenarios_from_net
from repro.core.costs import AnalyticCostModel, TPU_V5E_SPEC
from repro.core.layouts import LAYOUT_BY_NAME
from repro.core.primitives import (
    clear_extensions, extension_token, register_extension, registry,
    unregister_extension,
)
from repro.core.scenario import Scenario, ref_conv
from repro.core.selection import select_pbqp
from repro.serving.towers import conv_tower, uniform_stack

pytestmark = pytest.mark.usefixtures("clean_registry")


@pytest.fixture
def clean_registry():
    clear_extensions()
    yield
    clear_extensions()


TPU_COST = lambda: AnalyticCostModel(TPU_V5E_SPEC, include_tpu_only=True)

SCN_K3 = Scenario(c=8, h=12, w=12, stride=1, k=3, m=8)
SCN_K1 = Scenario(c=8, h=10, w=10, stride=1, k=1, m=8, pad=0)


# ----------------------------------------------------------------------
# parameter spaces
# ----------------------------------------------------------------------
class TestSpaces:
    def test_all_kernel_packages_declare_a_space(self):
        sp = spaces()
        assert set(sp) == {"matmul", "conv_direct", "conv_im2col",
                          "winograd_gemm", "flash_attention",
                          "layout_transform"}
        assert sum(s.registers for s in sp.values()) == 4
        assert len(kernel_spaces(None)) == 2

    def test_configs_are_valid_and_named_uniquely(self):
        for s in spaces().values():
            cfgs = s.configs()
            assert cfgs, s.kernel
            names = {s.make_primitive(c).name for c in cfgs} \
                if s.registers else \
                {variant_name(s.kernel, c, s.axis_order) for c in cfgs}
            assert len(names) == len(cfgs), s.kernel
            for c in cfgs:
                assert s.valid(c), (s.kernel, c)
                assert set(c) == set(s.axis_order)

    def test_generated_variants_carry_params_and_unique_names(self):
        variants = generate_variants()
        assert len(variants) > 100
        assert len({p.name for p in variants}) == len(variants)
        base_names = {p.name for p in registry()}
        for p in variants:
            assert p.params and p.family == "pallas"
            assert "@" in p.name and p.name not in base_names

    @pytest.mark.parametrize("kernel,scn", [
        ("conv_im2col", SCN_K3), ("conv_direct", SCN_K3),
        ("winograd_gemm", SCN_K3), ("matmul", SCN_K1),
    ])
    def test_variant_matches_reference_conv(self, kernel, scn):
        """Smallest config of each registering space that the scenario
        admits (blocks lane-aligned or spanning their axis), interpret
        mode."""
        space = spaces()[kernel]
        prim = next(p for p in map(space.make_primitive, space.configs())
                    if p.supports(scn))
        rng = np.random.default_rng(0)
        x = rng.normal(size=scn.in_shape_chw).astype(np.float32)
        w = (rng.normal(size=scn.weight_shape) * 0.1).astype(np.float32)
        b = rng.normal(size=(scn.m,)).astype(np.float32)
        want = ref_conv(x, w, b, scn.stride, scn.pad)
        packed = prim.prepare(scn, w, b)
        xin = LAYOUT_BY_NAME[prim.l_in].to_memory(x)
        y = np.asarray(prim.make(scn)(xin, packed))
        got = LAYOUT_BY_NAME[prim.l_out].from_memory(y)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2,
                                   err_msg=prim.name)


# ----------------------------------------------------------------------
# registry extension
# ----------------------------------------------------------------------
class TestRegistryExtension:
    def test_register_unregister_roundtrip(self):
        n0 = len(registry())
        space = spaces()["conv_im2col"]
        prim = space.make_primitive(space.configs()[0])
        register_extension("t", (prim,), token="abc")
        assert len(registry()) == n0 + 1
        assert extension_token() == "t:abc"
        assert unregister_extension("t")
        assert len(registry()) == n0
        assert extension_token() == ""
        assert not unregister_extension("t")

    def test_duplicate_names_rejected(self):
        base = registry()[0]
        with pytest.raises(ValueError, match="duplicate"):
            register_extension("dup", (base,))
        space = spaces()["conv_im2col"]
        prim = space.make_primitive(space.configs()[0])
        register_extension("a", (prim,))
        with pytest.raises(ValueError, match="duplicate"):
            register_extension("b", (prim,))

    def test_cost_model_version_rotates_with_extensions(self):
        cm = TPU_COST()
        v0 = cm.version()
        space = spaces()["conv_im2col"]
        prim = space.make_primitive(space.configs()[0])
        register_extension("t", (prim,), token="abc")
        v1 = cm.version()
        assert v1 != v0
        register_extension("t2", (space.make_primitive(
            space.configs()[1]),), token="xyz")
        assert cm.version() not in (v0, v1)
        clear_extensions()
        assert cm.version() == v0


# ----------------------------------------------------------------------
# dominance pruning
# ----------------------------------------------------------------------
def _cand(name, costs, prunable=True, group="g"):
    return Candidate(name=name, prunable=prunable,
                     group=(group, tuple(sorted(costs))),
                     costs=tuple(sorted(costs.items())))


def _group_of(cands):
    by = {}
    for c in cands:
        by.setdefault(c.group, []).append(c)
    return by


def _check_sound(cands, survivors, pruned):
    """Every pruned candidate is weakly covered by a survivor in its
    group on every bucket — so it can never be the per-bucket winner."""
    surv = set(survivors)
    by_group = _group_of(cands)
    for group in by_group.values():
        live = [c for c in group if c.name in surv]
        for v in group:
            if v.name in surv:
                continue
            vc = v.cost_map()
            assert any(
                set(vc) <= set(u.cost_map())
                and all(u.cost_map()[b] <= vc[b] for b in vc)
                for u in live), f"{v.name} pruned without cover"


class TestPruning:
    def test_dominated_variant_pruned_with_dominator_recorded(self):
        a = _cand("a", {"b0": 1.0, "b1": 1.0})
        b = _cand("b", {"b0": 2.0, "b1": 1.0})
        survivors, pruned = prune_dominated([a, b])
        assert survivors == ["a"] and pruned == {"b": "a"}

    def test_pareto_incomparable_both_survive(self):
        a = _cand("a", {"b0": 1.0, "b1": 3.0})
        b = _cand("b", {"b0": 3.0, "b1": 1.0})
        survivors, pruned = prune_dominated([a, b])
        assert survivors == ["a", "b"] and not pruned

    def test_handwritten_never_pruned_and_wins_ties(self):
        base = _cand("zz_base", {"b0": 1.0}, prunable=False)
        tied = _cand("aa_variant", {"b0": 1.0})
        worse = _cand("mm_variant", {"b0": 2.0})
        survivors, pruned = prune_dominated([base, tied, worse])
        assert survivors == ["zz_base"]
        assert pruned["aa_variant"] == "zz_base"
        # mm's recorded dominator may itself be pruned; the chain must
        # still bottom out in a survivor (transitivity)
        assert set(pruned) == {"aa_variant", "mm_variant"}
        _check_sound([base, tied, worse], survivors, pruned)

    def test_different_groups_never_compared(self):
        a = _cand("a", {"b0": 1.0}, group="g1")
        b = _cand("b", {"b0": 9.0}, group="g2")
        survivors, _ = prune_dominated([a, b])
        assert survivors == ["a", "b"]

    def test_unmeasured_candidate_not_used_as_dominator(self):
        empty = _cand("empty", {})
        a = _cand("a", {"b0": 5.0})
        survivors, pruned = prune_dominated([empty, a])
        assert "a" in survivors and "a" not in pruned

    # -- properties (hypothesis + seeded smoke loop) -------------------
    @staticmethod
    def _random_cands(rng_draw):
        """rng_draw(n) -> int in [0, n); shared shape for both drivers."""
        buckets = [f"b{i}" for i in range(1 + rng_draw(3))]
        support = tuple(buckets[:1 + rng_draw(len(buckets))])
        cands = []
        n = 2 + rng_draw(5)
        costs_alphabet = (1.0, 2.0, 4.0, 8.0)
        for i in range(n):
            costs = {b: costs_alphabet[rng_draw(4)] for b in support}
            cands.append(_cand(f"c{i}", costs,
                               prunable=bool(rng_draw(4)),
                               group="g"))
        return cands

    @given(st.data())
    @settings(max_examples=150, deadline=None)
    def test_property_pruned_never_per_bucket_winner(self, data):
        cands = self._random_cands(
            lambda n: data.draw(st.integers(0, n - 1)))
        survivors, pruned = prune_dominated(cands)
        assert set(survivors) | set(pruned) == {c.name for c in cands}
        _check_sound(cands, survivors, pruned)

    @given(st.data())
    @settings(max_examples=150, deadline=None)
    def test_property_stable_under_permutation(self, data):
        cands = self._random_cands(
            lambda n: data.draw(st.integers(0, n - 1)))
        survivors, pruned = prune_dominated(cands)
        perm = data.draw(st.permutations(cands))
        survivors2, pruned2 = prune_dominated(perm)
        assert survivors == survivors2
        assert set(pruned) == set(pruned2)

    def test_smoke_properties_seeded(self):
        rng = np.random.default_rng(7)
        for _ in range(200):
            cands = self._random_cands(lambda n: int(rng.integers(n)))
            survivors, pruned = prune_dominated(cands)
            _check_sound(cands, survivors, pruned)
            order = rng.permutation(len(cands))
            s2, p2 = prune_dominated([cands[i] for i in order])
            assert survivors == s2 and set(pruned) == set(p2)

    def test_pruning_never_changes_the_pbqp_optimum(self):
        """End to end: solving over survivors-only equals solving over
        the full candidate pool — the pruned variants were never
        needed (the tune sweep covers every bucket the net solves)."""
        net = uniform_stack((256, 16, 16), depth=2, k=1)
        scns = scenarios_from_net(net, batches=(1,))
        cost = TPU_COST()
        res = tune(scns, kernels=("matmul",), measure_mode="analytic")
        surv = res.catalog.build_primitives()
        assert res.pruned > 0
        all_variants = generate_variants(kernels=("matmul",))
        register_extension("all", tuple(all_variants))
        full = select_pbqp(net, cost)
        clear_extensions()
        register_extension("surv", tuple(surv))
        lean = select_pbqp(net, cost)
        assert lean.predicted_cost == pytest.approx(
            full.predicted_cost, rel=1e-9)


# ----------------------------------------------------------------------
# catalog
# ----------------------------------------------------------------------
def _tiny_tune(**kw):
    return tune(scenario_grid("tiny"), measure_mode="analytic", **kw)


class TestCatalog:
    def test_roundtrip_and_install(self, tmp_path):
        res = _tiny_tune()
        cat = res.catalog
        assert res.generated == len(cat.variants) > 0
        path = tmp_path / "cat.json"
        cat.save(path)
        loaded = VariantCatalog.load(path)
        assert loaded.content_hash() == cat.content_hash()
        assert loaded.survivors() == cat.survivors()
        n0 = len(registry())
        n = loaded.install()
        assert n == len(cat.survivors())
        assert len(registry()) == n0 + n
        assert cat.content_hash() in extension_token()
        assert VariantCatalog.uninstall()
        assert len(registry()) == n0

    def test_schema_mismatch_rejected(self, tmp_path):
        res = _tiny_tune()
        payload = res.catalog.to_payload()
        payload["schema"] = 999
        with pytest.raises(ValueError, match="schema"):
            VariantCatalog.from_payload(payload)

    def test_stale_parameter_space_rejected(self):
        res = _tiny_tune()
        cat = res.catalog
        name = cat.survivors()[0]
        entry = cat.variants[name]
        key = next(iter(entry["params"]))
        entry["params"] = dict(entry["params"], **{key: 7777})
        with pytest.raises(ValueError, match="re-run the tuner"):
            cat.build_primitives()

    def test_kernel_only_winners_recorded(self):
        res = _tiny_tune()
        keys = list(res.catalog.kernels)
        assert any(k.startswith("flash_attention::") for k in keys)
        assert any(k.startswith("layout_transform::") for k in keys)
        for e in res.catalog.kernels.values():
            assert e["seconds"] > 0 and e["params"]


# ----------------------------------------------------------------------
# tuner + CLI
# ----------------------------------------------------------------------
class TestTuner:
    def test_budget_caps_and_resumes(self, tmp_path):
        prof_path = tmp_path / "p.json"
        res = _tiny_tune(budget=25, profile_path=prof_path)
        assert res.sweep["measured"] == 25
        assert res.sweep["remaining"] > 0
        res2 = _tiny_tune(profile=res.profile, profile_path=prof_path)
        assert res2.sweep["skipped"] == 25
        assert res2.sweep["remaining"] == 0
        assert res2.surviving >= 1

    def test_plan_only_measures_nothing(self):
        variants, items, index = plan_only(scenario_grid("small"))
        assert len(items) == len(index) > 0 and len(variants) > 0
        prim_keys = [k for k, e in index.items() if e[0] == "prim"]
        assert all(k.startswith("prim::") for k in prim_keys)

    def test_cli_dry_run(self, capsys):
        from repro.launch.tune import main
        assert main(["--catalog", "/nonexistent/never-written.json",
                     "--grid", "tiny", "--dry-run"]) == 0
        out = capsys.readouterr().out
        assert "dry run: nothing measured, nothing written" in out
        assert not pathlib.Path("/nonexistent").exists()

    def test_cli_tiny_run_writes_catalog(self, tmp_path, capsys):
        from repro.launch.tune import main
        cat = tmp_path / "cat.json"
        rc = main(["--catalog", str(cat), "--grid", "tiny",
                   "--kernels", "conv_im2col", "--max-per-kernel", "4",
                   "--measure", "analytic"])
        assert rc == 0
        assert cat.exists() and cat.with_suffix(".profile.json").exists()
        loaded = VariantCatalog.load(cat)
        assert json.loads(cat.read_text())["schema"] == 1
        n0 = len(registry())
        loaded.install()
        assert len(registry()) >= n0
        # re-run resumes: everything covered, nothing new measured
        clear_extensions()
        rc = main(["--catalog", str(cat), "--grid", "tiny",
                   "--kernels", "conv_im2col", "--max-per-kernel", "4",
                   "--measure", "analytic"])
        assert rc == 0
        assert "measured 0," in capsys.readouterr().out


# ----------------------------------------------------------------------
# anytime solve over the widened registry
# ----------------------------------------------------------------------
class TestAnytimeOnWidenedRegistry:
    def test_deadline_respected_with_near_optimal_cost(self):
        """Regression for the solve->compile->serve fallback ladder:
        with the autotuned extension installed (>= 70 primitives) the
        anytime solver must return by its deadline with an incumbent
        within 10% of the exact optimum."""
        net = conv_tower((32, 32, 32), depth=3, width=32)
        cost = TPU_COST()
        res = tune(scenario_grid("tiny")
                   + scenarios_from_net(net, batches=(1,)),
                   measure_mode="analytic")
        res.catalog.install()
        assert len(registry()) >= 70
        exact = select_pbqp(net, cost)
        deadline = 0.5
        t0 = time.perf_counter()
        anytime = select_pbqp(net, cost, deadline_s=deadline)
        wall = time.perf_counter() - t0
        assert wall <= deadline + 0.5
        assert anytime.predicted_cost <= 1.1 * exact.predicted_cost
