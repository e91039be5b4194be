"""GPipe-style pipeline parallelism over a mesh axis via shard_map.

The model's superblocks are split into S stages along the ``stage``
mesh axis; microbatches stream through with collective_permute boundary
transfers.  The schedule is the classic GPipe fill-drain loop expressed
as a ``lax.fori_loop`` over T = n_micro + S - 1 ticks — every tick each
stage computes one microbatch (or idles in the ramp) and the boundary
activations rotate by one stage.

At 1000+ node scale this maps pipeline stages onto the slow inter-pod
axis (stage boundary traffic is tiny: one (micro_b, t, d) tensor per
tick) while TP/DP stay on fast intra-pod ICI — the standard production
topology.  Used by examples/pipeline_parallel.py and
tests/test_distributed.py (4-device CPU mesh).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_ticks"]


def pipeline_ticks(s: int, n_micro: int) -> int:
    """Fill-drain tick count of the GPipe schedule: ``n_micro + s - 1``
    (the bubble term the solver's pp node costs scale by — see
    ``selection.PlacementPricing``)."""
    if s < 1 or n_micro < 1:
        raise ValueError(f"need s >= 1 and n_micro >= 1, got "
                         f"s={s} n_micro={n_micro}")
    return n_micro + s - 1


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params,
                   x, *, n_micro: int, axis: str = "stage"):
    """Run ``y = stage_S(...stage_1(x))`` pipelined over ``axis``.

    stage_fn(params_for_stage, x_micro) -> y_micro (same shape).
    stage_params: pytree with a leading stage axis (sharded over axis).
    x: (n_micro, micro_b, ...) microbatched input (replicated).
    """
    s = mesh.shape[axis]
    t_total = pipeline_ticks(s, n_micro)

    def per_stage(params, xs):
        stage = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)  # local stage slice
        buf = jnp.zeros_like(xs)     # output accumulator (n_micro, ...)
        carry = jnp.zeros_like(xs[0])

        def tick(t, state):
            carry, buf = state
            m = t - stage            # microbatch index at this stage
            # stage 0 reads its input from xs; others from the carry
            inp = jnp.where(stage == 0,
                            xs[jnp.clip(m, 0, n_micro - 1)], carry)
            active = jnp.logical_and(m >= 0, m < n_micro)
            out = stage_fn(params, inp)
            out = jnp.where(active, out, carry)
            # last stage banks its result
            buf = jax.lax.cond(
                jnp.logical_and(active, stage == s - 1),
                lambda b: b.at[jnp.clip(m, 0, n_micro - 1)].set(out),
                lambda b: b, buf)
            # rotate boundary activations forward one stage
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % s) for i in range(s)])
            return (nxt, buf)

        _, buf = jax.lax.fori_loop(0, t_total, tick, (carry, buf))
        # only the last stage holds real outputs; broadcast to all
        buf = jax.lax.psum(
            jnp.where(stage == s - 1, buf, jnp.zeros_like(buf)), axis)
        return buf

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x)
