"""Mixture-of-Experts FFN with sort-based token dispatch.

TPU adaptation: instead of the GShard (B,T,E,C) one-hot dispatch einsum
(whose dispatch tensor is enormous at kimi scale), tokens are sorted by
destination expert and gathered into a capacity-bounded (E, C, D)
buffer.  Under expert-parallel sharding (experts -> "model" axis) XLA
lowers the gather/scatter to the expert all-to-all; the buffer is
explicitly annotated so the partitioner keeps it expert-sharded.
Overflow tokens beyond capacity are dropped (standard capacity-factor
semantics); gates renormalise over the kept top-k.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .sharding import PDef, ShardingPlan


def moe_defs(cfg) -> Dict[str, PDef]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": PDef((d, e), ("d_model", "experts")),
        "w1": PDef((e, d, f), ("experts", "d_model", "d_ff")),
        "w3": PDef((e, d, f), ("experts", "d_model", "d_ff")),
        "w2": PDef((e, f, d), ("experts", "d_ff", "d_model")),
    }


def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane alignment


def moe_ffn(cfg, p, x, plan: ShardingPlan):
    """x: (B, T, D) -> (B, T, D)."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n = b * t
    c = capacity(cfg, n)
    xf = x.reshape(n, d)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"]
                        .astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (N, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- sort-based dispatch ----
    pair_expert = expert_idx.reshape(-1)                     # (N*K,)
    order = jnp.argsort(pair_expert, stable=True)
    sorted_e = pair_expert[order]
    # rank of each pair within its expert segment
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(n * k) - seg_start
    keep = rank < c
    dest = jnp.where(keep, sorted_e * c + rank, e * c)        # OOB -> drop
    src_token = order // k
    src_gate = gate_vals.reshape(-1)[order]

    buf = jnp.zeros((e * c, d), x.dtype).at[dest].set(
        xf[src_token], mode="drop")
    buf = plan.constrain(buf.reshape(e, c, d), "experts", None, "d_model")

    # ---- expert computation (per-expert gated FFN) ----
    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    h = plan.constrain(h, "experts", None, "d_ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"])
    out_buf = plan.constrain(out_buf, "experts", None, "d_model")
    out_flat = out_buf.reshape(e * c, d)

    # ---- combine ----
    contrib = jnp.where(keep[:, None],
                        out_flat[jnp.minimum(dest, e * c - 1)], 0.0)
    y = jnp.zeros((n, d), x.dtype).at[src_token].add(
        contrib * src_gate[:, None].astype(x.dtype))
    y = y.reshape(b, t, d)
    return plan.constrain(y, "batch", "seq", "d_model")


def _local_dispatch(cfg, p, xf, c):
    """Shared sort-based dispatch on a device-local token slab.

    Returns (buf (E, C, D) dispatched tokens, combine metadata)."""
    n, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    pair_expert = expert_idx.reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    sorted_e = pair_expert[order]
    rank = jnp.arange(n * k) - jnp.searchsorted(sorted_e, sorted_e,
                                                side="left")
    keep = rank < c
    dest = jnp.where(keep, sorted_e * c + rank, e * c)
    src_token = order // k
    src_gate = gate_vals.reshape(-1)[order]
    buf = jnp.zeros((e * c, d), xf.dtype).at[dest].set(
        xf[src_token], mode="drop")
    return buf.reshape(e, c, d), (keep, dest, src_token, src_gate)


def _local_combine(cfg, out_flat, meta, n, d, dtype):
    e, c = cfg.n_experts, out_flat.shape[0] // cfg.n_experts
    keep, dest, src_token, src_gate = meta
    contrib = jnp.where(keep[:, None],
                        out_flat[jnp.minimum(dest, e * c - 1)], 0.0)
    y = jnp.zeros((n, d), dtype).at[src_token].add(
        contrib * src_gate[:, None].astype(dtype))
    return y


def moe_ffn_alltoall(cfg, p, x, plan: ShardingPlan):
    """Expert-parallel MoE with explicit all-to-alls (shard_map).

    §Perf hillclimb for the kimi cell: the gather-based dispatch above
    makes the SPMD partitioner all-gather the token slab (hundreds of
    TB/step at kimi scale).  Here routing runs on a (batch x seq)-local
    slab per device; the only cross-device traffic is two all-to-alls of
    the capacity-bounded dispatch buffer — the textbook GShard EP
    schedule, sized top_k * tokens * d_model.

    Requires a mesh with a "model" axis; seq divisible by |model|.
    """
    mesh = plan.mesh
    b, t, d = x.shape
    e = cfg.n_experts
    tp = mesh.shape["model"]
    e_local = e // tp
    t_local = t // tp
    n_local = b * t_local
    c = capacity(cfg, n_local)
    # per (dest-shard, local-expert) capacity such that E*C splits evenly
    assert (e * c) % tp == 0

    from jax.sharding import PartitionSpec as P

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(xl, router, w1, w3, w2):
        # xl: (b_local, t_local, d); experts weights local: (E_local,...)
        xf = xl.reshape(-1, d)
        buf, meta = _local_dispatch(
            cfg, {"router": router}, xf, c)          # (E, C, d)
        # group by destination shard and exchange
        buf = buf.reshape(tp, e_local * c, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        # buf: (tp source shards, e_local * c, d)
        buf = buf.reshape(tp, e_local, c, d)
        h = jnp.einsum("secd,edf->secf", buf, w1)
        h = jax.nn.silu(h) * jnp.einsum("secd,edf->secf", buf, w3)
        out = jnp.einsum("secf,efd->secd", h, w2)    # (tp, e_local, c, d)
        out = out.reshape(tp, e_local * c, d)
        out = jax.lax.all_to_all(out, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        out_flat = out.reshape(e * c, d)
        y = _local_combine(cfg, out_flat, meta, xf.shape[0], d, xl.dtype)
        return y.reshape(xl.shape)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axes or None, "model", None),
                  P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(data_axes or None, "model", None),
        check_vma=False)
    return fn(x, p["router"], p["w1"], p["w3"], p["w2"])


def aux_load_balance_loss(cfg, logits):
    """Switch-style load-balance auxiliary (returned by train paths)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    e = cfg.n_experts
    frac = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    top1 = jnp.argmax(probs, axis=-1)
    hard = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32),
                    axis=tuple(range(probs.ndim - 1)))
    return e * jnp.sum(frac * hard)
