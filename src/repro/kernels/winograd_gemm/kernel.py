"""Batched point-wise GEMM Pallas kernel for Winograd convolution.

The Winograd data flow is  V = B^T d B  (input transform, cheap),
Q[p] = U[p] @ V[p]  for each of the alpha^2 transform points p (this is
>95% of the FLOPs), then  y = A^T Q A.  This kernel implements the
batched GEMM stage with MXU tiling; transforms stay in XLA (they are
bandwidth-bound elementwise-ish work that XLA fuses well — the division
of labour the paper's Intel selections imply).

Grid: (P, N/bn, C/bc) with the contraction innermost; U tile (M, bc),
V tile (bc, bn), f32 VMEM accumulator of (M, bn).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret


def _bgemm_kernel(u_ref, v_ref, o_ref, acc_ref, *, precision):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(u_ref[0], v_ref[0], precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def winograd_bgemm_pallas(u, v, *, bn: int = 128, bc: int = 128,
                          precision=None, interpret=None):
    """u: (P, M, C), v: (P, C, N) -> (P, M, N);  C % bc == N % bn == 0.

    ``precision`` is the MXU precision of the tile products (None: the
    default, which rounds float32 operands to bfloat16 on a TPU).
    """
    p, m, c = u.shape
    _, _, n = v.shape
    assert v.shape == (p, c, n) and n % bn == 0 and c % bc == 0
    if interpret is None:
        interpret = use_interpret()

    return pl.pallas_call(
        functools.partial(_bgemm_kernel, precision=precision),
        grid=(p, n // bn, c // bc),
        in_specs=[
            pl.BlockSpec((1, m, bc), lambda pp, j, kk: (pp, 0, kk)),
            pl.BlockSpec((1, bc, bn), lambda pp, j, kk: (pp, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, m, bn), lambda pp, j, kk: (pp, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p, m, n), u.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        interpret=interpret,
    )(u, v)
