"""Tiled data-layout transformation (CHW <-> HWC) Pallas kernel.

The paper's DT-graph edges are executed by routines like this one: a
blocked transpose that reads (C, bh, bw) tiles and writes (bh, bw, C)
tiles, keeping both tiles VMEM-resident so HBM sees only two streaming
passes.  On TPU the (8, 128) sublane/lane register tiling makes the
choice of which axis lands innermost *the* performance lever — exactly
the paper's thesis that layout is a first-class optimization decision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import use_interpret


def _chw_to_hwc_kernel(x_ref, o_ref):
    o_ref[...] = jnp.transpose(x_ref[...], (1, 2, 0))


def _hwc_to_chw_kernel(x_ref, o_ref):
    o_ref[...] = jnp.transpose(x_ref[...], (2, 0, 1))


def chw_to_hwc_pallas(x, *, bh: int = 8, bw: int = 128, interpret=None):
    """x: (C, H, W) -> (H, W, C); H % bh == W % bw == 0."""
    c, h, w = x.shape
    assert h % bh == 0 and w % bw == 0
    if interpret is None:
        interpret = use_interpret()
    return pl.pallas_call(
        _chw_to_hwc_kernel,
        grid=(h // bh, w // bw),
        in_specs=[pl.BlockSpec((c, bh, bw), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((bh, bw, c), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((h, w, c), x.dtype),
        interpret=interpret,
    )(x)


def hwc_to_chw_pallas(x, *, bh: int = 8, bw: int = 128, interpret=None):
    """x: (H, W, C) -> (C, H, W); H % bh == W % bw == 0."""
    h, w, c = x.shape
    assert h % bh == 0 and w % bw == 0
    if interpret is None:
        interpret = use_interpret()
    return pl.pallas_call(
        _hwc_to_chw_kernel,
        grid=(h // bh, w // bw),
        in_specs=[pl.BlockSpec((bh, bw, c), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((c, bh, bw), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((c, h, w), x.dtype),
        interpret=interpret,
    )(x)
