"""Tunable space of the im2col GEMM kernel (autotune hook).

The kernel is a (M, CKK) x (CKK, OHOW) GEMM tiled (bm, bn, bk); the
working set per grid step is the LHS/RHS/accumulator tiles.  Variants
inherit ``pallas_im2col_chw``'s layouts and fusable sets — the fused
entry points already take the block sizes through the ops wrapper.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from ...autotune.space import TunableSpace, params_tuple
from ...core.primitives import Primitive, _sup
from ..common import lane_block_ok
from .ops import conv_im2col

BASE_NAME = "pallas_im2col_chw"

#: f32 VMEM budget for one grid step's tiles (conservative half-VMEM)
_VMEM_BYTES = 4 * 2 ** 20

AXES = (("bm", (32, 64, 128, 256)),
        ("bn", (64, 128, 256, 512)),
        ("bk", (32, 64, 128, 256)))


def _valid(p) -> bool:
    bm, bn, bk = p["bm"], p["bn"], p["bk"]
    if any(b % 8 for b in (bm, bn, bk)):  # MXU sublane alignment
        return False
    tiles = bm * bk + bk * bn + 2 * bm * bn  # lhs + rhs + out + f32 acc
    return tiles * 4 <= _VMEM_BYTES


def _prepare(scn, w, b):
    return {"w": jnp.asarray(w), "b": jnp.asarray(b)}


def _make(scn, *, bm, bn, bk):
    def f(x, packed):  # x: CHW
        return conv_im2col(x, packed["w"], packed["b"], stride=scn.stride,
                           pad=scn.pad, bm=bm, bn=bn, bk=bk)
    return f


def _fused(bm, bn, bk):
    def build(scn, l_in, l_out):
        def f(x, packed):
            return conv_im2col(x, packed["w"], packed["b"],
                               stride=scn.stride, pad=scn.pad,
                               bm=bm, bn=bn, bk=bk,
                               in_layout=l_in, out_layout=l_out)
        return f
    return build


def _supports(bm, bn, bk):
    # every block axis is a lane axis in some layout variant (the (K, M)
    # prologue and (N, M) epilogue put M minor), so each must tile its
    # GEMM dim in whole 128-lane blocks or span it
    base = _sup()

    def supports(scn) -> bool:
        m, k = scn.m, scn.c * scn.k * scn.k
        return (base(scn) and lane_block_ok(bm, m) and lane_block_ok(bk, k)
                and lane_block_ok(bn, scn.out_h * scn.out_w))
    return supports


def _make_primitive(params) -> Primitive:
    bm, bn, bk = params["bm"], params["bn"], params["bk"]
    return Primitive(
        name=SPACE.name_for(BASE_NAME, params),
        family="pallas", l_in="CHW", l_out="CHW",
        supports=_supports(bm, bn, bk), prepare=_prepare,
        make=functools.partial(_make, bm=bm, bn=bn, bk=bk),
        tags=("tpu-only", "autotuned"),
        fusable_in=("HWC",), fusable_out=("HWC",),
        fused=_fused(bm, bn, bk),
        params=params_tuple(params, SPACE.axis_order))


SPACE = TunableSpace(kernel="conv_im2col", axes=AXES, valid=_valid,
                     make_primitive=_make_primitive)
