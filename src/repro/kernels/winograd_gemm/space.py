"""Tunable space of the Winograd batched-GEMM kernel (autotune hook).

Axes: ``m_`` — the F(m, 3) output tile (2 or 4; changes the offline
kernel transform, so it is part of ``prepare``); ``bn`` — spatial-tile
block of the batched GEMM; ``bc`` — input-channel block.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from ...autotune.space import TunableSpace, params_tuple
from ...core.primitives import Primitive, _sup
from ..common import lane_block_ok
from .ops import conv_winograd, prepare_kernel

BASE_NAME = "pallas_wino_chw"

_VMEM_BYTES = 4 * 2 ** 20

AXES = (("m_", (2, 4)),
        ("bn", (32, 64, 128, 256)),
        ("bc", (32, 64, 128)))


def _valid(p) -> bool:
    m_, bn, bc = p["m_"], p["bn"], p["bc"]
    if bn % 8 or bc % 8:
        return False
    a2 = (m_ + 2) ** 2  # alpha^2 for k=3
    # per grid step: V tile (bc, bn), U slice (M<=256, bc), acc (M, bn)
    return a2 * (bc * bn + 256 * bc + 256 * bn) * 4 <= 4 * _VMEM_BYTES


def _prepare(m_):
    def prep(scn, w, b):
        return {"u": prepare_kernel(w, m_), "b": jnp.asarray(b)}
    return prep


def _make(scn, *, m_, bn, bc):
    def f(x, packed):  # x: CHW
        return conv_winograd(x, packed["u"], packed["b"], m_=m_, k=scn.k,
                             stride=scn.stride, pad=scn.pad, bn=bn, bc=bc)
    return f


def _fused(m_, bn, bc):
    def build(scn, l_in, l_out):
        def f(x, packed):
            return conv_winograd(x, packed["u"], packed["b"], m_=m_,
                                 k=scn.k, stride=scn.stride, pad=scn.pad,
                                 bn=bn, bc=bc,
                                 in_layout=l_in, out_layout=l_out)
        return f
    return build


def _supports(m_, bn, bc):
    # bc is the lane axis of the (M, bc) kernel-transform block, bn of
    # the (bc, bn) input-transform and (M, bn) output blocks
    base = _sup(k_in=(3,), stride1=True)

    def supports(scn) -> bool:
        tiles = -(-scn.out_h // m_) * -(-scn.out_w // m_)
        return (base(scn) and lane_block_ok(bc, scn.c)
                and lane_block_ok(bn, tiles))
    return supports


def _make_primitive(params) -> Primitive:
    m_, bn, bc = params["m_"], params["bn"], params["bc"]
    # keep the hand-written entries' name shape (pallas_wino_f{m}x3_…)
    # so the analytic model's tile parser reads the F(m, 3) config
    base = f"pallas_wino_f{m_}x3_chw"
    pt = params_tuple(params, SPACE.axis_order)
    return Primitive(
        name=SPACE.name_for(base, {k: v for k, v in params.items()
                                   if k != "m_"}),
        family="pallas", l_in="CHW", l_out="CHW",
        supports=_supports(m_, bn, bc),
        prepare=_prepare(m_),
        make=functools.partial(_make, m_=m_, bn=bn, bc=bc),
        tags=("tpu-only", "autotuned"),
        fusable_in=("HWC",), fusable_out=("HWC",),
        fused=_fused(m_, bn, bc),
        params=pt)


SPACE = TunableSpace(kernel="winograd_gemm", axes=AXES, valid=_valid,
                     make_primitive=_make_primitive)
