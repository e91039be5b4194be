"""Tunable space of the direct NHWC kernel (autotune hook).

Axis: ``bm`` — output-channel tile (the grid dimension).  The kernel
runs stride 1 only, ``bm`` must tile the output channels in whole
128-lane blocks (or span them), and the blocks must fit VMEM at that
``bm``: the generated primitive's ``supports`` checks all three.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ...autotune.space import TunableSpace, params_tuple
from ...core.primitives import Primitive, _sup
from ..common import lane_block_ok
from .kernel import fits_vmem
from .ops import conv_direct

BASE_NAME = "pallas_direct_hwc"

AXES = (("bm", (32, 64, 128, 256)),)


def _valid(p) -> bool:
    return p["bm"] % 8 == 0


def _supports(bm):
    # bm is the lane axis of the weight and output blocks
    base = _sup(stride1=True)
    return lambda scn: (base(scn) and lane_block_ok(bm, scn.m)
                        and fits_vmem(scn, bm))


def _prepare(scn, w, b):
    return {"w": jnp.asarray(np.transpose(w, (2, 3, 1, 0)).copy()),
            "b": jnp.asarray(b)}


def _make(scn, *, bm):
    def f(x, packed):  # x: HWC
        return conv_direct(x, packed["w"], packed["b"], pad=scn.pad, bm=bm)
    return f


def _fused(bm):
    def build(scn, l_in, l_out):
        def f(x, packed):
            return conv_direct(x, packed["w"], packed["b"], pad=scn.pad,
                               bm=bm, in_layout=l_in, out_layout=l_out)
        return f
    return build


def _make_primitive(params) -> Primitive:
    bm = params["bm"]
    return Primitive(
        name=SPACE.name_for(BASE_NAME, params),
        family="pallas", l_in="HWC", l_out="HWC",
        supports=_supports(bm), prepare=_prepare,
        make=functools.partial(_make, bm=bm),
        tags=("tpu-only", "autotuned"),
        fusable_in=("CHW",), fusable_out=("CHW",),
        fused=_fused(bm),
        params=params_tuple(params, SPACE.axis_order))


SPACE = TunableSpace(kernel="conv_direct", axes=AXES, valid=_valid,
                     make_primitive=_make_primitive)
