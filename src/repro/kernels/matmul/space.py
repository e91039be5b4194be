"""Tunable space of the MXU GEMM kernel (autotune hook).

Registered variants are pointwise (K=1) convolutions — the (M, C) x
(C, OHOW) GEMM the hand-written ``pallas_pw_gemm_chw`` entry runs —
tiled (bm, bn, bk).  ``bk`` doubles as the software-pipeline depth knob:
the kernel's grid walks K in ``bk`` steps.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from ...autotune.space import TunableSpace, params_tuple
from ...core.primitives import Primitive, _sup
from ..common import lane_block_ok
from .ops import matmul

BASE_NAME = "pallas_pw_gemm_chw"

_VMEM_BYTES = 4 * 2 ** 20

AXES = (("bm", (32, 64, 128, 256)),
        ("bn", (64, 128, 256, 512)),
        ("bk", (32, 64, 128, 256)))


def _valid(p) -> bool:
    bm, bn, bk = p["bm"], p["bn"], p["bk"]
    if any(b % 8 for b in (bm, bn, bk)):
        return False
    return (bm * bk + bk * bn + 2 * bm * bn) * 4 <= _VMEM_BYTES


def _prepare(scn, w, b):
    return {"w": jnp.asarray(w.reshape(scn.m, scn.c)),
            "b": jnp.asarray(b)}


def _make(scn, *, bm, bn, bk):
    def f(x, packed):  # x: CHW
        s = scn.stride
        xs = x[:, ::s, ::s] if s > 1 else x
        y = matmul(packed["w"], xs.reshape(scn.c, -1), bm=bm, bn=bn, bk=bk)
        y = y.reshape(scn.m, scn.out_h, scn.out_w)
        return y + packed["b"][:, None, None]
    return f


def _fused(bm, bn, bk):
    mm = functools.partial(matmul, bm=bm, bn=bn, bk=bk)

    def build(scn, l_in, l_out):
        def f(x, packed):
            s = scn.stride
            w = packed["w"]  # (M, C)
            if l_in == "HWC":
                xs = x[::s, ::s, :] if s > 1 else x
                p = xs.reshape(-1, scn.c)  # (OHOW, C)
                if l_out == "HWC":
                    y = mm(p, w.T).reshape(scn.out_h, scn.out_w, scn.m)
                    return y + packed["b"]
                y = mm(p, w.T, out_layout="nm")
                return (y.reshape(scn.m, scn.out_h, scn.out_w)
                        + packed["b"][:, None, None])
            xs = x[:, ::s, ::s] if s > 1 else x
            p = xs.reshape(scn.c, -1)  # (C, OHOW)
            if l_out == "HWC":
                y = mm(w, p, out_layout="nm")
                return (y.reshape(scn.out_h, scn.out_w, scn.m)
                        + packed["b"])
            y = mm(w, p).reshape(scn.m, scn.out_h, scn.out_w)
            return y + packed["b"][:, None, None]
        return f
    return build


def _supports(bm, bn, bk):
    # every block axis is a lane axis in some layout variant (the (K, M)
    # prologue and (N, M) epilogue put M minor), so each must tile its
    # GEMM dim in whole 128-lane blocks or span it
    base = _sup(k_in=(1,))

    def supports(scn) -> bool:
        m, k = scn.m, scn.c
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


SPACE = TunableSpace(kernel="matmul", axes=AXES, valid=_valid,
                     make_primitive=_make_primitive)
