"""Pallas TPU kernels for the performance hot-spots.

Each kernel lives in its own subpackage with:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — jit'd general wrapper (padding, batching)
  ref.py    — pure-jnp oracle used by the allclose tests
  bench.py  — ``benchmark_entry(scn)``: the calibration sweep hook
              (repro.calibrate.sweep) — returns a zero-arg builder
              producing a ``(fn, args)`` timing closure at the
              scenario's tensor sizes, or None when unsupported

``register_pallas_primitives`` plugs the convolution kernels into the
paper's primitive registry as the ``pallas`` family; they are tagged
``tpu-only`` so the CPU profiler skips them (the analytic TPU cost model
prices them instead).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np


def register_pallas_primitives(add, _sup) -> None:
    from . import conv_direct, conv_im2col, winograd_gemm
    from .matmul import ops as mm_ops

    # ---- direct NHWC ----
    def direct_prepare(scn, w, b):
        return {"w": jnp.asarray(np.transpose(w, (2, 3, 1, 0)).copy()),
                "b": jnp.asarray(b)}

    def direct_make(scn):
        def f(x, packed):  # x: HWC
            return conv_direct.conv_direct(x, packed["w"], packed["b"],
                                           pad=scn.pad)
        return f

    def direct_fused(scn, l_in, l_out):
        # in-kernel prologue/epilogue: the CHW strip is transposed while
        # VMEM-resident and CHW output is stored through a remapped out
        # BlockSpec (see kernels/conv_direct/kernel.py)
        def f(x, packed):
            return conv_direct.conv_direct(
                x, packed["w"], packed["b"], pad=scn.pad, in_layout=l_in,
                out_layout=l_out)
        return f

    # stride 1 and VMEM-resident blocks: where the kernel compiles
    direct_sup = _sup(stride1=True)
    add("pallas_direct_hwc", "pallas", "HWC", "HWC",
        lambda s: direct_sup(s) and conv_direct.fits_vmem(s),
        direct_prepare, direct_make,
        tags=("tpu-only",), fusable_in=("CHW",), fusable_out=("CHW",),
        fused=direct_fused)

    # ---- im2col GEMM ----
    def im2_prepare(scn, w, b):
        return {"w": jnp.asarray(w), "b": jnp.asarray(b)}

    def im2_make(scn):
        def f(x, packed):  # x: CHW
            return conv_im2col.conv_im2col(
                x, packed["w"], packed["b"], stride=scn.stride, pad=scn.pad)
        return f

    def im2_fused(scn, l_in, l_out):
        # HWC input feeds the Toeplitz gather directly; HWC output runs
        # the GEMM with the transposed-output epilogue BlockSpec
        def f(x, packed):
            return conv_im2col.conv_im2col(
                x, packed["w"], packed["b"], stride=scn.stride,
                pad=scn.pad, in_layout=l_in, out_layout=l_out)
        return f

    add("pallas_im2col_chw", "pallas", "CHW", "CHW", _sup(),
        im2_prepare, im2_make, tags=("tpu-only",),
        fusable_in=("HWC",), fusable_out=("HWC",), fused=im2_fused)

    # ---- winograd F(2,3)/F(4,3) ----
    for m_ in (2, 4):
        def wino_prepare(scn, w, b, m_=m_):
            return {"u": winograd_gemm.prepare_kernel(w, m_),
                    "b": jnp.asarray(b)}

        def wino_make(scn, m_=m_):
            def f(x, packed):  # x: CHW
                return winograd_gemm.conv_winograd(
                    x, packed["u"], packed["b"], m_=m_, k=scn.k,
                    stride=scn.stride, pad=scn.pad)
            return f

        def wino_fused(scn, l_in, l_out, m_=m_):
            # the inverse output transform emits HWC itself (reordered
            # einsum) — epilogue fusion with zero extra passes
            def f(x, packed):
                return winograd_gemm.conv_winograd(
                    x, packed["u"], packed["b"], m_=m_, k=scn.k,
                    stride=scn.stride, pad=scn.pad, in_layout=l_in,
                    out_layout=l_out)
            return f

        add(f"pallas_wino_f{m_}x3_chw", "pallas", "CHW", "CHW",
            _sup(k_in=(3,), stride1=True), wino_prepare, wino_make,
            tags=("tpu-only",), fusable_in=("HWC",), fusable_out=("HWC",),
            fused=wino_fused)

    # ---- pointwise (K=1) MXU GEMM ----
    def pw_prepare(scn, w, b):
        return {"w": jnp.asarray(w.reshape(scn.m, scn.c)),
                "b": jnp.asarray(b)}

    def pw_make(scn):
        def f(x, packed):  # x: CHW
            s = scn.stride
            xs = x[:, ::s, ::s] if s > 1 else x
            y = mm_ops.matmul(packed["w"], xs.reshape(scn.c, -1))
            y = y.reshape(scn.m, scn.out_h, scn.out_w)
            return y + packed["b"][:, None, None]
        return f

    def pw_fused(scn, l_in, l_out):
        # the GEMM kernel's layout-parameterized entry points absorb
        # both ends: an HWC input is consumed as the (OHOW, C) LHS and
        # an HWC output is emitted via the transposed-output epilogue —
        # no standalone transpose in any combination
        def f(x, packed):
            s = scn.stride
            w = packed["w"]  # (M, C)
            if l_in == "HWC":
                xs = x[::s, ::s, :] if s > 1 else x
                p = xs.reshape(-1, scn.c)  # (OHOW, C)
                if l_out == "HWC":
                    y = mm_ops.matmul(p, w.T)          # (OHOW, M)
                    y = y.reshape(scn.out_h, scn.out_w, scn.m)
                    return y + packed["b"]
                y = mm_ops.matmul(p, w.T, out_layout="nm")  # (M, OHOW)
                y = y.reshape(scn.m, scn.out_h, scn.out_w)
                return y + packed["b"][:, None, None]
            xs = x[:, ::s, ::s] if s > 1 else x
            p = xs.reshape(scn.c, -1)  # (C, OHOW)
            if l_out == "HWC":
                y = mm_ops.matmul(w, p, out_layout="nm")   # (OHOW, M)
                y = y.reshape(scn.out_h, scn.out_w, scn.m)
                return y + packed["b"]
            y = mm_ops.matmul(w, p).reshape(scn.m, scn.out_h, scn.out_w)
            return y + packed["b"][:, None, None]
        return f

    add("pallas_pw_gemm_chw", "pallas", "CHW", "CHW", _sup(k_in=(1,)),
        pw_prepare, pw_make, tags=("tpu-only",),
        fusable_in=("HWC",), fusable_out=("HWC",), fused=pw_fused)
