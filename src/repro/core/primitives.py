"""The DNN primitive library: 70+ convolution routines in 6 families.

Section 4 of the paper.  Each primitive is a 3-tuple {L_in, P, L_out}
(input layout, routine, output layout) plus a ``supports`` predicate over
scenarios.  Families:

* ``direct``   — direct-loop methods (XLA native conv under various
                 dimension orders, textbook sum-of-single-channels,
                 shift-and-add loop nests, blocked-channel variants).
* ``im2``      — im2col/im2row: Toeplitz patch matrix + one GEMM.
* ``kn2``      — kn2row/kn2col (Vasudevan et al.): K^2 accumulating GEMMs,
                 low memory, stride-1 only.
* ``winograd`` — minimal-filtering F(m, r) for K in {3, 5}; 2-D nested and
                 the low-memory 1-D row-wise variants (the paper's
                 ARM-friendly selections); stride-1 only.
* ``fft``      — frequency-domain convolution; full 2-D and the
                 low-memory sum-of-1D-rows variant.
* ``pallas``   — TPU Pallas kernels (see repro/kernels/): MXU-tiled
                 im2col GEMM and direct conv.  Registered separately so
                 that CPU profiling can exclude them (they are priced by
                 the analytic TPU cost model instead).

Weight packing (kernel transforms, GEMM transposes, layout blocking) is
done once in ``prepare`` — it is deployment-time work, excluded from the
profiled runtime, exactly as the paper ships pre-packed weights.

Every primitive is validated against ``scenario.ref_conv`` over a sweep
of scenarios in tests/test_primitives.py.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .layouts import LAYOUT_BY_NAME, Layout
from .scenario import Scenario
from .winograd_transforms import winograd_matrices

__all__ = ["Primitive", "build_registry", "convert_layout", "registry",
           "FUSABLE_LAYOUTS", "register_extension", "unregister_extension",
           "clear_extensions", "extension_token",
           "invalidate_registry_cache"]

#: layouts the generic jnp prologue/epilogue wrapper can absorb — every
#: permutation layout plus the blocked HWC8 (whose feasibility is gated
#: per shape by ``layouts.transform_feasible`` at pricing time).
FUSABLE_LAYOUTS = ("CHW", "HWC", "HCW", "CWH", "WCH", "WHC", "HWC8")


# ----------------------------------------------------------------------
# layout conversion (jnp; used by the legalizer's conversion layers)
# ----------------------------------------------------------------------
def convert_layout(x, src: str, dst: str):
    """Convert activation tensor between memory layouts (traced, jnp)."""
    if src == dst:
        return x
    ls, ld = LAYOUT_BY_NAME[src], LAYOUT_BY_NAME[dst]
    # -> logical CHW
    if ls.block_c:
        cpos = ls.perm.index(0)
        x = jnp.moveaxis(x, -1, cpos + 1)
        shape = list(x.shape)
        shape[cpos:cpos + 2] = [shape[cpos] * shape[cpos + 1]]
        x = x.reshape(shape)
    x = jnp.transpose(x, np.argsort(ls.perm))
    # -> destination
    x = jnp.transpose(x, ld.perm)
    if ld.block_c:
        cpos = ld.perm.index(0)
        c = x.shape[cpos]
        shape = list(x.shape)
        shape[cpos:cpos + 1] = [c // ld.block_c, ld.block_c]
        x = x.reshape(shape)
        x = jnp.moveaxis(x, cpos + 1, -1)
    return x


def _from_chw(y_chw, dst: str):
    return convert_layout(y_chw, "CHW", dst)


def _to_chw(x, src: str):
    return convert_layout(x, src, "CHW")


# ----------------------------------------------------------------------
@dataclass(frozen=True)
class Primitive:
    """One routine in the library: {L_in, P, L_out} + applicability."""

    name: str
    family: str
    l_in: str
    l_out: str
    supports: Callable[[Scenario], bool]
    #: (scenario, w(M,C,K,K) np, b(M,) np) -> pytree of packed jnp arrays
    prepare: Callable[[Scenario, np.ndarray, np.ndarray], Any]
    #: scenario -> f(x_mem, packed) -> y_mem   (pure, jit-able)
    make: Callable[[Scenario], Callable]
    tags: Tuple[str, ...] = ()
    #: layouts the routine can consume *directly* in its prologue (fused
    #: read: no materialized DT round trip on the incoming edge)
    fusable_in: Tuple[str, ...] = ()
    #: layouts the routine can emit directly in its epilogue
    fusable_out: Tuple[str, ...] = ()
    #: optional custom fused builder ``(scn, l_in, l_out) -> f(x, packed)``
    #: — Pallas primitives install kernel variants whose BlockSpec index
    #: maps remap the grid (true in-kernel prologue/epilogue fusion);
    #: jnp primitives fall back to the generic wrapper below.
    fused: Optional[Callable] = None
    #: tuning parameters of a generated variant (sorted (name, value)
    #: pairs — hashable).  Empty for hand-written entries; the analytic
    #: TPU model prices tile quantization/alignment from these, and the
    #: autotune catalog round-trips them (see repro/autotune/).
    params: Tuple[Tuple[str, int], ...] = ()

    def make_fused(self, scn: Scenario, l_in: Optional[str] = None,
                   l_out: Optional[str] = None) -> Callable:
        """Entry point consuming ``l_in``-layout input and emitting
        ``l_out``-layout output (defaults: the native layouts).

        The generic path rewrites the conversion *inside* the primitive's
        call region: executed without an optimization barrier between the
        transform and the compute (see ``core.plan``), XLA folds the
        layout remap into the kernel's first read / last write instead of
        materializing an intermediate tensor through HBM.  Primitives
        with a custom ``fused`` builder get real in-kernel fusion.
        """
        li = l_in or self.l_in
        lo = l_out or self.l_out
        if li == self.l_in and lo == self.l_out:
            return self.make(scn)
        if li != self.l_in and li not in self.fusable_in:
            raise ValueError(f"{self.name}: cannot fuse input layout {li} "
                             f"(fusable_in={self.fusable_in})")
        if lo != self.l_out and lo not in self.fusable_out:
            raise ValueError(f"{self.name}: cannot fuse output layout {lo} "
                             f"(fusable_out={self.fusable_out})")
        if self.fused is not None:
            return self.fused(scn, li, lo)
        inner = self.make(scn)
        nat_in, nat_out = self.l_in, self.l_out

        def f(x, packed):
            if li != nat_in:
                x = convert_layout(x, li, nat_in)
            y = inner(x, packed)
            if lo != nat_out:
                y = convert_layout(y, nat_out, lo)
            return y

        return f

    def __repr__(self) -> str:  # pragma: no cover
        return f"<{self.family}:{self.name} {self.l_in}->{self.l_out}>"


def _std_prepare(scn: Scenario, w: np.ndarray, b: np.ndarray):
    return {"w": jnp.asarray(w), "b": jnp.asarray(b)}


def _any(scn: Scenario) -> bool:
    return True


def _stride1(scn: Scenario) -> bool:
    return scn.stride == 1


def _pad_chw(x, p):
    return jnp.pad(x, ((0, 0), (p, p), (p, p))) if p else x


# ======================================================================
# direct family
# ======================================================================
_DN_LHS = {"CHW": "NCHW", "HWC": "NHWC", "HCW": "NHCW"}


def _direct_lax(scn: Scenario, l_in: str, l_out: str, rhs_spec: str):
    dn = lax.conv_dimension_numbers(
        (1,) + tuple(LAYOUT_BY_NAME[l_in].to_memory(np.zeros(scn.in_shape_chw)).shape),
        scn.weight_shape if rhs_spec == "OIHW" else
        (scn.k, scn.k, scn.c, scn.m),
        (_DN_LHS[l_in], rhs_spec, _DN_LHS[l_out]),
    )

    def f(x, packed):
        lhs = x[None]
        out = lax.conv_general_dilated(
            lhs, packed["w"], (scn.stride, scn.stride),
            [(scn.pad, scn.pad)] * 2, dimension_numbers=dn)
        out = out[0]
        # add bias along the M axis of the output layout
        m_axis = _DN_LHS[l_out].index("C") - 1
        bshape = [1, 1, 1]
        bshape[m_axis] = scn.m
        return out + packed["b"].reshape(bshape)

    return f


def _direct_lax_prepare(rhs_spec):
    def prep(scn, w, b):
        if rhs_spec == "HWIO":
            w = np.transpose(w, (2, 3, 1, 0))
        return {"w": jnp.asarray(w), "b": jnp.asarray(b)}
    return prep


def _sum2d(scn: Scenario):
    """Textbook sum-of-single-channels: one 2-D conv per input channel,
    accumulated with a scan.  The paper's SUM2D baseline."""
    def f(x, packed):  # x: CHW
        w, b = packed["w"], packed["b"]  # (M, C, K, K)

        def body(acc, cw):
            xc, wc = cw  # (H, W), (M, K, K)
            out = lax.conv_general_dilated(
                xc[None, None], wc[:, None], (scn.stride, scn.stride),
                [(scn.pad, scn.pad)] * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            return acc + out[0], None

        init = jnp.zeros(scn.out_shape_chw, x.dtype)
        acc, _ = lax.scan(body, init, (x, jnp.swapaxes(w, 0, 1)))
        return acc + b[:, None, None]

    return f


def _sum1d(scn: Scenario):
    """Direct conv as a sum of 1-D row convolutions (textbook variant)."""
    def f(x, packed):  # CHW
        w, b = packed["w"], packed["b"]
        xp = _pad_chw(x, scn.pad)
        oh, ow = scn.out_h, scn.out_w
        acc = jnp.zeros((scn.m, oh, ow), x.dtype)
        for i in range(scn.k):
            rows = xp[:, i:i + (oh - 1) * scn.stride + 1:scn.stride, :]
            # 1-D correlation along W for kernel row i
            out = lax.conv_general_dilated(
                rows[None], w[:, :, i, :][..., None, :],
                (1, scn.stride), [(0, 0), (0, 0)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            acc = acc + out[0]
        return acc + b[:, None, None]

    return f


def _shift_add(scn: Scenario, layout: str, use_scan: bool,
               l_in: Optional[str] = None, l_out: Optional[str] = None):
    """Shift-and-add loop nest over the K x K kernel positions.

    ``l_in``/``l_out`` override the wire layouts (transform fusion);
    the CHW working layout means a CHW wire fuses for free.
    """
    l_in = l_in or layout
    l_out = l_out or layout

    def f(x, packed):
        w, b = packed["w"], packed["b"]  # (M, C, K, K)
        xc = _to_chw(x, l_in)
        xp = _pad_chw(xc, scn.pad)
        oh, ow, s = scn.out_h, scn.out_w, scn.stride

        if use_scan:
            kk = scn.k * scn.k
            wflat = w.reshape(scn.m, scn.c, kk)

            def body(acc, t):
                i, j = t // scn.k, t % scn.k
                win = lax.dynamic_slice(
                    xp, (0, i, j),
                    (scn.c, (oh - 1) * s + 1, (ow - 1) * s + 1))[:, ::s, ::s]
                return acc + jnp.einsum("mc,chw->mhw", wflat[:, :, t], win), None

            acc, _ = lax.scan(body, jnp.zeros((scn.m, oh, ow), x.dtype),
                              jnp.arange(kk))
        else:
            acc = jnp.zeros((scn.m, oh, ow), x.dtype)
            for i in range(scn.k):
                for j in range(scn.k):
                    win = xp[:, i:i + (oh - 1) * s + 1:s,
                             j:j + (ow - 1) * s + 1:s]
                    acc = acc + jnp.einsum("mc,chw->mhw", w[:, :, i, j], win)
        return _from_chw(acc + b[:, None, None], l_out)

    return f


def _blocked_hwc8(scn: Scenario):
    """Shift-add over a channel-blocked HWC8 tensor (vector-friendly)."""
    def f(x, packed):  # x: (H, W, C/8, 8)
        w, b = packed["w"], packed["b"]  # w: (M/8, 8, C/8, 8, K, K)
        p, s = scn.pad, scn.stride
        xp = jnp.pad(x, ((p, p), (p, p), (0, 0), (0, 0)))
        oh, ow = scn.out_h, scn.out_w
        acc = jnp.zeros((oh, ow, scn.m // 8, 8), x.dtype)
        for i in range(scn.k):
            for j in range(scn.k):
                win = xp[i:i + (oh - 1) * s + 1:s,
                         j:j + (ow - 1) * s + 1:s]
                acc = acc + jnp.einsum("hwcb,ndcb->hwnd", win, w[..., i, j])
        return acc + b.reshape(scn.m // 8, 8)

    return f


def _blocked_prepare(scn, w, b):
    wb = w.reshape(scn.m // 8, 8, scn.c // 8, 8, scn.k, scn.k)
    return {"w": jnp.asarray(wb), "b": jnp.asarray(b)}


# ======================================================================
# im2 family
# ======================================================================
def _patches_chw(x, scn: Scenario, method: str):
    """Toeplitz patch tensor (C, K, K, OH, OW) from logical CHW input."""
    if method == "xla":
        pt = lax.conv_general_dilated_patches(
            x[None], (scn.k, scn.k), (scn.stride, scn.stride),
            [(scn.pad, scn.pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
        return pt.reshape(scn.c, scn.k, scn.k, scn.out_h, scn.out_w)
    # manual: stack shifted strided slices
    xp = _pad_chw(x, scn.pad)
    oh, ow, s = scn.out_h, scn.out_w, scn.stride
    rows = []
    for i in range(scn.k):
        cols = []
        for j in range(scn.k):
            cols.append(xp[:, i:i + (oh - 1) * s + 1:s,
                           j:j + (ow - 1) * s + 1:s])
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)  # (C, K, K, OH, OW)


def _im2(scn: Scenario, l_in: str, l_out: str, method: str, trans_b: bool,
         split_c: int = 0):
    def f(x, packed):
        xc = _to_chw(x, l_in)
        pt = _patches_chw(xc, scn, method)  # (C, K, K, OH, OW)
        oh, ow = scn.out_h, scn.out_w
        if split_c:
            # low-memory: GEMM per channel chunk, accumulated
            csz = max(1, scn.c // split_c)
            acc = jnp.zeros((scn.m, oh * ow), x.dtype)
            wm = packed["w"]  # (M, C, K*K) or (C, K*K, M) if trans_b
            for c0 in range(0, scn.c, csz):
                p = pt[c0:c0 + csz].reshape(-1, oh * ow)
                if trans_b:
                    acc = acc + (p.T @ wm[c0:c0 + csz].reshape(-1, scn.m)).T
                else:
                    acc = acc + wm[:, c0:c0 + csz].reshape(scn.m, -1) @ p
            y = acc
        else:
            p = pt.reshape(scn.c * scn.k * scn.k, oh * ow)
            if trans_b:
                y = (p.T @ packed["w"]).T  # (CKK, M) weights
            else:
                y = packed["w"] @ p        # (M, CKK) weights
        y = y.reshape(scn.m, oh, ow) + packed["b"][:, None, None]
        return _from_chw(y, l_out)

    return f


def _im2_prepare(trans_b: bool, split_c: int = 0):
    def prep(scn, w, b):
        if split_c:
            wm = w.reshape(scn.m, scn.c, scn.k * scn.k)
            if trans_b:
                wm = np.transpose(wm, (1, 2, 0))  # (C, KK, M)
            return {"w": jnp.asarray(wm), "b": jnp.asarray(b)}
        wm = w.reshape(scn.m, -1)
        if trans_b:
            wm = wm.T.copy()
        return {"w": jnp.asarray(wm), "b": jnp.asarray(b)}
    return prep


def _im2row_hwc(scn: Scenario, l_out: str, method: str, trans_b: bool,
                l_in: str = "HWC"):
    """HWC-native im2row: patch rows (OH*OW, K*K*C) @ (K*K*C, M).

    ``l_in`` overrides the wire layout (transform fusion): a CHW wire
    skips the internal transpose and feeds the patch gather directly.
    """
    def f(x, packed):
        xc = _to_chw(x, l_in)
        pt = _patches_chw(xc, scn, method)  # (C, K, K, OH, OW)
        p = jnp.transpose(pt, (3, 4, 1, 2, 0)).reshape(
            scn.out_h * scn.out_w, -1)  # (OHOW, KKC)
        if trans_b:
            y = (packed["w"] @ p.T).T  # (M, KKC) @ (KKC, OHOW)
        else:
            y = p @ packed["w"]        # (KKC, M)
        y = y.reshape(scn.out_h, scn.out_w, scn.m) + packed["b"]
        if l_out == "HWC":
            return y
        return convert_layout(y, "HWC", l_out)

    return f


def _im2row_prepare(trans_b: bool):
    def prep(scn, w, b):
        wm = np.transpose(w, (2, 3, 1, 0)).reshape(-1, scn.m)  # (KKC, M)
        if trans_b:
            wm = wm.T.copy()
        return {"w": jnp.asarray(wm), "b": jnp.asarray(b)}
    return prep


# pointwise (K=1) GEMM specialisations
def _pw(scn: Scenario, layout: str, trans_b: bool):
    def f(x, packed):
        s = scn.stride
        if layout == "CHW":
            xs = x[:, ::s, ::s] if s > 1 else x
            p = xs.reshape(scn.c, -1)
            y = (p.T @ packed["w"]).T if trans_b else packed["w"] @ p
            y = y.reshape(scn.m, scn.out_h, scn.out_w) + packed["b"][:, None, None]
            return y
        elif layout == "HWC":
            xs = x[::s, ::s, :] if s > 1 else x
            p = xs.reshape(-1, scn.c)
            y = (packed["w"] @ p.T).T if trans_b else p @ packed["w"]
            return y.reshape(scn.out_h, scn.out_w, scn.m) + packed["b"]
        else:  # HCW
            xs = x[::s, :, ::s] if s > 1 else x
            y = jnp.einsum("hcw,cm->hmw", xs, packed["w"])
            return y + packed["b"][None, :, None]

    return f


def _pw_prepare(layout: str, trans_b: bool):
    def prep(scn, w, b):
        wm = w.reshape(scn.m, scn.c)
        if layout == "CHW":
            wm = wm.T.copy() if trans_b else wm
        elif layout == "HWC":
            wm = wm if trans_b else wm.T.copy()
        else:
            wm = wm.T.copy()
        return {"w": jnp.asarray(wm), "b": jnp.asarray(b)}
    return prep


# ======================================================================
# kn2 family (stride-1 only)
# ======================================================================
def _kn2(scn: Scenario, col: bool, mode: str,
         l_in: Optional[str] = None, l_out: Optional[str] = None):
    """kn2row / kn2col: one (M x C) GEMM per kernel position, shifted
    accumulation into the output.  Low memory, no Toeplitz matrix.

    ``l_in``/``l_out`` override the wire layouts (transform fusion): the
    prologue reads ``l_in`` directly — a CHW wire into kn2col skips the
    internal transpose entirely — and the epilogue emits ``l_out`` by
    retargeting the accumulation einsum where possible.
    """
    l_in = l_in or ("HWC" if col else "CHW")
    l_out = l_out or ("HWC" if col else "CHW")

    def f(x, packed):
        w, b = packed["w"], packed["b"]  # (K, K, M, C)
        xc = _to_chw(x, l_in)
        xp = _pad_chw(xc, scn.pad)
        oh, ow = scn.out_h, scn.out_w
        # the accumulation einsum can emit either HWC or CHW directly —
        # the epilogue-fusion lever; other layouts convert from CHW
        hwc_acc = l_out == "HWC"

        def one(i, j):
            win = xp[:, i:i + oh, j:j + ow]
            if hwc_acc:
                return jnp.einsum("chw,mc->hwm", win, w[i, j])
            return jnp.einsum("mc,chw->mhw", w[i, j], win)

        if mode == "scan":
            wflat = w.reshape(scn.k * scn.k, scn.m, scn.c)

            def body(acc, t):
                i, j = t // scn.k, t % scn.k
                win = lax.dynamic_slice(xp, (0, i, j), (scn.c, oh, ow))
                if hwc_acc:
                    return acc + jnp.einsum("chw,mc->hwm", win, wflat[t]), None
                return acc + jnp.einsum("mc,chw->mhw", wflat[t], win), None

            shape = (oh, ow, scn.m) if hwc_acc else (scn.m, oh, ow)
            acc, _ = lax.scan(body, jnp.zeros(shape, x.dtype),
                              jnp.arange(scn.k * scn.k))
        elif mode == "stack":
            parts = jnp.stack([one(i, j) for i in range(scn.k)
                               for j in range(scn.k)])
            acc = jnp.sum(parts, axis=0)
        else:  # unrolled accumulation
            acc = one(0, 0)
            for t in range(1, scn.k * scn.k):
                acc = acc + one(t // scn.k, t % scn.k)

        if hwc_acc:
            return acc + b
        return _from_chw(acc + b[:, None, None], l_out)

    return f


def _kn2_prepare(scn, w, b):
    return {"w": jnp.asarray(np.transpose(w, (2, 3, 0, 1)).copy()),
            "b": jnp.asarray(b)}


# ======================================================================
# winograd family (stride-1, K in {3, 5})
# ======================================================================
def _wino2d(scn: Scenario, m_: int, l_in: str, l_out: str):
    A, G, Bt = (jnp.asarray(t, jnp.float32)
                for t in winograd_matrices(m_, scn.k))
    a = m_ + scn.k - 1

    def f(x, packed):
        U = packed["w"]  # (M, C, a, a) transformed kernels
        xc = _to_chw(x, l_in)
        oh, ow = scn.out_h, scn.out_w
        nth, ntw = -(-oh // m_), -(-ow // m_)
        # pad so that tiles of alpha with stride m_ cover all outputs
        ph = (nth - 1) * m_ + a - (scn.h + 2 * scn.pad)
        pw = (ntw - 1) * m_ + a - (scn.w + 2 * scn.pad)
        xp = jnp.pad(xc, ((0, 0), (scn.pad, scn.pad + max(ph, 0)),
                          (scn.pad, scn.pad + max(pw, 0))))
        pt = lax.conv_general_dilated_patches(
            xp[None], (a, a), (m_, m_), [(0, 0), (0, 0)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
        d = pt.reshape(scn.c, a, a, nth, ntw)
        V = jnp.einsum("ai,cijtu,bj->cabtu", Bt, d, Bt)
        Q = jnp.einsum("mcab,cabtu->mabtu", U, V)
        Y = jnp.einsum("ap,mabtu,bq->mtpuq", A, Q, A)
        y = Y.reshape(scn.m, nth * m_, ntw * m_)[:, :oh, :ow]
        return _from_chw(y + packed["b"][:, None, None], l_out)

    return f


def _wino2d_prepare(m_: int):
    def prep(scn, w, b):
        A, G, Bt = winograd_matrices(m_, scn.k)
        U = np.einsum("ar,mcrs,bs->mcab", G, w, G)
        return {"w": jnp.asarray(U, jnp.float32), "b": jnp.asarray(b)}
    return prep


def _wino1d(scn: Scenario, m_: int, l_in: str, l_out: str):
    """Row-wise 1-D Winograd: F(m_, K) along W for each kernel row, with
    the K row contributions accumulated pre-output-transform.  Needs only
    O(alpha/m_) extra memory per row — the paper's ARM selections."""
    A, G, Bt = (jnp.asarray(t, jnp.float32)
                for t in winograd_matrices(m_, scn.k))
    a = m_ + scn.k - 1

    def f(x, packed):
        Ug = packed["w"]  # (K, M, C, a): per kernel row transformed taps
        xc = _to_chw(x, l_in)
        oh, ow = scn.out_h, scn.out_w
        ntw = -(-ow // m_)
        pw = (ntw - 1) * m_ + a - (scn.w + 2 * scn.pad)
        xp = jnp.pad(xc, ((0, 0), (scn.pad, scn.pad),
                          (scn.pad, scn.pad + max(pw, 0))))
        Q = jnp.zeros((scn.m, oh, ntw, a), x.dtype)
        for i in range(scn.k):
            rows = xp[:, i:i + oh, :]  # stride-1 only
            # tiles along W: (C, OH, ntw, a)
            idx = (jnp.arange(ntw)[:, None] * m_ + jnp.arange(a)[None, :])
            tiles = rows[:, :, idx]
            V = jnp.einsum("ab,chtb->chta", Bt, tiles)
            Q = Q + jnp.einsum("mca,chta->mhta", Ug[i], V)
        Y = jnp.einsum("ap,mhta->mhtp", A, Q)
        y = Y.reshape(scn.m, oh, ntw * m_)[:, :, :ow]
        return _from_chw(y + packed["b"][:, None, None], l_out)

    return f


def _wino1d_prepare(m_: int):
    def prep(scn, w, b):
        A, G, Bt = winograd_matrices(m_, scn.k)
        # (K rows, M, C, alpha)
        Ug = np.einsum("ar,mcir->imca", G, w)
        return {"w": jnp.asarray(Ug, jnp.float32), "b": jnp.asarray(b)}
    return prep


# ======================================================================
# fft family
# ======================================================================
def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _fft2d(scn: Scenario, l_in: str, l_out: str, pow2: bool,
           subsample: bool = False):
    def f(x, packed):
        Wf, b = packed["w"], packed["b"]
        xc = _to_chw(x, l_in)
        xp = _pad_chw(xc, scn.pad)
        hp, wp = xp.shape[1], xp.shape[2]
        fh, fw = hp + scn.k - 1, wp + scn.k - 1
        if pow2:
            fh, fw = _next_pow2(fh), _next_pow2(fw)
        Xf = jnp.fft.rfft2(xp, s=(fh, fw))
        Of = jnp.einsum("chw,mchw->mhw", Xf, Wf)
        of = jnp.fft.irfft2(Of, s=(fh, fw))
        full_oh = hp - scn.k + 1
        full_ow = wp - scn.k + 1
        y = of[:, scn.k - 1:scn.k - 1 + full_oh,
               scn.k - 1:scn.k - 1 + full_ow]
        if subsample and scn.stride > 1:
            y = y[:, ::scn.stride, ::scn.stride]
        y = y + b[:, None, None]
        return _from_chw(y.astype(x.dtype), l_out)

    return f


def _fft2d_prepare(pow2: bool):
    def prep(scn, w, b):
        hp, wp = scn.h + 2 * scn.pad, scn.w + 2 * scn.pad
        fh, fw = hp + scn.k - 1, wp + scn.k - 1
        if pow2:
            fh, fw = _next_pow2(fh), _next_pow2(fw)
        wf = np.fft.rfft2(w[:, :, ::-1, ::-1], s=(fh, fw))
        return {"w": jnp.asarray(wf), "b": jnp.asarray(b)}
    return prep


def _fft1d_sum(scn: Scenario, l_in: str, l_out: str, pow2: bool):
    """2-D conv as a sum of per-kernel-row 1-D FFT convolutions along W,
    accumulated in the frequency domain (the paper's low-memory variant)."""
    def f(x, packed):
        Wf, b = packed["w"], packed["b"]  # (K, M, C, F)
        xc = _to_chw(x, l_in)
        xp = _pad_chw(xc, scn.pad)
        wp = xp.shape[2]
        fw = wp + scn.k - 1
        if pow2:
            fw = _next_pow2(fw)
        oh = scn.out_h
        Of = None
        for i in range(scn.k):
            rows = xp[:, i:i + oh, :]
            Rf = jnp.fft.rfft(rows, n=fw, axis=-1)  # (C, OH, F)
            term = jnp.einsum("chf,mcf->mhf", Rf, Wf[i])
            Of = term if Of is None else Of + term
        of = jnp.fft.irfft(Of, n=fw, axis=-1)
        y = of[:, :, scn.k - 1:scn.k - 1 + scn.out_w]
        return _from_chw(y.astype(x.dtype) + b[:, None, None], l_out)

    return f


def _fft1d_prepare(pow2: bool):
    def prep(scn, w, b):
        wp = scn.w + 2 * scn.pad
        fw = wp + scn.k - 1
        if pow2:
            fw = _next_pow2(fw)
        wf = np.fft.rfft(w[:, :, :, ::-1], n=fw, axis=-1)  # (M, C, K, F)
        wf = np.transpose(wf, (2, 0, 1, 3)).copy()  # (K, M, C, F)
        return {"w": jnp.asarray(wf), "b": jnp.asarray(b)}
    return prep


# ======================================================================
# registry construction
# ======================================================================
def _sup(k_in=None, stride1=False, blocked=False, kmin_hw=True):
    def s(scn: Scenario) -> bool:
        if k_in is not None and scn.k not in k_in:
            return False
        if stride1 and scn.stride != 1:
            return False
        if blocked and (scn.c % 8 or scn.m % 8):
            return False
        if kmin_hw and (scn.h + 2 * scn.pad < scn.k or
                        scn.w + 2 * scn.pad < scn.k):
            return False
        return True
    return s


@functools.lru_cache(maxsize=1)
def build_registry() -> Tuple[Primitive, ...]:
    prims: List[Primitive] = []

    def add(name, family, l_in, l_out, supports, prepare, make, tags=(),
            fusable_in=FUSABLE_LAYOUTS, fusable_out=FUSABLE_LAYOUTS,
            fused=None):
        prims.append(Primitive(name, family, l_in, l_out, supports,
                               prepare, make, tuple(tags),
                               tuple(fusable_in), tuple(fusable_out),
                               fused))

    # ---------------- direct ----------------
    # direct_lax is natively layout-parameterized: a fused edge simply
    # rebuilds the conv with dimension_numbers matching the wire layout
    # — the operator consumes/emits it directly, no transpose op at all
    def _lax_fused(rhs):
        return lambda scn, li, lo: _direct_lax(scn, li, lo, rhs)

    for l_in, l_out in [("CHW", "CHW"), ("HWC", "HWC"), ("CHW", "HWC"),
                        ("HWC", "CHW"), ("HCW", "HCW")]:
        for rhs in (["OIHW", "HWIO"] if l_in in ("CHW", "HWC") else ["OIHW"]):
            add(f"direct_lax_{l_in.lower()}_{l_out.lower()}_{rhs.lower()}",
                "direct", l_in, l_out, _sup(),
                _direct_lax_prepare(rhs),
                functools.partial(_direct_lax, l_in=l_in, l_out=l_out,
                                  rhs_spec=rhs),
                fusable_in=tuple(_DN_LHS), fusable_out=tuple(_DN_LHS),
                fused=_lax_fused(rhs))
    def _shift_fused(layout, use_scan):
        return lambda scn, li, lo: _shift_add(scn, layout, use_scan,
                                              l_in=li, l_out=lo)

    add("sum2d", "direct", "CHW", "CHW", _sup(), _std_prepare, _sum2d,
        tags=("baseline",))
    add("sum1d", "direct", "CHW", "CHW", _sup(), _std_prepare, _sum1d)
    for layout in ["CHW", "HWC", "HCW"]:
        add(f"direct_shiftadd_{layout.lower()}", "direct", layout, layout,
            _sup(), _std_prepare,
            functools.partial(_shift_add, layout=layout, use_scan=False),
            fused=_shift_fused(layout, False))
    for layout in ["CHW", "HWC"]:
        add(f"direct_shiftscan_{layout.lower()}", "direct", layout, layout,
            _sup(), _std_prepare,
            functools.partial(_shift_add, layout=layout, use_scan=True),
            fused=_shift_fused(layout, True))
    add("direct_blocked_hwc8", "direct", "HWC8", "HWC8",
        _sup(blocked=True), _blocked_prepare, _blocked_hwc8)

    # ---------------- im2 ----------------
    def _im2_fused(method, trans_b, split_c=0):
        return lambda scn, li, lo: _im2(scn, li, lo, method, trans_b,
                                        split_c)

    def _im2row_fused(method, trans_b):
        return lambda scn, li, lo: _im2row_hwc(scn, lo, method, trans_b,
                                               l_in=li)

    for method in ["xla", "manual"]:
        for trans_b in [False, True]:
            t = "t" if trans_b else "n"
            add(f"im2col_{method}_{t}_chw", "im2", "CHW", "CHW", _sup(),
                _im2_prepare(trans_b),
                functools.partial(_im2, l_in="CHW", l_out="CHW",
                                  method=method, trans_b=trans_b),
                fused=_im2_fused(method, trans_b))
            add(f"im2row_{method}_{t}_hwc", "im2", "HWC", "HWC", _sup(),
                _im2row_prepare(trans_b),
                functools.partial(_im2row_hwc, l_out="HWC", method=method,
                                  trans_b=trans_b),
                fused=_im2row_fused(method, trans_b))
    add("im2col_xla_n_chw_hwc", "im2", "CHW", "HWC", _sup(),
        _im2_prepare(False),
        functools.partial(_im2, l_in="CHW", l_out="HWC", method="xla",
                          trans_b=False),
        fused=_im2_fused("xla", False))
    add("im2row_xla_n_hwc_chw", "im2", "HWC", "CHW", _sup(),
        _im2row_prepare(False),
        functools.partial(_im2row_hwc, l_out="CHW", method="xla",
                          trans_b=False),
        fused=_im2row_fused("xla", False))
    for split in [4, 8]:
        add(f"im2col_split{split}_chw", "im2", "CHW", "CHW", _sup(),
            _im2_prepare(False, split_c=split),
            functools.partial(_im2, l_in="CHW", l_out="CHW", method="xla",
                              trans_b=False, split_c=split),
            tags=("lowmem",), fused=_im2_fused("xla", False, split))
    # pointwise K=1 GEMM specialisations
    for layout in ["CHW", "HWC"]:
        for trans_b in [False, True]:
            t = "t" if trans_b else "n"
            add(f"pw_gemm_{t}_{layout.lower()}", "im2", layout, layout,
                _sup(k_in=(1,)), _pw_prepare(layout, trans_b),
                functools.partial(_pw, layout=layout, trans_b=trans_b))
    add("pw_gemm_n_hcw", "im2", "HCW", "HCW", _sup(k_in=(1,)),
        _pw_prepare("HCW", False),
        functools.partial(_pw, layout="HCW", trans_b=False))

    # ---------------- kn2 ----------------
    def _kn2_fused(col, mode):
        return lambda scn, li, lo: _kn2(scn, col, mode, l_in=li, l_out=lo)

    for col, layout in [(False, "CHW"), (True, "HWC")]:
        nm = "kn2col" if col else "kn2row"
        for mode in ["unroll", "scan", "stack"]:
            add(f"{nm}_{mode}_{layout.lower()}", "kn2", layout, layout,
                _sup(stride1=True), _kn2_prepare,
                functools.partial(_kn2, col=col, mode=mode),
                tags=("lowmem",) if mode != "stack" else (),
                fused=_kn2_fused(col, mode))

    # ---------------- winograd ----------------
    def _wino2d_fused(m_):
        return lambda scn, li, lo: _wino2d(scn, m_, li, lo)

    def _wino1d_fused(m_):
        return lambda scn, li, lo: _wino1d(scn, m_, li, lo)

    for m_ in [2, 4, 6]:
        for layout in ["CHW", "HWC"]:
            for k in ([3, 5] if m_ != 6 else [3]):
                add(f"wino2d_f{m_}x{k}_{layout.lower()}", "winograd",
                    layout, layout, _sup(k_in=(k,), stride1=True),
                    _wino2d_prepare(m_),
                    functools.partial(_wino2d, m_=m_, l_in=layout,
                                      l_out=layout),
                    fused=_wino2d_fused(m_))
    for m_ in [2, 4]:
        for layout in ["CHW", "HWC"]:
            for k in [3, 5]:
                add(f"wino1d_f{m_}x{k}_{layout.lower()}", "winograd",
                    layout, layout, _sup(k_in=(k,), stride1=True),
                    _wino1d_prepare(m_),
                    functools.partial(_wino1d, m_=m_, l_in=layout,
                                      l_out=layout),
                    tags=("lowmem",), fused=_wino1d_fused(m_))

    # ---------------- fft ----------------
    def _fft2d_fused(pow2, subsample=False):
        return lambda scn, li, lo: _fft2d(scn, li, lo, pow2, subsample)

    def _fft1d_fused(pow2):
        return lambda scn, li, lo: _fft1d_sum(scn, li, lo, pow2)

    for layout in ["CHW", "HWC"]:
        for pow2 in [False, True]:
            p = "p2" if pow2 else "ex"
            add(f"fft2d_{p}_{layout.lower()}", "fft", layout, layout,
                _sup(stride1=True), _fft2d_prepare(pow2),
                functools.partial(_fft2d, l_in=layout, l_out=layout,
                                  pow2=pow2),
                fused=_fft2d_fused(pow2))
            add(f"fft1d_sum_{p}_{layout.lower()}", "fft", layout, layout,
                _sup(stride1=True), _fft1d_prepare(pow2),
                functools.partial(_fft1d_sum, l_in=layout, l_out=layout,
                                  pow2=pow2),
                tags=("lowmem",), fused=_fft1d_fused(pow2))
    add("fft2d_strided_chw", "fft", "CHW", "CHW", _sup(), _fft2d_prepare(False),
        functools.partial(_fft2d, l_in="CHW", l_out="CHW", pow2=False,
                          subsample=True),
        fused=_fft2d_fused(False, True))

    # ---------------- pallas (TPU kernels; analytic costs) ----------------
    from ..kernels import register_pallas_primitives
    register_pallas_primitives(add, _sup)

    names = [p.name for p in prims]
    assert len(names) == len(set(names)), "duplicate primitive names"
    return tuple(prims)


# ----------------------------------------------------------------------
# registry extensions + memoization
#
# ``registry()`` is on the hot path of every solve (``primitives_for``
# walks it once per node), so the base + extension concatenation is
# memoized; mutators below invalidate explicitly.  Extensions are how
# the autotuner (repro/autotune/) registers generated Pallas variants as
# first-class primitives without rebuilding the hand-written library.
# ----------------------------------------------------------------------
_REG_LOCK = threading.Lock()
#: name -> (primitives, token); token feeds CostModel.version() so
#: installing/removing an extension rotates every cached plan key.
_EXTENSIONS: Dict[str, Tuple[Tuple[Primitive, ...], str]] = {}
_REG_CACHE: Optional[Tuple[Primitive, ...]] = None


def invalidate_registry_cache() -> None:
    """Drop the memoized registry; next ``registry()`` rebuilds it."""
    global _REG_CACHE
    with _REG_LOCK:
        _REG_CACHE = None


def register_extension(name: str, prims: Sequence[Primitive],
                       token: str = "") -> None:
    """Install (or replace) an extension set of primitives.

    ``token`` should digest the extension's content (the autotuner
    passes the variant catalog's content hash): it is folded into
    ``extension_token()`` and hence every ``CostModel.version()``, so
    plans cached against a different variant set can never be served.
    """
    prims = tuple(prims)
    with _REG_LOCK:
        base_names = {p.name for p in build_registry()}
        for other, (ps, _) in _EXTENSIONS.items():
            if other != name:
                base_names.update(p.name for p in ps)
        names = [p.name for p in prims]
        dup = (set(names) & base_names) or \
            {n for n in names if names.count(n) > 1}
        if dup:
            raise ValueError(f"extension {name!r}: duplicate primitive "
                             f"names {sorted(dup)}")
        _EXTENSIONS[name] = (prims, str(token))
        global _REG_CACHE
        _REG_CACHE = None


def unregister_extension(name: str) -> bool:
    """Remove one extension; returns whether it was installed."""
    with _REG_LOCK:
        found = _EXTENSIONS.pop(name, None) is not None
        if found:
            global _REG_CACHE
            _REG_CACHE = None
        return found


def clear_extensions() -> None:
    """Remove every extension (tests; serve-path reset)."""
    with _REG_LOCK:
        _EXTENSIONS.clear()
        global _REG_CACHE
        _REG_CACHE = None


def extension_token() -> str:
    """Digest of the installed extensions (empty string when none).

    Folded into ``CostModel.version()`` (see ``core.costs``): the plan
    cache key moves whenever the variant set changes.
    """
    if not _EXTENSIONS:
        return ""
    return ";".join(f"{n}:{_EXTENSIONS[n][1] or len(_EXTENSIONS[n][0])}"
                    for n in sorted(_EXTENSIONS))


def registry() -> Tuple[Primitive, ...]:
    """The full primitive library: hand-written base + extensions."""
    global _REG_CACHE
    cache = _REG_CACHE
    if cache is None:
        with _REG_LOCK:
            cache = _REG_CACHE
            if cache is None:
                ext = tuple(p for n in sorted(_EXTENSIONS)
                            for p in _EXTENSIONS[n][0])
                cache = _REG_CACHE = build_registry() + ext
    return cache


def primitives_for(scn: Scenario,
                   families: Optional[Sequence[str]] = None,
                   exclude_tags: Sequence[str] = ()) -> List[Primitive]:
    out = []
    for p in registry():
        if families and p.family not in families:
            continue
        if any(t in p.tags for t in exclude_tags):
            continue
        if p.supports(scn):
            out.append(p)
    return out
