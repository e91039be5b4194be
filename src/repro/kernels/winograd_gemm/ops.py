"""Full Winograd F(m, 3) convolution with the Pallas batched-GEMM core."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...core.winograd_transforms import winograd_matrices
from ..common import pad_to
from .kernel import winograd_bgemm_pallas


def prepare_kernel(w, m_: int = 2):
    """Offline kernel transform: (M, C, K, K) -> (alpha^2, M, C)."""
    mm, c, k, _ = w.shape
    A, G, Bt = winograd_matrices(m_, k)
    U = np.einsum("ar,mcrs,bs->abmc", G, np.asarray(w), G)
    return jnp.asarray(U.reshape((m_ + k - 1) ** 2, mm, c), jnp.float32)


@functools.partial(jax.jit, static_argnames=("m_", "k", "stride", "pad",
                                             "bn", "bc", "in_layout",
                                             "out_layout"))
def conv_winograd(x, u, b, *, m_: int = 2, k: int = 3, stride: int = 1,
                  pad: int = 1, bn: int = 128, bc: int = 128,
                  in_layout: str = "CHW", out_layout: str = "CHW"):
    """x: (C, H, W); u: prepared kernels (alpha^2, M, C); b: (M,).

    Returns (M, OH, OW).  stride must be 1 (Winograd restriction).

    Layout-parameterized (transform fusion): ``in_layout="HWC"`` feeds
    the transpose straight into the input-transform patch gather (XLA
    fuses it — the transforms are already XLA-side by design);
    ``out_layout="HWC"`` reorders the *output transform's* einsum so the
    inverse transform itself emits (OH, OW, M) — the epilogue produces
    the consumer's layout with no extra pass over the output.
    """
    assert stride == 1
    assert in_layout in ("CHW", "HWC") and out_layout in ("CHW", "HWC")
    if in_layout == "HWC":
        x = jnp.transpose(x, (2, 0, 1))
    c, h, wd = x.shape
    _, m, _ = u.shape
    a = m_ + k - 1
    A, G, Bt = (jnp.asarray(t, jnp.float32) for t in winograd_matrices(m_, k))
    oh, ow = h + 2 * pad - k + 1, wd + 2 * pad - k + 1
    nth, ntw = -(-oh // m_), -(-ow // m_)
    ph = (nth - 1) * m_ + a - (h + 2 * pad)
    pw = (ntw - 1) * m_ + a - (wd + 2 * pad)
    xp = jnp.pad(x, ((0, 0), (pad, pad + max(ph, 0)),
                     (pad, pad + max(pw, 0))))
    pt = lax.conv_general_dilated_patches(
        xp[None], (a, a), (m_, m_), [(0, 0), (0, 0)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
    d = pt.reshape(c, a, a, nth * ntw)
    # F(4, 3)'s transforms amplify rounding ~15x more than F(2, 3)'s
    # (measured on a TPU v5e at GoogLeNet conv2 against a float32
    # reference: 8.5e-2 vs 5.9e-3 of max|y| with bfloat16 MXU passes),
    # so m >= 4 runs its transforms and tile products in float32
    prec = lax.Precision.HIGHEST if m_ >= 4 else None
    V = jnp.einsum("ai,ciju,bj->abcu", Bt, d, Bt,
                   precision=prec).reshape(a * a, c, -1)

    n = nth * ntw
    bc_ = min(bc, max(8, c))
    bn_ = min(bn, max(8, n))
    Vp, _ = pad_to(V, 1, bc_)
    Vp, _ = pad_to(Vp, 2, bn_)
    Up, _ = pad_to(u, 2, bc_)
    Q = winograd_bgemm_pallas(Up, Vp, bn=bn_, bc=bc_,
                              precision=prec)[:, :, :n]

    Q = Q.reshape(a, a, m, nth, ntw)
    if out_layout == "HWC":
        Y = jnp.einsum("ap,abmtu,bq->tpuqm", A, Q, A, precision=prec)
        y = Y.reshape(nth * m_, ntw * m_, m)[:oh, :ow, :]
        return y + b
    Y = jnp.einsum("ap,abmtu,bq->mtpuq", A, Q, A, precision=prec)
    y = Y.reshape(m, nth * m_, ntw * m_)[:, :oh, :ow]
    return y + b[:, None, None]
