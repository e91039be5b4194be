"""jit'd wrapper for the direct NHWC Pallas convolution."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import pad_to
from .kernel import conv_direct_pallas


@functools.partial(jax.jit, static_argnames=("pad", "bm", "in_layout",
                                             "out_layout"))
def conv_direct(x, w, b, *, pad: int = 0, bm: int = 128,
                in_layout: str = "HWC", out_layout: str = "HWC"):
    """Stride-1 direct conv, layout-parameterized (transform fusion entry
    point).

    ``in_layout="HWC"``: x is (H, W, C); ``"CHW"``: x is (C, H, W) and
    the kernel prologue remaps it in VMEM.  ``out_layout`` selects
    (OH, OW, M) vs (M, OH, OW) — the CHW output is stored through the
    kernel's remapped epilogue BlockSpec.  w: (K, K, C, M); b: (M,).
    """
    if in_layout == "CHW":
        c, h, wd = x.shape
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)))
    else:
        h, wd, c = x.shape
        xp = jnp.pad(x, ((pad, pad), (pad, pad), (0, 0)))
    k, _, _, m = w.shape
    oh = h + 2 * pad - k + 1
    ow = wd + 2 * pad - k + 1
    bm_ = min(bm, max(8, m))
    wp, _ = pad_to(w, 3, bm_)
    bp, _ = pad_to(b, 0, bm_)
    out = conv_direct_pallas(xp, wp, bp, bm=bm_, in_layout=in_layout,
                             out_layout=out_layout)
    if out_layout == "CHW":
        return out[:m].reshape(m, oh, ow)
    return out[:, :m].reshape(oh, ow, m)
