"""jit'd wrappers for the tiled layout-transform kernels (any shape)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import pad_to
from .kernel import chw_to_hwc_pallas, hwc_to_chw_pallas


@jax.jit
def chw_to_hwc(x):
    c, h, w = x.shape
    bh = 8 if h >= 8 else h
    bw = 128 if w >= 128 else w
    xp, _ = pad_to(x, 1, bh)
    xp, _ = pad_to(xp, 2, bw)
    return chw_to_hwc_pallas(xp, bh=bh, bw=bw)[:h, :w, :]


@jax.jit
def hwc_to_chw(x):
    h, w, c = x.shape
    bh = 8 if h >= 8 else h
    bw = 128 if w >= 128 else w
    xp, _ = pad_to(x, 0, bh)
    xp, _ = pad_to(xp, 1, bw)
    return hwc_to_chw_pallas(xp, bh=bh, bw=bw)[:, :h, :w]


#: direct tiled kernels by (src, dst) layout-name pair
_DIRECT = {
    ("CHW", "HWC"): chw_to_hwc,
    ("HWC", "CHW"): hwc_to_chw,
}


def convert(x, src: str, dst: str):
    """Layout-parameterized entry point: tiled one-shot transform when a
    direct kernel exists for (src, dst), traced ``convert_layout``
    otherwise — callers get the best available route without caring
    which pairs have dedicated kernels."""
    if src == dst:
        return x
    fn = _DIRECT.get((src, dst))
    if fn is not None:
        return fn(x)
    from ...core.primitives import convert_layout
    return convert_layout(x, src, dst)
