"""Calibration benchmark entry for the tiled layout-transform kernels."""
from __future__ import annotations

import numpy as np

from ...core.scenario import Scenario


def benchmark_entry(scn: Scenario):
    """Zero-arg builder timing the tiled CHW->HWC transpose on the
    scenario's input tensor."""
    def build():
        import jax.numpy as jnp

        from .ops import chw_to_hwc
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=scn.in_shape_chw), jnp.float32)
        return chw_to_hwc, (x,)

    return build
