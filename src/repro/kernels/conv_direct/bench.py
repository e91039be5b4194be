"""Calibration benchmark entry for the direct NHWC Pallas convolution."""
from __future__ import annotations

import numpy as np

from ...core.scenario import Scenario
from .kernel import fits_vmem


def benchmark_entry(scn: Scenario):
    """Zero-arg builder timing ``conv_direct`` at this scenario, or None.

    The builder defers tensor allocation and jit to measurement time so
    sweep planning (and ``--dry-run``) stays free.
    """
    if (scn.stride != 1 or scn.h + 2 * scn.pad < scn.k
            or scn.w + 2 * scn.pad < scn.k or not fits_vmem(scn)):
        return None

    def build():
        import jax.numpy as jnp

        from .ops import conv_direct
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(scn.h, scn.w, scn.c)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(scn.k, scn.k, scn.c, scn.m)) * 0.1,
                        jnp.float32)
        b = jnp.asarray(rng.normal(size=(scn.m,)), jnp.float32)
        fn = lambda x, w, b: conv_direct(x, w, b, pad=scn.pad)
        return fn, (x, w, b)

    return build
