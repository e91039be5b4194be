"""Shared helpers for the Pallas TPU kernels.

All kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling,
MXU-aligned block shapes) and validated on CPU via interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def use_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode here.

    On a TPU they compile through Mosaic; on the CPU, where the tests
    run, interpret mode executes their bodies in Python.  Any other
    platform has neither, and raises rather than interpret silently.
    """
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on a TPU, or interpreted on "
                       f"the CPU; this process's devices are {platform!r}")


def pad_to(x, axis: int, multiple: int, value=0.0):
    """Pad ``axis`` of x up to a multiple; returns (padded, orig_size)."""
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), n


def lane_block_ok(block: int, dim: int) -> bool:
    """Whether a block may tile ``dim`` as a block's minor (lane) axis.

    Mosaic needs it to be a multiple of 128 lanes or to span the whole
    axis.  ``block`` is the requested size before the ops wrappers'
    clamp ``min(block, max(8, dim))``: a clamped block spans its axis.
    """
    b = min(block, max(8, dim))
    return b % 128 == 0 or b >= dim


def cdiv(a: int, b: int) -> int:
    return -(-a // b)
