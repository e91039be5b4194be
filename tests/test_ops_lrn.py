"""The LRN op against a float64 across-channel LRN, in every layout.

Caffe's across-channel LRN: ``x / (bias + alpha/size * sum of x^2 over the
size channels centred on each one) ** beta``, the window zero-padded at
the channel edges.  ``lrn()`` computes the window sum as shifted slices
of the padded square; it must give the same result in each layout an op
node accepts, unbatched and under ``vmap``, and lower to no
``reduce_window``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import DEFAULT_OP_LAYOUTS, lrn
from repro.core.layouts import LAYOUT_BY_NAME

ALPHA, BETA, BIAS = 1e-4, 0.75, 1.0
H, W, BATCH = 7, 5, 8


def lrn_reference(x_chw: np.ndarray, size: int) -> np.ndarray:
    """Float64 across-channel LRN of a logical (..., C, H, W) array."""
    x = x_chw.astype(np.float64)
    c = x.shape[-3]
    half = size // 2
    pads = [(0, 0)] * x.ndim
    pads[-3] = (half, half)
    padded = np.pad(x * x, pads)
    s = sum(padded[..., i:i + c, :, :] for i in range(size))
    return x / (BIAS + (ALPHA / size) * s) ** BETA


def _run(x_chw: np.ndarray, layout_name: str, size: int,
         batched: bool) -> np.ndarray:
    """``lrn(size).fn`` on ``x_chw`` stored in ``layout_name``, back to CHW."""
    layout = LAYOUT_BY_NAME[layout_name]
    op = lrn(size=size, alpha=ALPHA, beta=BETA, bias=BIAS)
    def one(x):
        return op.fn([x], layout, None)
    if batched:
        fn = jax.jit(jax.vmap(one))
        x_mem = np.stack([layout.to_memory(xi) for xi in x_chw])
        y_mem = np.asarray(fn(jnp.asarray(x_mem)))
        return np.stack([layout.from_memory(yi) for yi in y_mem])
    y_mem = np.asarray(jax.jit(one)(jnp.asarray(layout.to_memory(x_chw))))
    return layout.from_memory(y_mem)


@pytest.mark.parametrize("c", [3, 64, 192])
@pytest.mark.parametrize("size", [3, 5])
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("layout_name", DEFAULT_OP_LAYOUTS)
def test_lrn_matches_float64(layout_name, batched, size, c):
    rng = np.random.default_rng(c * 10 + size)
    shape = ((BATCH,) if batched else ()) + (c, H, W)
    # wide enough that the window sum moves the denominator well off bias
    x = (100.0 * rng.standard_normal(shape)).astype(np.float32)
    y = _run(x, layout_name, size, batched)
    assert y.dtype == np.float32 and y.shape == shape
    np.testing.assert_allclose(y, lrn_reference(x, size), rtol=1e-5,
                               atol=0)
    if layout_name == "HWC" and not batched and size == 5 and c == 64:
        # a CHW input permuted to HWC, normalised, permuted back, is the
        # CHW result
        np.testing.assert_allclose(y, _run(x, "CHW", size, batched),
                                   rtol=1e-6, atol=0)


def test_lrn_lowers_to_no_reduce_window():
    """GoogLeNet's norm2 at batch 8: the channel window is slices, not a
    ``reduce_window`` (a separate, slow op on the TPU)."""
    layout = LAYOUT_BY_NAME["CHW"]
    op = lrn()
    fn = jax.vmap(lambda x: op.fn([x], layout, None))
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((8, 192, 56, 56), jnp.float32)).as_text()
    assert "reduce_window" not in text
    assert "slice" in text
