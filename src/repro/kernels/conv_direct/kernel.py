"""Direct NHWC convolution Pallas kernel.

TPU adaptation of the direct-loop family: instead of a 6-deep scalar
loop nest (CPU) the kernel keeps the input strip in VMEM and performs
one MXU matmul per kernel tap: for each (i, j) in K x K the shifted
(OH*OW, C) window is multiplied with the (C, bm) weight slice and
accumulated in an f32 VMEM scratch.  Grid is over output-channel tiles
(bm, MXU-lane aligned).  The whole strip must fit the kernel's scoped
VMEM: :func:`fits_vmem` is the check the registry's ``supports()`` runs.

Stride 1 only: Mosaic cannot lower a strided slice of a VMEM value
(``vector.extract_strided_slice`` takes unit strides), so strided layers
go to the other families.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret


#: scoped VMEM Mosaic grants one kernel on a TPU v5e unless the call
#: asks for more (the chip holds 128 MiB)
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _tile_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of an f32 buffer: its two minor dims pad to (8, 128)."""
    *lead, sub, lane = shape
    return (math.prod(lead) * (-(-sub // 8) * 8) * (-(-lane // 128) * 128)
            * itemsize)


def vmem_bytes(hp: int, wp: int, c: int, k: int, bm: int,
               out_layout: str = "HWC") -> int:
    """VMEM one grid step of :func:`conv_direct_pallas` holds, counted
    with the HWC strip.

    Counts the double-buffered blocks (input strip, (K, K, C, bm) weights,
    bias, output tile), the f32 accumulator scratch, and the body's
    values: the loaded strip, one tap window, one tap product and a
    transposed output.  Against the compiler's own count this runs ~6%
    high (VGG conv3: 22.3 MiB estimated, 21.0 MiB reported).  A strip
    that arrives CHW through the fused prologue compiled wherever the
    HWC strip fits, on every stride-1 layer of AlexNet, VGG-A and
    GoogLeNet.
    """
    ohow = (hp - k + 1) * (wp - k + 1)
    strip = _tile_bytes((hp, wp, c))
    acc = _tile_bytes((ohow, bm))
    o_blk = _tile_bytes((bm, ohow)) if out_layout == "CHW" else acc
    blocks = 2 * (strip + _tile_bytes((k, k, c, bm)) + _tile_bytes((1, bm))
                  + o_blk)
    values = (strip + _tile_bytes((ohow, c)) + acc
              + (o_blk if out_layout == "CHW" else 0))
    return blocks + acc + values


def fits_vmem(scn, bm: int = 128) -> bool:
    """Whether the kernel fits the scoped VMEM at this scenario, in either
    output layout the registry may fuse onto it."""
    bm = min(bm, max(8, scn.m))  # the clamp conv_direct applies
    hp, wp = scn.h + 2 * scn.pad, scn.w + 2 * scn.pad
    return max(vmem_bytes(hp, wp, scn.c, scn.k, bm, lo)
               for lo in ("HWC", "CHW")) <= VMEM_LIMIT_BYTES


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k: int,
                 oh: int, ow: int, c: int, chw_in: bool, chw_out: bool):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    xa = x_ref[...]  # whole strip lives in VMEM
    if chw_in:
        # fused prologue: the producer handed us CHW; remap to the
        # kernel's HWC working order while the strip is VMEM-resident
        # (no HBM transpose round trip)
        xa = jnp.transpose(xa, (1, 2, 0))
    # fully unrolled K x K tap loop: one static MXU dot per tap (Mosaic
    # lowers no dynamic_slice of a VMEM value, so taps stay static)
    for i in range(k):
        for j in range(k):
            win = jax.lax.slice(xa, (i, j, 0), (i + oh, j + ow, c))
            acc_ref[...] += jnp.dot(
                win.reshape(oh * ow, c), w_ref[i, j],
                preferred_element_type=jnp.float32)
    out = acc_ref[...] + b_ref[...].astype(jnp.float32)
    if chw_out:
        # fused epilogue: emit the consumer's CHW layout through the
        # remapped (bm, OH*OW) out BlockSpec
        out = out.T
    o_ref[...] = out.astype(o_ref.dtype)


def conv_direct_pallas(x, w, b, *, bm: int = 128,
                       in_layout: str = "HWC", out_layout: str = "HWC",
                       interpret=None):
    """Pre-padded single-image stride-1 conv; w: (K, K, C, M), M % bm == 0.

    Layout-parameterized entry point: ``in_layout`` is the layout the
    input strip arrives in — ``"HWC"`` (native, shape (Hp, Wp, C)) or
    ``"CHW"`` (shape (C, Hp, Wp), transposed in the kernel prologue).
    ``out_layout`` picks the emitted layout: ``"HWC"`` returns
    (OH*OW, M), ``"CHW"`` returns (M, OH*OW) stored via a remapped out
    BlockSpec in the epilogue.  The ops wrapper reshapes to spatial.
    """
    assert in_layout in ("HWC", "CHW") and out_layout in ("HWC", "CHW")
    chw_in = in_layout == "CHW"
    chw_out = out_layout == "CHW"
    if chw_in:
        c, hp, wp = x.shape
    else:
        hp, wp, c = x.shape
    k, _, _, m = w.shape
    assert m % bm == 0
    oh, ow = hp - k + 1, wp - k + 1
    if interpret is None:
        interpret = use_interpret()

    kern = functools.partial(_conv_kernel, k=k, oh=oh,
                             ow=ow, c=c, chw_in=chw_in, chw_out=chw_out)
    in_spec = pl.BlockSpec((c, hp, wp), lambda mi: (0, 0, 0)) if chw_in \
        else pl.BlockSpec((hp, wp, c), lambda mi: (0, 0, 0))
    out_spec = pl.BlockSpec((bm, oh * ow), lambda mi: (mi, 0)) if chw_out \
        else pl.BlockSpec((oh * ow, bm), lambda mi: (0, mi))
    out_shape = (m, oh * ow) if chw_out else (oh * ow, m)
    return pl.pallas_call(
        kern,
        grid=(m // bm,),
        in_specs=[
            in_spec,
            pl.BlockSpec((k, k, c, bm), lambda mi: (0, 0, 0, mi)),
            pl.BlockSpec((1, bm), lambda mi: (0, mi)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((oh * ow, bm), jnp.float32)],
        interpret=interpret,
    )(x, w, b.reshape(1, m))
