"""Per-kernel allclose validation against the pure-jnp oracles.

All Pallas kernels run in interpret mode on CPU (the kernel body
executes in Python); shapes and dtypes are swept per kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.primitives import registry
from repro.core.scenario import Scenario
from repro.kernels.conv_direct import conv_direct, conv_direct_ref
from repro.kernels.conv_im2col import conv_im2col, conv_im2col_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.layout_transform import (
    chw_to_hwc, chw_to_hwc_ref, hwc_to_chw, hwc_to_chw_ref,
)
from repro.kernels.matmul import matmul, matmul_ref
from repro.kernels.winograd_gemm import (
    bgemm_ref, conv_ref, conv_winograd, prepare_kernel,
    winograd_bgemm_pallas,
)

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


class TestMatmul:
    @pytest.mark.parametrize("m,k,n", [
        (128, 128, 128), (256, 384, 128), (64, 96, 32), (17, 33, 9),
        (1, 128, 128), (130, 257, 129),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, m, k, n, dtype):
        x = jnp.asarray(RNG.normal(size=(m, k)), dtype)
        y = jnp.asarray(RNG.normal(size=(k, n)), dtype)
        got = matmul(x, y)
        want = matmul_ref(x, y)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    def test_fused_bias_relu(self):
        x = jnp.asarray(RNG.normal(size=(64, 64)), jnp.float32)
        y = jnp.asarray(RNG.normal(size=(64, 48)), jnp.float32)
        b = jnp.asarray(RNG.normal(size=(48,)), jnp.float32)
        got = matmul(x, y, b, fuse_relu=True)
        want = matmul_ref(x, y, b, fuse_relu=True)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert (np.asarray(got) >= 0).all()

    @pytest.mark.parametrize("bm,bn,bk", [(32, 32, 32), (64, 128, 32)])
    def test_block_shape_sweep(self, bm, bn, bk):
        x = jnp.asarray(RNG.normal(size=(128, 96)), jnp.float32)
        y = jnp.asarray(RNG.normal(size=(96, 160)), jnp.float32)
        got = matmul(x, y, bm=bm, bn=bn, bk=bk)
        np.testing.assert_allclose(got, matmul_ref(x, y), rtol=2e-5,
                                   atol=2e-5)


class TestConvDirect:
    @pytest.mark.parametrize("h,w,c,m,k,pad", [
        (14, 14, 16, 32, 3, 1),
        (13, 9, 8, 16, 3, 1),
        (27, 27, 3, 16, 5, 2),
        (12, 12, 4, 8, 1, 0),
        (10, 10, 8, 130, 3, 1),   # m > block
    ])
    def test_shapes(self, h, w, c, m, k, pad):
        x = jnp.asarray(RNG.normal(size=(h, w, c)), jnp.float32)
        wt = jnp.asarray(RNG.normal(size=(k, k, c, m)) * 0.1, jnp.float32)
        b = jnp.asarray(RNG.normal(size=(m,)), jnp.float32)
        got = conv_direct(x, wt, b, pad=pad)
        want = conv_direct_ref(x, wt, b, pad=pad)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("scn,offered", [
        # GoogLeNet conv2 and a VGG conv4 layer fit the scoped VMEM
        (Scenario(c=64, h=56, w=56, stride=1, k=3, m=192), True),
        (Scenario(c=256, h=28, w=28, stride=1, k=3, m=512), True),
        # VGG conv1 (C=3 pads to 128 lanes) and conv3 overflow it
        (Scenario(c=3, h=224, w=224, stride=1, k=3, m=64), False),
        (Scenario(c=256, h=56, w=56, stride=1, k=3, m=256), False),
        # strided layers: GoogLeNet conv1, AlexNet conv1
        (Scenario(c=3, h=224, w=224, stride=2, k=7, m=64), False),
        (Scenario(c=3, h=227, w=227, stride=4, k=11, m=96, pad=0), False),
    ])
    def test_registry_offers_it_where_it_compiles(self, scn, offered):
        """supports() of the direct kernel matches what the v5e compiler
        accepts (tests/test_tpu_compile.py compiles the same layers)."""
        prim = next(p for p in registry() if p.name == "pallas_direct_hwc")
        assert prim.supports(scn) is offered

    def test_bf16(self):
        x = jnp.asarray(RNG.normal(size=(8, 8, 8)), jnp.bfloat16)
        wt = jnp.asarray(RNG.normal(size=(3, 3, 8, 16)) * 0.1, jnp.bfloat16)
        b = jnp.zeros((16,), jnp.bfloat16)
        got = conv_direct(x, wt, b, pad=1)
        want = conv_direct_ref(x, wt, b, pad=1)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)


class TestConvIm2col:
    @pytest.mark.parametrize("h,w,c,m,k,stride,pad", [
        (14, 14, 16, 32, 3, 1, 1),
        (27, 27, 3, 16, 11, 4, 0),   # AlexNet conv1 shape family
        (9, 13, 8, 24, 5, 1, 2),
        (7, 7, 32, 8, 1, 1, 0),
    ])
    def test_shapes(self, h, w, c, m, k, stride, pad):
        x = jnp.asarray(RNG.normal(size=(c, h, w)), jnp.float32)
        wt = jnp.asarray(RNG.normal(size=(m, c, k, k)) * 0.1, jnp.float32)
        b = jnp.asarray(RNG.normal(size=(m,)), jnp.float32)
        got = conv_im2col(x, wt, b, stride=stride, pad=pad)
        want = conv_im2col_ref(x, wt, b, stride=stride, pad=pad)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


class TestWinogradGemm:
    @pytest.mark.parametrize("p,m,c,n", [(16, 32, 64, 128), (36, 8, 16, 49)])
    def test_bgemm(self, p, m, c, n):
        u = jnp.asarray(RNG.normal(size=(p, m, c)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(p, c, n)), jnp.float32)
        from repro.kernels.common import pad_to
        vp, _ = pad_to(v, 2, 128 if n >= 128 else n)
        up, _ = pad_to(u, 2, c)
        got = winograd_bgemm_pallas(up, vp, bn=vp.shape[2] // max(1, vp.shape[2] // 128) if vp.shape[2] % 128 else 128, bc=c)
        got = got[:, :, :n]
        np.testing.assert_allclose(got, bgemm_ref(u, v), rtol=2e-4,
                                   atol=2e-4)

    @pytest.mark.parametrize("m_", [2, 4])
    @pytest.mark.parametrize("h,w,c,m", [(14, 14, 8, 16), (9, 11, 4, 8)])
    def test_full_conv(self, m_, h, w, c, m):
        x = jnp.asarray(RNG.normal(size=(c, h, w)), jnp.float32)
        wt = jnp.asarray(RNG.normal(size=(m, c, 3, 3)) * 0.1, jnp.float32)
        b = jnp.asarray(RNG.normal(size=(m,)), jnp.float32)
        u = prepare_kernel(np.asarray(wt), m_)
        got = conv_winograd(x, u, b, m_=m_, k=3, pad=1)
        want = conv_ref(x, wt, b, pad=1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


class TestFlashAttention:
    @pytest.mark.parametrize("hq,hkv,lq,lk,d", [
        (4, 4, 128, 128, 32),
        (8, 2, 128, 256, 64),    # GQA group 4
        (4, 1, 64, 64, 32),      # MQA
        (2, 2, 100, 130, 16),    # unaligned seq -> padded + masked
    ])
    def test_plain(self, hq, hkv, lq, lk, d):
        q = jnp.asarray(RNG.normal(size=(1, hq, lq, d)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, hkv, lk, d)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, hkv, lk, d)), jnp.float32)
        got = flash_attention(q, k, v, bq=64, bk=64)
        want = attention_ref(q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_causal(self):
        q = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
        got = flash_attention(q, k, v, causal=True, bq=32, bk=32)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_sliding_window_and_softcap(self):
        """gemma2-style: local window + logit soft-capping."""
        q = jnp.asarray(RNG.normal(size=(1, 2, 128, 16)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 2, 128, 16)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, 2, 128, 16)), jnp.float32)
        got = flash_attention(q, k, v, causal=True, window=48,
                              softcap=30.0, bq=32, bk=32)
        want = attention_ref(q, k, v, causal=True, window=48, softcap=30.0)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        q = jnp.asarray(RNG.normal(size=(2, 2, 64, 32)), jnp.bfloat16)
        k = jnp.asarray(RNG.normal(size=(2, 2, 64, 32)), jnp.bfloat16)
        v = jnp.asarray(RNG.normal(size=(2, 2, 64, 32)), jnp.bfloat16)
        got = flash_attention(q, k, v, causal=True, bq=32, bk=32)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)


class TestLayoutTransform:
    @pytest.mark.parametrize("c,h,w", [(16, 32, 128), (3, 17, 50),
                                       (64, 8, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_roundtrip_and_ref(self, c, h, w, dtype):
        x = jnp.asarray(RNG.normal(size=(c, h, w)), dtype)
        hwc = chw_to_hwc(x)
        np.testing.assert_array_equal(np.asarray(hwc),
                                      np.asarray(chw_to_hwc_ref(x)))
        back = hwc_to_chw(hwc)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(hwc_to_chw(hwc)),
                                      np.asarray(hwc_to_chw_ref(hwc)))
