"""The reference's residual join and folded-BatchNorm gain, on the CPU.

A tiny residual list (a stem conv, an identity block and a stride-2 block
with a 1x1 projection shortcut) runs as an independent NumPy walk does.
ResNet-50 v1.5 at its published depth and widths, at 3x64x64, shows why
the gain is there: without it the check sees one class of 1000 and can
judge nothing; with it the int8 control and a block whose branch is
dropped both read far above the bfloat16 operands the configurations
state.
"""
import pathlib

import numpy as np
import pytest

from benchmarks.chip import harness, reference

resnet50 = harness.load_module(
    pathlib.Path(__file__).with_name("resnet50_v15.py"), "resnet50_v15")

TINY_CHW = (3, 11, 11)


def _tiny(gain=0.5):
    def L(name, op, inputs, **sizes):
        return dict(name=name, op=op, inputs=inputs, **sizes)

    g = {} if gain is None else {"gain": gain}
    return [
        L("stem", "conv", ["data"], k=3, m=8, stride=1, pad=1),
        L("stem_relu", "relu", ["stem"]),
        L("b1_a", "conv", ["stem_relu"], k=3, m=8, stride=1, pad=1),
        L("b1_a_relu", "relu", ["b1_a"]),
        L("b1_b", "conv", ["b1_a_relu"], k=3, m=8, stride=1, pad=1, **g),
        L("b1_add", "add", ["b1_b", "stem_relu"]),
        L("b1_relu", "relu", ["b1_add"]),
        L("b2_a", "conv", ["b1_relu"], k=3, m=16, stride=2, pad=1),
        L("b2_a_relu", "relu", ["b2_a"]),
        L("b2_b", "conv", ["b2_a_relu"], k=3, m=16, stride=1, pad=1, **g),
        L("b2_proj", "conv", ["b1_relu"], k=1, m=16, stride=2, pad=0),
        L("b2_add", "add", ["b2_b", "b2_proj"]),
        L("b2_relu", "relu", ["b2_add"]),
        L("gap", "gap", ["b2_relu"]),
        L("fc", "fc", ["gap"], out=10, relu=False),
    ]


def _numpy_walk(layers, params, x):
    """Every layer of ``layers`` on one image, in float64, by loops over
    the kernel's taps: the shapes and the logits."""
    vals = {"data": np.asarray(x, np.float64)}
    for ly in layers:
        v = vals[ly["inputs"][0]]
        op = ly["op"]
        if op == "conv":
            w = params[ly["name"]]["w"].astype(np.float64)
            k, s, pd = ly["k"], ly["stride"], ly["pad"]
            c, h, wd = v.shape
            vp = np.zeros((c, h + 2 * pd, wd + 2 * pd))
            vp[:, pd:pd + h, pd:pd + wd] = v
            oh, ow = (h + 2 * pd - k) // s + 1, (wd + 2 * pd - k) // s + 1
            y = np.zeros((ly["m"], oh, ow))
            for i in range(k):
                for j in range(k):
                    patch = vp[:, i:i + s * oh:s, j:j + s * ow:s]
                    y += np.einsum("mc,chw->mhw", w[:, :, i, j], patch)
            y += params[ly["name"]]["b"][:, None, None]
        elif op == "relu":
            y = np.maximum(v, 0.0)
        elif op == "add":
            y = sum(vals[i] for i in ly["inputs"])
        elif op == "gap":
            y = v.mean(axis=(1, 2), keepdims=True)
        elif op == "fc":
            y = (v.reshape(-1) @ params[ly["name"]]["w"]
                 + params[ly["name"]]["b"])[:, None, None]
        vals[ly["name"]] = y
    return ({n: a.shape for n, a in vals.items()},
            vals[layers[-1]["name"]].reshape(-1))


def test_tiny_residual_net_matches_a_numpy_walk():
    layers = _tiny()
    params = reference.init_params(layers, TINY_CHW, 5)
    x = np.random.default_rng(1).normal(size=(3, *TINY_CHW)).astype(np.float32)
    got = reference.logits(layers, params, x, block=2)
    shp = reference.shapes(layers, TINY_CHW)
    for xi, row in zip(x, got):
        want_shp, want = _numpy_walk(layers, params, xi)
        assert shp == want_shp
        np.testing.assert_allclose(row, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
    assert shp["b2_add"] == (16, 6, 6)


@pytest.mark.parametrize("inputs", [["stem_relu", "b2_proj"], ["stem_relu"]],
                         ids=["mismatched", "one_input"])
def test_add_needs_two_or_more_inputs_of_one_shape(inputs):
    layers = _tiny()
    layers[-4] = dict(layers[-4], inputs=inputs)  # b2_add
    with pytest.raises(ValueError, match="add b2_add"):
        reference.shapes(layers, TINY_CHW)


@pytest.mark.parametrize("gain", [0.25, 0.5])
def test_gain_scales_only_that_convs_weights(gain):
    plain = reference.init_params(_tiny(None), TINY_CHW, 9)
    scaled = reference.init_params(_tiny(gain), TINY_CHW, 9)
    assert list(plain) == list(scaled)
    for name in plain:
        g = gain if name in ("b1_b", "b2_b") else 1.0
        # a power of two scales float64 and float32 values exactly
        np.testing.assert_array_equal(scaled[name]["w"],
                                      np.float32(g) * plain[name]["w"])
        np.testing.assert_array_equal(scaled[name]["b"], plain[name]["b"])


# ----------------------------------------------------------------------
# ResNet-50 v1.5, published depth and widths, at 3x64x64
# ----------------------------------------------------------------------
CHW = (3, 64, 64)
N_IMAGES = 8


def _cfg(branch_gain):
    return dict(resnet50.CONFIG, input_chw=list(CHW), branch_gain=branch_gain)


@pytest.fixture(scope="module")
def rn50():
    """Per precision, the largest error over 8 seeded images against the
    float32 reference, and what it needs to read more."""
    cfg = _cfg(resnet50.BRANCH_GAIN)
    layers = resnet50.layers(cfg)
    params = reference.init_params(layers, CHW, 7)
    images = harness.Images(CHW, 7)
    x = np.stack([images.get(i) for i in range(N_IMAGES)])
    ref = reference.logits(layers, params, x)

    def err(lo):
        p = np.exp(reference.log_softmax(lo)).astype(np.float32)
        return float(reference.logit_err(p, ref).max())

    errs = {p: err(reference.logits(layers, params, x, precision=p))
            for p in ("bf16_operands", "int8")}
    return dict(cfg=cfg, params=params, x=x, ref=ref, err=err, errs=errs)


def test_resnet50_without_the_gain_keeps_one_class_and_judges_nothing(rn50):
    layers = resnet50.layers(_cfg(None))
    assert not any("gain" in ly for ly in layers)
    params = reference.init_params(layers, CHW, 7)
    ref = reference.logits(layers, params, rn50["x"])
    kept = (reference.log_softmax(ref) >= reference.LOGP_FLOOR).sum(axis=1)
    assert kept.max() < 10
    itself = np.exp(reference.log_softmax(ref)).astype(np.float32)
    assert np.isinf(reference.logit_err(itself, ref)).all()


def test_resnet50_with_the_gain_keeps_every_class(rn50):
    kept = (reference.log_softmax(rn50["ref"]) >= reference.LOGP_FLOOR)
    assert kept.all()
    assert rn50["err"](rn50["ref"]) < 1e-6


def test_resnet50_int8_control_reads_3x_the_bf16_operands(rn50):
    assert rn50["errs"]["int8"] >= 3 * rn50["errs"]["bf16_operands"], \
        rn50["errs"]


@pytest.mark.parametrize("block", ["s2b1", "s4b3", "s5b2"])
def test_resnet50_dropped_branch_reads_10x_the_bf16_operands(rn50, block):
    dropped = resnet50.layers(rn50["cfg"], drop=(block,))
    assert f"{block}_add" not in {ly["name"] for ly in dropped}
    err = rn50["err"](reference.logits(dropped, rn50["params"], rn50["x"]))
    assert err >= 10 * rn50["errs"]["bf16_operands"], (err, rn50["errs"])
