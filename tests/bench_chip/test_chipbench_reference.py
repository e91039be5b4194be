"""The benchmark's plain reference against the program, on the CPU.

At the smallest input each net builds at, the reference's weights drawn
from a seed equal the served stack's, and its logits give the program's
reference plan's softmax to float32 rounding.  The controls (the
reference a step below the served precision) fail the configuration's
limit there too.
"""
import json

import numpy as np
import pytest

from benchmarks.chip import harness, reference

SMALL = {"googlenet": ("googlenet", 1 / 7), "vgg16": ("vgg-d", 1 / 7)}


def _config(name):
    cfg = json.loads((harness.HERE / "configs" / f"{name}.json").read_text())
    mod = harness.load_module(harness.HERE / "configs" / f"{name}.py",
                              f"test_ref_{name}")
    return cfg, mod.layers(cfg)


@pytest.fixture(scope="module", params=sorted(SMALL))
def small(request):
    from repro.convnets import NETWORKS

    name = request.param
    cfg, layers = _config(name)
    net_name, scale = SMALL[name]
    net = NETWORKS[net_name](scale)
    return name, cfg, layers, net, tuple(net.nodes["data"].out_shape)


def test_weights_equal_the_served_stacks(small):
    _, _, layers, net, chw = small
    ref = reference.init_params(layers, chw, 123)
    prog = net.init_params(123)
    ours = [ly["name"] for ly in layers if ly["op"] in ("conv", "fc")]
    theirs = [n for n in net.order if n in prog]
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(ref[a]["w"], prog[b]["w"])
        np.testing.assert_array_equal(ref[a]["b"], prog[b]["b"])


def test_shapes_equal_the_served_nets(small):
    _, _, layers, net, chw = small
    shp = reference.shapes(layers, chw)
    assert shp[layers[-1]["name"]] == net.nodes[net.outputs()[0]].out_shape
    ours = [shp[ly["name"]] for ly in layers if ly["op"] == "conv"]
    theirs = [n.out_shape for n in net.conv_nodes()]
    assert ours == theirs


def test_reference_matches_the_programs_reference_plan(small):
    from repro.core.plan import compile_plan
    from repro.reliability import reference_selection

    _, _, layers, net, chw = small
    x = np.random.default_rng(0).normal(size=(2, *chw)).astype(np.float32)
    ref = reference.logits(layers, reference.init_params(layers, chw, 7), x,
                           block=2)
    cnet = compile_plan(reference_selection(net), net.init_params(7))
    probs = np.stack([np.asarray(cnet(xi)[net.outputs()[0]]).reshape(-1)
                      for xi in x])
    assert reference.logit_err(probs, ref).max() < 1e-5


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_limit(small, seed):
    name, cfg, layers, _, chw = small
    images = harness.Images(chw, seed)
    x = np.stack([images.get(i) for i in range(8)])
    params = reference.init_params(layers, chw, seed)
    ref = reference.logits(layers, params, x)
    lo = reference.logits(layers, params, x, precision="int8")
    err = reference.logit_err(np.exp(reference.log_softmax(lo)), ref).max()
    assert err > cfg["check"]["logit_err"], (name, err)


def test_error_sees_every_class_of_a_flat_softmax():
    rng = np.random.default_rng(0)
    z = rng.normal(0, 0.01, size=(1, 1000))  # nearly flat softmax
    p = np.exp(reference.log_softmax(z))
    assert reference.logit_err(p, z).max() < 1e-9
    noisy = z + rng.normal(0, 0.001, size=z.shape)
    err = reference.logit_err(np.exp(reference.log_softmax(noisy)), z)
    assert err.max() == pytest.approx(0.1, rel=0.1)  # 0.001 / 0.01


def test_underflowed_class_below_the_floor_is_not_compared():
    z = np.array([[110.0, 0.0, 100.0, 104.0]])  # class 1: log p = -110
    p = np.exp(reference.log_softmax(z)).astype(np.float32)
    assert p[0, 1] == 0.0
    assert reference.logit_err(p, z).max() < 1e-6
    z[0, 1] = 60.0  # now class 1 is compared, and a served 0 fails it
    p[0, 1] = 0.0
    assert np.isinf(reference.logit_err(p, z).max())


def test_sample_keeps_first_and_last_and_depends_on_the_seed():
    a = reference.sample(1000, 64, 5)
    assert len(a) == 64 and a[0] == 0 and a[-1] == 999
    assert a == reference.sample(1000, 64, 5)
    assert a != reference.sample(1000, 64, 6)
    assert reference.sample(10, 64, 5) == list(range(10))
