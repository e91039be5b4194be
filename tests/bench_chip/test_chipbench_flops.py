"""Model operation counts of the benchmark's nets against their papers."""
import json
import pathlib

import pytest

from benchmarks.chip import flops, harness

resnet50 = harness.load_module(
    pathlib.Path(__file__).with_name("resnet50_v15.py"), "resnet50_v15")


def _layers(name):
    if name == "resnet50_v15":  # not a configuration yet: the tests' own list
        return resnet50.layers(resnet50.CONFIG), tuple(
            resnet50.CONFIG["input_chw"])
    cfg = json.loads((harness.HERE / "configs" / f"{name}.json").read_text())
    mod = harness.load_module(harness.HERE / "configs" / f"{name}.py",
                              f"test_flops_{name}")
    return mod.layers(cfg), tuple(cfg["input_chw"])


@pytest.mark.parametrize("name, published, exact", [
    # Szegedy et al.: "1.5 billion multiply-adds" at inference
    ("googlenet", 1.5e9, 1_582_671_872),
    # VGG-16 (D) at 224x224: 15.5 GMAC, as commonly tabulated
    ("vgg16", 15.5e9, 15_470_264_320),
    # ResNet-50 v1.5: the 4.1 GMAC usually quoted (He et al. Table 1 gives
    # 3.8e9 for v1, stride on the first 1x1); its 16 joins add no MACs
    ("resnet50_v15", 4.1e9, 4_089_184_256),
])
def test_macs_match_the_published_count(name, published, exact):
    layers, chw = _layers(name)
    macs = flops.macs(layers, chw)
    assert macs == exact
    assert abs(macs - published) / published < 0.06
    assert flops.model_flops(layers, chw) == 2.0 * macs


def test_flops_ignore_which_primitive_runs():
    # counted from layer shapes alone: the count has no notion of a plan
    layers, chw = _layers("googlenet")
    convs = [ly for ly in layers if ly["op"] == "conv"]
    assert len(convs) == 57
    assert sum(ly["op"] == "fc" for ly in layers) == 1
