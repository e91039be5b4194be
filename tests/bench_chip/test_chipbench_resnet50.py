"""The ``resnet50`` configuration and its ``resnet50.b8`` cell: found by
name from files alone, the same layer list as the tests' own ResNet-50
v1.5 (``resnet50_v15.py``), and a sound run through the harness on the
CPU at a tiny size."""
import json
import shutil
import time

import pytest

from benchmarks.chip import flops, harness

MACS = 4_089_184_256  # ResNet-50 v1.5 at 3x224x224
CONFIG = harness.HERE / "configs" / "resnet50_v15.json"
tests_list = harness.load_module(
    harness.ROOT / "tests" / "bench_chip" / "resnet50_v15.py",
    "test_resnet50_tests_list")


def test_resnet50_cell_is_found_with_its_metrics():
    cell = harness.load_cell("resnet50.b8")
    chw = tuple(cell.config["input_chw"])
    assert chw == (3, 224, 224) and cell.chips == 1
    assert cell.config["net"] == "resnet50" and cell.config["reduced"] == []
    assert cell.traffic == {"driver": "closed_batch", "batch": 8}
    assert [m.name for m in cell.end_to_end] == ["images_per_s",
                                                 "latency_p99_ms", "setup_s"]
    assert [m.name for m in cell.per_layer] == ["solve_ms", "mfu.batch",
                                                "idle_share.batch"]
    assert flops.macs(cell.layers, chw) == MACS
    ops = [ly["op"] for ly in cell.layers]
    assert (ops.count("conv"), ops.count("add")) == (53, 16)


@pytest.mark.parametrize("drop", [(), ("s4b3",)], ids=["whole", "s4b3"])
def test_configuration_list_is_the_tests_resnet50(drop):
    cfg = json.loads(CONFIG.read_text())
    mod = harness.load_module(CONFIG.with_suffix(".py"),
                              "test_resnet50_config_list")
    assert {k: cfg[k] for k in tests_list.CONFIG} == tests_list.CONFIG
    assert mod.layers(cfg, drop) == tests_list.layers(tests_list.CONFIG,
                                                      drop)


def test_a_copy_of_the_configuration_is_files_alone(tmp_path):
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    # under a name the tree lacks, so that nothing there is rewritten
    cfg = json.loads(CONFIG.read_text())
    (chip / "configs" / "resnet50_copy.json").write_text(
        json.dumps(dict(cfg, name="resnet50_copy")))
    shutil.copy(CONFIG.with_suffix(".py"), chip / "configs" /
                "resnet50_copy.py")
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "resnet50_copy", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "resnet50_copy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "resnet50_copy.b8",
                               "config": "resnet50_copy",
                               "traffic": "batch8_closed", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("resnet50_copy.b8", root)
    assert flops.macs(cell.layers, tuple(cell.config["input_chw"])) == MACS
    assert cell.driver.__name__.endswith("closed_batch")
    assert all(p.read_bytes() == b for p, b in before.items())


def test_sound_resnet50_b8_run_is_correct(tmp_path):
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    path = chip / "configs" / "resnet50_v15.json"
    cfg = json.loads(path.read_text())
    cfg.update(scale=1 / 7, input_chw=[3, 32, 32])
    path.write_text(json.dumps(cfg))

    res = harness.run_cell(harness.load_cell("resnet50.b8", root),
                           2**31 + 17, 1.0, False, time.perf_counter(),
                           peak_flops=197e12)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"images_per_s", "latency_p99_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["logit_err"]["value"] < 1e-4  # float32 on the CPU
