"""BENCHMARK.json against the benchmark contract, and the harness finding
every piece of a cell by name from files alone."""
import json
import pathlib
import re
import shutil

import pytest

from benchmarks.chip import flops, harness, reference

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((harness.ROOT / p).is_dir() for p in BENCH["paths"])
    assert (harness.ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["reduced"] == []
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    c = harness.load_cell(cell)
    e2e = [m.name for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in BENCH["per_layer"]:
        if cell in m.get("workloads", [cell]):
            assert m["moves"] in e2e


def _copy_tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _vgg16_384(chip):
    cfg = json.loads((chip / "configs" / "vgg16.json").read_text())
    cfg.update(name="vgg16_384", input_chw=[3, 384, 384])
    return cfg, (chip / "configs" / "vgg16.py").read_text()


def _resnet50(chip):
    # a residual net: its list has ``add`` joins and convs with a ``gain``
    py = pathlib.Path(__file__).with_name("resnet50_v15.py")
    mod = harness.load_module(py, "resnet50_v15")
    return dict(mod.CONFIG, name="resnet50"), py.read_text()


@pytest.mark.parametrize("config, make, macs", [
    ("vgg16_384", _vgg16_384, 45_423_165_440),
    ("resnet50", _resnet50, 4_089_184_256),
], ids=["vgg16_384", "resnet50"])
def test_a_new_config_traffic_and_metric_are_files_alone(tmp_path, config,
                                                         make, macs):
    root = _copy_tree(tmp_path)
    chip = root / "benchmarks" / "chip"
    before = _snapshot(chip)
    cfg, py = make(chip)
    (chip / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    (chip / "configs" / f"{config}.py").write_text(py)
    (chip / "traffic" / "batch32_closed.json").write_text(
        json.dumps({"driver": "closed_batch", "batch": 32}))
    (chip / "metrics" / "calls_per_s.b32.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "x",
                             "file": f"benchmarks/chip/configs/{config}.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": f"{config}.b32", "config": config,
                               "traffic": "batch32_closed", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "calls_per_s.b32", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "executable", "moves": "images_per_s",
                               "workloads": [f"{config}.b32"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(f"{config}.b32", root)
    chw = tuple(cell.config["input_chw"])
    assert chw == tuple(cfg["input_chw"])
    assert reference.shapes(cell.layers, chw)[cell.layers[-1]["name"]] == (
        1000, 1, 1)
    assert flops.macs(cell.layers, chw) == macs
    assert cell.traffic["batch"] == 32
    assert cell.driver.__name__.endswith("closed_batch")
    assert [m.name for m in cell.per_layer] == ["solve_ms", "calls_per_s.b32"]
    assert cell.per_layer[1].read(None) == 42.0
    after = _snapshot(chip)
    assert all(after[p] == b for p, b in before.items())  # nothing edited


def test_unknown_cell_and_device_kind_are_errors():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell")
    with pytest.raises(KeyError):
        harness.peak_flops("TPU v9 imaginary")
    assert harness.peak_flops("TPU v5 lite") == 197e12
