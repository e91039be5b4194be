"""Whole runs of the benchmark's cells on the CPU at a tiny size, sound
and with the timed path broken underneath.

The harness's look for a chip lives in ``run.py``; these tests call the
rest of a run (``harness.run_cell``) directly, on copies of the cells
whose configurations take a 32x32 image.
"""
import json
import shutil
import time

import numpy as np
import pytest

from benchmarks.chip import harness


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for name in ("googlenet", "vgg16"):
        path = chip / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(scale=1 / 7, input_chw=[3, 32, 32])
        path.write_text(json.dumps(cfg))
    path = chip / "traffic" / "poisson_open.json"
    traffic = json.loads(path.read_text())
    traffic["rate_per_s"] = 40
    path.write_text(json.dumps(traffic))
    return root


def _run(root, cell, seed=2**31 + 17, seconds=1.0):
    return harness.run_cell(harness.load_cell(cell, root), seed, seconds,
                            False, time.perf_counter(), peak_flops=197e12)


@pytest.mark.parametrize("cell, metrics", [
    ("googlenet.b8", {"images_per_s", "setup_s"}),
    ("googlenet.b1", {"latency_p50_ms", "latency_p99_ms", "setup_s"}),
    ("googlenet.serve", {"latency_p50_ms", "setup_s"}),
])
def test_sound_run_is_correct(tiny_root, cell, metrics):
    res = _run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_err"]["value"] < 1e-4  # float32 on the CPU


def _alter_one_answer(out):
    """Each call's first answer with its classes shifted by one."""
    (k, v), = out.items()
    v = np.array(v)
    if v.ndim == 4:
        v[0] = np.roll(v[0], 1, axis=0)
    else:
        v = np.roll(v, 1, axis=0)
    return {k: v}


def _swap_rows(out):
    """A batch's answers handed back one row off."""
    return {k: np.roll(np.asarray(v), 1, axis=0) if np.ndim(v) == 4 else v
            for k, v in out.items()}


@pytest.mark.parametrize("cell, fault", [
    ("googlenet.b8", _alter_one_answer),
    ("googlenet.b1", _alter_one_answer),
    ("googlenet.serve", _alter_one_answer),
    ("googlenet.b8", _swap_rows),
])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault):
    from repro.core.plan import CompiledNet

    sound = CompiledNet.__call__
    monkeypatch.setattr(CompiledNet, "__call__",
                        lambda self, x: fault(sound(self, x)))
    res = _run(tiny_root, cell)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]


def test_failed_request_makes_the_run_not_correct(tiny_root, monkeypatch):
    from repro.serving import PlanServer

    calls = {"n": 0}
    sound = PlanServer.infer

    def flaky(self, x):
        calls["n"] += 1
        if calls["n"] == 6:  # after warm-up, inside the window
            raise RuntimeError("planted")
        return sound(self, x)

    monkeypatch.setattr(PlanServer, "infer", flaky)
    res = _run(tiny_root, "googlenet.b1")
    assert res["failed"] == 1 and not res["correct"]


def test_in_place_request_sends_the_bytes_of_a_fresh_image():
    images = harness.Images((3, 8, 8), 2**31 + 5)
    for i in (0, 5, harness.POOL + 5, 3 * harness.POOL + 63):
        fresh = images.get(i)
        assert np.array_equal(images.request(i), fresh)
        assert not np.shares_memory(fresh, images.pool)
    assert not np.array_equal(images.get(5), images.get(harness.POOL + 5))
