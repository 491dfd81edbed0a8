"""The harness end to end on the CPU, at tiny sizes: every cell runs and
is correct, what is added as files is found by name, and the look for a
chip refuses what is not a known TPU."""
import json
import os
import shutil
import subprocess
import types

import pytest

from bench import run, spec
from bench.tests.conftest import REPO, cells

CELLS = cells()


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_runs_and_is_correct(tiny_root, workload):
    r = run.run_cell(workload, 2**31 + 3, 0.3, False, root=tiny_root,
                     require_tpu=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) >= {"teps", "setup_s"}
    assert r["metrics"]["teps"]["unit"] == "edges/s"
    assert list(r)[-1] == "check"
    assert all(v["value"] <= v["limit"] for v in r["check"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_the_per_layer_metrics(tiny_root, workload):
    r = run.run_cell(workload, 7, 0.3, True, root=tiny_root,
                     require_tpu=False)
    assert r["correct"]
    # the CPU has no device trace: the readers of device time stay silent
    assert set(r["metrics"]) == {"engine.steps_per_query", "engine.step_ms",
                                 "relax.weight_bytes_per_edge"}
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["device"]["window_s"] > 0


def test_same_seed_same_inputs(tiny_root):
    a = run.run_cell("kron-sssp", 99, 0.2, False, root=tiny_root,
                     require_tpu=False)
    b = run.run_cell("kron-sssp", 99, 0.2, False, root=tiny_root,
                     require_tpu=False)
    assert a["check"] == b["check"]


def add_dummy_cell(root):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus new entries, editing no file that is there."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "dummy-kron.json"), "w") as f:
        json.dump({"generator": "kronecker", "dataset_seed": 1,
                   "scale": 8, "edgefactor": 8,
                   "initiator": [0.45, 0.15, 0.15, 0.25],
                   "precision": "float32"}, f)
    with open(os.path.join(bench, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({"program": "sssp", "batch": 2,
                   "roots": {"draw": "nonisolated", "count": 4,
                             "set_seed": 0}}, f)
    with open(os.path.join(bench, "metrics", "dummy.calls.py"), "w") as f:
        f.write("def read(win):\n    return len(win.done)\n")
    path = os.path.join(root, "BENCHMARK.json")
    b = spec.load_json(path)
    b["configs"].append({"name": "dummy-kron", "source": "test",
                         "file": "bench/configs/dummy-kron.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "dummy", "config": "dummy-kron",
                           "traffic": "dummy-mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "dummy.calls", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "teps",
                           "workloads": ["dummy"]})
    with open(path, "w") as f:
        json.dump(b, f)


def test_new_files_are_found_by_name(tiny_root):
    add_dummy_cell(tiny_root)
    r = run.run_cell("dummy", 5, 0.2, True, root=tiny_root,
                     require_tpu=False)
    assert r["correct"]
    assert r["metrics"]["dummy.calls"]["value"] >= 1
    assert r["metrics"]["dummy.calls"]["unit"] == "calls"
    # the metric is the dummy cell's alone
    r = run.run_cell("kron-sssp", 5, 0.2, True, root=tiny_root,
                     require_tpu=False)
    assert "dummy.calls" not in r["metrics"]


def fake_devices(monkeypatch, platform, kind, count):
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)


@pytest.mark.parametrize("platform,kind,count,chips,why", [
    ("cpu", "cpu", 1, 1, "no TPU"),
    ("tpu", "TPU v99", 1, 1, "not in bench/peaks.json"),
    ("tpu", "TPU v5 lite", 1, 4, "needs 4 chips"),
])
def test_the_look_for_a_chip_refuses(monkeypatch, platform, kind, count,
                                     chips, why):
    fake_devices(monkeypatch, platform, kind, count)
    with pytest.raises(SystemExit, match=why):
        run.require_chip(chips, REPO)


def test_the_look_for_a_chip_takes_a_v5e(monkeypatch):
    fake_devices(monkeypatch, "tpu", "TPU v5 lite", 4)
    devs, peaks = run.require_chip(1, REPO)
    assert len(devs) == 1 and peaks["hbm_bytes_per_s"] == 819e9


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    b = spec.load_json(os.path.join(REPO, "BENCHMARK.json"))
    p = subprocess.run(b["command"] + ["--workload", "kron-sssp",
                                       "--seed", "1", "--seconds", "1",
                                       "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "program is missing" in p.stderr
