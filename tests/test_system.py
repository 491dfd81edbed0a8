"""End-to-end behaviour tests: the paper's pipeline + the train/serve
drivers (resume-after-kill included)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900, **kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # the CLIs keep a persistent compile cache; tests leave it off
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return subprocess.run([sys.executable, "-m"] + args,
                          capture_output=True, text=True, cwd=REPO,
                          timeout=timeout, env=env, **kw)


def test_graph_pipeline_end_to_end():
    """compile -> simulate -> verify + baseline speedups (paper pipeline)."""
    from repro.core import BFS, compile_mapping, simulate, baselines
    from repro.graphs import make_road_network, reference
    g = make_road_network(96, seed=0, delete_frac=0.7)
    m = compile_mapping(g, effort=1, seed=0)
    r = simulate(m, BFS, src=1)
    ref, _ = reference.bfs(g, 1)
    assert np.allclose(np.where(np.isinf(r.attrs), -1, r.attrs),
                       np.where(np.isinf(ref), -1, ref))
    t_flip = r.cycles / m.arch.freq_mhz
    mcu = baselines.mcu_cycles("bfs", g, 1)
    cgra = baselines.cgra_cycles("bfs", g, 1)
    # paper Fig. 10: FLIP beats both baselines by large factors
    assert mcu.time_us / t_flip > 10
    assert cgra.time_us / t_flip > 3


def test_graph_run_cli():
    out = _run(["repro.launch.graph_run", "--algo", "bfs", "--dataset",
                "SRN", "--engine", "jax", "--effort", "0"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "correct vs reference: True" in out.stdout


def test_train_cli_and_resume():
    """Train 8 steps, kill, resume to 12: checkpoint-restart works and the
    loss curve continues (fault-tolerance path)."""
    import shutil
    ckpt = "/tmp/test_ckpt_resume"
    shutil.rmtree(ckpt, ignore_errors=True)
    base = ["repro.launch.train", "--arch", "qwen3_0_6b", "--preset",
            "tiny", "--seq", "64", "--batch", "4", "--ckpt-dir", ckpt,
            "--ckpt-every", "4", "--log-every", "4"]
    out = _run(base + ["--steps", "8"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "step=8" in out.stdout
    out2 = _run(base + ["--steps", "12", "--resume"])
    assert out2.returncode == 0, out2.stderr[-2000:]
    assert "resumed from step 8" in out2.stdout
    assert "step=12" in out2.stdout


def test_serve_cli():
    out = _run(["repro.launch.serve", "--arch", "qwen3_0_6b", "--preset",
                "tiny", "--slots", "4", "--requests", "6",
                "--max-new", "8"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "requests" in out.stdout


def test_expert_placement_reduces_traffic():
    from repro.core.placement import expert_affinity, place_experts
    rng = np.random.default_rng(0)
    E, k = 32, 4
    gperm = rng.permutation(E).reshape(8, 4)
    topk = np.stack([rng.permuted(gperm[rng.integers(0, 8)])[:k]
                     for _ in range(1500)])
    pl = place_experts(expert_affinity(topk, E), num_devices=8, seed=0)
    assert pl.est_cost < pl.baseline_cost * 0.7   # >30% traffic cut
    assert sorted(pl.perm.tolist()) == list(range(E))
