"""Distributed pieces that need >1 device run in subprocesses with
xla_force_host_platform_device_count (the main test process keeps the real
1-device platform per the assignment)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (DEFAULT_RULES, logical_to_pspec,
                                        mesh_context, constrain)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, devices: int = 8) -> str:
    prog = (f"import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": os.environ.get("HOME", REPO),
                              # force the host platform: without this, jax
                              # backend discovery can block for minutes
                              # probing accelerators from the clean env
                              "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ----------------------- sharding rules (no mesh needed) --------------- #
def test_pspec_no_mesh_is_empty():
    assert logical_to_pspec((4, 4), ("batch", "embed"), None) == P()


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


def test_pspec_rules_subprocess():
    out = _run_sub("""
    import jax, json
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import logical_to_pspec, DEFAULT_RULES
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    checks = []
    # normal weight: embed->data, mlp->model
    s = logical_to_pspec((8, 16), ("embed", "mlp"), mesh)
    checks.append(s == P("data", "model"))
    # non-divisible but >= axis size: uneven sharding kept (GSPMD pads)
    s = logical_to_pspec((6, 16), ("embed", "mlp"), mesh)
    checks.append(s == P("data", "model"))
    # dim smaller than the axis: replicate (GQA kv heads case)
    s = logical_to_pspec((1, 16), ("embed", "mlp"), mesh)
    checks.append(s == P(None, "model"))
    # tuple with missing axis filtered ("pod" absent)
    s = logical_to_pspec((8, 4), ("batch", None), mesh)
    checks.append(s == P("data", None))
    # one mesh axis used once
    s = logical_to_pspec((8, 8), ("mlp", "heads"), mesh)
    checks.append(s == P("model", None))
    print(json.dumps(checks))
    """)
    assert all(json.loads(out.strip().splitlines()[-1]))


# ----------------------- distributed graph engine ---------------------- #
def test_engine_distributed_matches_reference():
    out = _run_sub("""
    from repro.algebra import ALGEBRAS
    from repro.graphs import make_road_network, reference
    from repro.core.engine import FlipEngine
    g = make_road_network(128, seed=3)
    for algo, src in [("bfs", 2), ("sssp", 2), ("wcc", 0),
                      ("widest", 2), ("reach", 2), ("pagerank", 0)]:
        eng = FlipEngine.build(g, algo, tile=32)
        got, steps = eng.execute(src, distributed=True)  # like execute
        assert steps >= 1, algo
        ref, _ = reference.run(algo, g, src)
        assert ALGEBRAS[algo].results_match(got, ref), algo
    print("OK")
    """)
    assert "OK" in out


def test_engine_distributed_batched_and_zero_block_devices():
    """Batched queries stay replicated while tiles shard; with ntiles <
    ndev some devices own only padded tiles and zero real blocks -- the
    degenerate all-identity slab must be an exact no-op, not a crash."""
    out = _run_sub("""
    import numpy as np
    from repro.algebra import ALGEBRAS
    from repro.graphs import make_road_network, reference
    from repro.core.engine import FlipEngine
    # ntiles = 2 over 8 devices: 6 devices own zero blocks
    g = make_road_network(48, seed=1)
    for algo in ("sssp", "pagerank"):
        eng = FlipEngine.build(g, algo, tile=32)
        srcs = np.array([5, 0, 17, 23])
        outs, steps = eng.execute(srcs, distributed=True)
        assert outs.shape == (4, g.n) and steps.shape == (4,)
        for b, s in enumerate(srcs):
            ref, _ = reference.run(algo, g, int(s))
            assert ALGEBRAS[algo].results_match(outs[b], ref), (algo, b)
            solo, st = eng.execute(int(s), distributed=True)
            assert np.array_equal(outs[b], solo), (algo, b)
            assert steps[b] == st, (algo, b)
    print("OK")
    """)
    assert "OK" in out


# ----------------------- MoE dispatch equivalence ---------------------- #
def test_moe_all_to_all_matches_gspmd():
    out = _run_sub("""
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.distributed.sharding import mesh_context
    from repro.models import moe
    from repro.models.layers import init_tree
    from repro.launch.mesh import make_mesh
    cfg = get_smoke("granite_moe_3b_a800m")
    p = init_tree(jax.random.PRNGKey(0), moe.decls(cfg), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh_context(mesh):
        y1, a1 = jax.jit(lambda p, x: moe.apply(p, x, cfg, "gspmd"))(p, x)
        y2, a2 = jax.jit(lambda p, x: moe.apply(p, x, cfg,
                                                "all_to_all"))(p, x)
    assert float(jnp.abs(y1 - y2).max()) < 2e-5, float(jnp.abs(y1-y2).max())
    assert abs(float(a1) - float(a2)) < 1e-4
    print("OK")
    """)
    assert "OK" in out


# ----------------------- compressed psum over pods ---------------------- #
def test_compressed_psum_pod_axis():
    out = _run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("pod",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                    jnp.float32)
    @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
             out_specs=P("pod"), check_vma=False)
    def f(xs):
        mean, fb = compressed_psum(xs[0], "pod")
        return mean[None]
    got = f(x)[0]
    want = x.mean(axis=0)
    scale = float(jnp.abs(x).max()) / 127
    assert float(jnp.abs(got - want).max()) <= scale, "compression error"
    print("OK")
    """, devices=4)
    assert "OK" in out


# ----------------------- sharded train-step parity ---------------------- #
def test_sharded_train_step_matches_single_device():
    out = _run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke
    from repro.distributed.sharding import mesh_context, DEFAULT_RULES
    from repro.launch import steps as S
    from repro.models import model as M
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig
    from repro.launch.mesh import make_mesh
    cfg = get_smoke("qwen3_0_6b")
    opt_cfg = AdamWConfig()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params,
             "opt": adamw.init_opt_state(params, opt_cfg)}
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
             "labels": jnp.ones((4, 32), jnp.int32)}
    step = S.make_train_step(cfg, opt_cfg, impl="plain")
    # single device
    s1, m1 = jax.jit(step)(jax.tree_util.tree_map(lambda x: x, state), batch)
    # 8-device mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh_context(mesh, DEFAULT_RULES):
        sh = S.train_state_shardings(cfg, mesh, opt_cfg)
        bsh = S.batch_shardings(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}, mesh)
        s2, m2 = jax.jit(step, in_shardings=(sh, bsh),
                         out_shardings=(sh, None))(state, batch)
    d = abs(float(m1["loss"]) - float(m2["loss"]))
    assert d < 1e-3, d
    for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                    jax.tree_util.tree_leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-3)
    print("OK")
    """)
    assert "OK" in out
