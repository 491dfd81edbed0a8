"""Batched multi-query execution: (B, ntiles, T) equivalence.

`execute(srcs)` threads B independent queries through one shared
while_loop fixpoint; every row must be bit-for-bit the corresponding solo
`execute(src)` (the per-query convergence mask freezes finished queries), and
must match the numpy oracle, on the jnp fallback and the Pallas-interpret
kernel, in both data and op modes. The serving front-end adds bucketed
dispatch + tail padding on top and must preserve the same guarantee.
"""
import numpy as np
import pytest
from conftest import ALGOS, SRCS8, check_batch as _check_batch

from repro.algebra import ALGEBRAS
from repro.core.engine import FlipEngine
from repro.graphs import make_power_law, make_synthetic, reference
from repro.launch.serve_graph import GraphServer


@pytest.mark.parametrize("mode", ["data", "op"])
@pytest.mark.parametrize("algo", ALGOS)
def test_run_batch_jnp_bitexact(algo, mode):
    g = make_power_law(48, 140, seed=6)
    eng = FlipEngine.build(g, algo, tile=64, mode=mode, relax_mode="jnp")
    _check_batch(eng, g, SRCS8, algo)


@pytest.mark.parametrize("mode", ["data", "op"])
@pytest.mark.parametrize("algo", ALGOS)
def test_run_batch_interpret_kernel_bitexact(algo, mode):
    """Same contract through the Pallas kernel body (interpret mode),
    multi-tile so the batched grid's block/slab bookkeeping is real."""
    g = make_synthetic(24, 70, seed=2)
    eng = FlipEngine.build(g, algo, tile=8, mode=mode,
                           relax_mode="interpret")
    _check_batch(eng, g, SRCS8 % g.n, algo)


def test_run_batch_heterogeneous_convergence():
    """Queries finishing at very different steps: the long-tail query
    keeps relaxing while finished ones stay frozen."""
    g = make_synthetic(60, 130, seed=9)
    eng = FlipEngine.build(g, "sssp", tile=32, relax_mode="jnp")
    outs, steps = eng.execute(np.arange(8))
    assert steps.min() >= 1 and len(set(steps.tolist())) > 1
    for b in range(8):
        ref, _ = reference.run("sssp", g, b)
        assert ALGEBRAS["sssp"].results_match(outs[b], ref)


def test_run_batch_single_source_matches_run():
    g = make_synthetic(40, 110, seed=1)
    eng = FlipEngine.build(g, "widest", tile=32, relax_mode="jnp")
    outs, steps = eng.execute([7])
    solo, s = eng.execute(7)
    np.testing.assert_array_equal(outs[0], solo)
    assert steps[0] == s


# ----------------------------------------------------------------- #
# serving front-end
# ----------------------------------------------------------------- #
def test_graph_server_mixed_stream_matches_oracle():
    """Mixed-algebra stream, tail bucket not a multiple of B: bucketing,
    padding, and the per-algebra engine cache must all be transparent."""
    g = make_power_law(48, 140, seed=4)
    srv = GraphServer(g, batch=4, tile=32, relax_mode="jnp")
    rng = np.random.default_rng(0)
    algos = ["bfs", "pagerank", "widest"]
    stream = [(algos[int(rng.integers(3))], int(rng.integers(g.n)))
              for _ in range(22)]                   # 22 % 4 != 0
    reqs = srv.serve(stream)
    assert [(r.algo, r.src) for r in reqs] == stream    # order preserved
    assert srv.completed == 22
    assert len(srv._engines) == 3                   # one engine per algebra
    for r in reqs:
        assert r.done and r.steps >= 1
        ref, _ = reference.run(r.algo, g, r.src)
        assert ALGEBRAS[r.algo].results_match(r.result, ref), r.algo


def test_graph_server_padding_is_bitexact():
    """A padded tail dispatch returns exactly the solo-run results."""
    g = make_synthetic(40, 110, seed=5)
    srv = GraphServer(g, batch=8, tile=32, relax_mode="jnp")
    reqs = srv.serve([("bfs", 3), ("bfs", 17), ("bfs", 17)])
    assert srv.dispatches == 1
    eng = srv.engine("bfs")
    for r in reqs:
        solo, steps = eng.execute(r.src)
        np.testing.assert_array_equal(r.result, solo)
        assert r.steps == steps


def test_graph_server_rejects_unknown_algo():
    g = make_synthetic(20, 40, seed=0)
    srv = GraphServer(g, batch=2)
    with pytest.raises(ValueError, match="unknown algorithm"):
        srv.submit("not_an_algo", 0)
