"""Semiring algebra subsystem: randomized cross-layer equivalence.

Per registered algebra, the same algorithm runs through every execution
layer and must agree with the plain-numpy oracle:

  * reference oracle                   (repro.graphs.reference)
  * FlipEngine data mode               (frontier-driven, jnp kernel path)
  * FlipEngine op mode                 (full-sweep, classic-CGRA analogue)
  * Pallas kernel in interpret mode    (at least one non-tropical algebra)
  * cycle simulator                    (where the program is expressible)

Graphs are small fixed-seed Erdős–Rényi (`make_synthetic`) and power-law
(`make_power_law`) instances. Engine tests use a single 64-lane tile so
jit caches one executable per (algebra, mode) across all 20 graphs; a
separate multi-tile case exercises the block-sparse bsrc/bdst path.
"""
import jax
import numpy as np
import pytest
from conftest import ALGOS, SIM_ALGOS, assert_close as _assert_close, \
    tpu_only

from repro.algebra import ALGEBRAS, get_algebra
from repro.core import PROGRAMS, compile_mapping, simulate
from repro.core.engine import FlipEngine
from repro.graphs import (make_power_law, make_road_network, make_synthetic,
                          reference)


def _graphs20():
    """20 fixed-seed graphs: 10 Erdős–Rényi + 10 power-law, one size so
    the engine's jit cache is shared across all of them."""
    for seed in range(10):
        yield make_synthetic(48, 140, seed=seed), 3 + seed % 5
        yield make_power_law(48, 140, seed=seed), 3 + seed % 5


@pytest.mark.parametrize("algo", ALGOS)
def test_engine_matches_oracle_20_graphs(algo):
    for g, src in _graphs20():
        ref, _ = reference.run(algo, g, src)
        for mode in ("data", "op"):
            eng = FlipEngine.build(g, algo, tile=64, mode=mode,
                                   relax_mode="jnp")
            got, steps = eng.run(src)
            assert steps > 0
            _assert_close(got, ref, algo, f"mode={mode}")


@pytest.mark.parametrize("algo", ALGOS)
def test_engine_multitile_blocksparse(algo):
    """ntiles > 1: exercises bsrc/bdst block bookkeeping + segment ⊕."""
    g = make_power_law(70, 210, seed=42)
    ref, _ = reference.run(algo, g, 1)
    for mode in ("data", "op"):
        eng = FlipEngine.build(g, algo, tile=16, mode=mode,
                               relax_mode="jnp")
        got, _ = eng.run(1)
        _assert_close(got, ref, algo, f"multitile mode={mode}")


@pytest.mark.parametrize("algo", ["widest", "reach", "pagerank"])
def test_interpret_kernel_non_tropical(algo):
    """The Pallas kernel body (interpret mode) on non-(min,+) semirings."""
    g = make_synthetic(40, 110, seed=7)
    ref, _ = reference.run(algo, g, 2)
    eng = FlipEngine.build(g, algo, tile=16, mode="data",
                           relax_mode="interpret")
    got, _ = eng.run(2)
    _assert_close(got, ref, algo, "interpret")


@pytest.mark.parametrize("algo", SIM_ALGOS)
def test_sim_cross_layer(algo):
    """Cycle simulator vs oracle vs engine on ER + road graphs."""
    for g, src in [(make_synthetic(48, 140, seed=11), 2),
                   (make_road_network(64, seed=2, delete_frac=0.5), 5)]:
        m = compile_mapping(g, effort=0, seed=0)
        r = simulate(m, PROGRAMS[algo], src=src)
        ref, _ = reference.run(algo, g, src)
        _assert_close(r.attrs, ref, algo, "sim")
        got, _ = FlipEngine.build(g, algo, tile=64,
                                  relax_mode="jnp").run(src)
        _assert_close(got, ref, algo, "engine-vs-sim graph")


def test_pagerank_not_expressible_on_sim():
    g = make_synthetic(32, 80, seed=0)
    m = compile_mapping(g, effort=0, seed=0)
    with pytest.raises(ValueError, match="not expressible"):
        simulate(m, PROGRAMS["pagerank"], src=0)


def _pagerank_loop(g, b, damping=0.85, tol=1e-12, max_iters=10_000):
    """The per-vertex Jacobi loop the vectorized oracles replaced:
    p = b + damping * sum_{u -> v} p[u] / outdeg(u), float64."""
    deg = g.out_degree().astype(np.float64)
    p = np.zeros_like(b)
    for _ in range(max_iters):
        contrib = p / np.maximum(deg, 1).reshape((-1,) + (1,) * (b.ndim - 1))
        new = b.copy()
        for u in range(g.n):
            lo, hi = g.indptr[u], g.indptr[u + 1]
            if deg[u] and np.any(contrib[u]):
                new[g.indices[lo:hi]] += damping * contrib[u]
        delta = np.abs(new - p).max()
        p = new
        if delta < tol:
            break
    return p.astype(np.float32)


@pytest.mark.parametrize("graph", ["power_law", "road"])
def test_damped_walk_oracles_match_loop_reference(graph):
    """The bincount pagerank / labelprop oracles add each destination's
    contributions in source order, like the loop: results are equal."""
    from repro.algebra import landmarks
    g = (make_power_law(90, 400, seed=4) if graph == "power_law"
         else make_road_network(120, seed=1, delete_frac=0.4))
    got, _ = reference.pagerank(g)
    want = _pagerank_loop(g, np.full(g.n, 0.15 / g.n))
    np.testing.assert_array_equal(got, want)
    b = np.zeros((g.n, 8))
    b[landmarks(g.n, 5, 8), np.arange(8)] = 0.15
    got, _ = reference.labelprop(g, 5)
    np.testing.assert_array_equal(got, _pagerank_loop(g, b))


def test_pagerank_mass_conservation():
    """Rank sums to (1 - leaked dangling mass) <= 1, never more."""
    g = make_power_law(48, 140, seed=3)
    got, _ = FlipEngine.build(g, "pagerank", tile=64,
                              relax_mode="jnp").run(0)
    assert 0.0 < float(np.sum(got)) <= 1.0 + 1e-4


@tpu_only
@pytest.mark.parametrize("algo", ALGOS)
def test_pallas_compiled_matches_oracle(algo):
    g = make_synthetic(120, 360, seed=1)
    ref, _ = reference.run(algo, g, 0)
    eng = FlipEngine.build(g, algo, tile=128, mode="data",
                           relax_mode="pallas")
    got, _ = eng.run(0)
    _assert_close(got, ref, algo, "pallas-compiled")


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs a real multi-device platform; the "
                           "single-device CPU CI covers run_distributed "
                           "via the forced-host subprocess tests")
@pytest.mark.parametrize("algo", ["sssp", "pagerank"])
def test_run_distributed_real_devices(algo):
    g = make_synthetic(96, 280, seed=4)
    ref, _ = reference.run(algo, g, 0)
    got, steps = FlipEngine.build(g, algo, tile=32).run_distributed(0)
    assert steps > 0
    _assert_close(got, ref, algo, "distributed")


def test_register_custom_algebra_end_to_end():
    """The registry contract: one VertexAlgebra entry opens a new
    algorithm on every layer. Minimax path = (min, max) semiring."""
    import jax
    import jax.numpy as jnp
    from repro.algebra import Semiring, VertexAlgebra, register_algebra

    min_max = Semiring(
        name="min_max", zero=float("inf"), one=float("-inf"),
        add_np=np.minimum, mul_np=np.maximum,
        add_jnp=jnp.minimum, mul_jnp=jnp.maximum,
        add_reduce_jnp=jnp.min,
        segment_reduce_jnp=lambda x, s, n: jax.ops.segment_min(
            x, s, num_segments=n),
        idempotent=True,
    )
    minimax = register_algebra(VertexAlgebra(
        "minimax_test", min_max, weight_rule="graph"))
    try:
        g = make_synthetic(40, 120, seed=9)
        # oracle: Dijkstra minimizing the max edge weight along the path
        import heapq
        best = np.full(g.n, np.inf, dtype=np.float32)
        best[2] = -np.inf
        heap = [(-np.inf, 2)]
        while heap:
            d, u = heapq.heappop(heap)
            if d > best[u]:
                continue
            for k in range(g.indptr[u], g.indptr[u + 1]):
                v = int(g.indices[k])
                cand = max(d, float(g.weights[k]))
                if cand < best[v]:
                    best[v] = np.float32(cand)
                    heapq.heappush(heap, (cand, v))
        for mode in ("data", "op"):
            got, _ = FlipEngine.build(g, minimax, tile=64, mode=mode,
                                      relax_mode="jnp").run(2)
            _assert_close(got, best, "minimax", f"mode={mode}")
        # and on the cycle simulator, unchanged
        m = compile_mapping(g, effort=0, seed=0)
        r = simulate(m, get_algebra("minimax_test"), src=2)
        _assert_close(r.attrs, best, "minimax", "sim")
    finally:
        ALGEBRAS.pop("minimax_test", None)
