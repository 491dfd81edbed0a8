"""Streaming graph mutations: delta-driven incremental recompute.

The contract under test:

  * `Graph.apply_updates` / `BlockedGraph.apply_updates` rebuild exactly
    the touched tiles -- block-for-block equal to a from-scratch
    `build_blocks` over the mutated graph, for every registered algebra,
    including delete-then-reinsert, updates into carry-only destination
    tiles, and batches that activate a previously empty tile pair
    (shape-changing rebuilds);
  * after a `Semiring.monotone_under` batch,
    `FlipEngine.execute(src, warm=resolve_warm(prev, delta))` resumes from the previous fixpoint with only the affected sources
    seeded, and the result is **bit-for-bit** the from-scratch run --
    across all registered algebras x {jnp, interpret} x {solo, B=8};
  * non-monotone batches (deletes, ⊕-worsening reweights, non-idempotent
    ⊕) fall back to a full recompute through the same entry point;
  * `GraphServer` interleaves updates with queries, reuses value-only
    rebuilt engines, and never serves a stale graph (fingerprint-keyed
    engine cache).
"""
import numpy as np
import pytest
from conftest import ALGOS, SRCS8, oracle

from repro.algebra import ALGEBRAS, MAX_MIN, MIN_PLUS, OR_AND, PLUS_TIMES
from repro.core.engine import FlipEngine, WarmStart
from repro.graphs import Graph, make_power_law, make_synthetic, reference
from repro.kernels.frontier import build_blocks
from repro.launch.serve_graph import GraphServer


def _edge_array(g):
    """(m, 2) int array of (u, v) edge endpoints."""
    return np.stack([g.edge_sources(),
                     g.indices.astype(np.int64)], axis=1)


def _improving_weight(algo, w):
    """A raw weight moved in the algebra's ⊕-improving direction (for
    weight rules that ignore the raw weight, any value is improving:
    the stored ⊗ operand does not change)."""
    sr = ALGEBRAS[algo].semiring
    if ALGEBRAS[algo].weight_rule != "graph":
        return w + 1.0
    for cand in (w * 0.5, w * 2.0):
        if float(sr.add_np(np.float32(cand), np.float32(w))) == \
                np.float32(cand):
            return cand
    return w


def _monotone_batch(g, algo, rng, k=3):
    """Update batch that is ⊕-improving under the algebra: inserts of
    absent edges plus ⊕-improving reweights of existing ones."""
    edges = _edge_array(g)
    have = set(map(tuple, edges.tolist()))
    batch = []
    for i in rng.choice(g.m, size=min(k, g.m), replace=False):
        u, v = map(int, edges[i])
        batch.append((u, v, _improving_weight(algo, float(g.weights[i]))))
    inserts = 0
    while inserts < k:
        u, v = int(rng.integers(g.n)), int(rng.integers(g.n))
        if (u, v) not in have and (not g.directed or u != v):
            batch.append((u, v, float(rng.integers(1, 9))))
            have.add((u, v))
            if not g.directed:
                have.add((v, u))
            inserts += 1
    return batch


def _mixed_batch(g, rng, k=3):
    """Adversarial batch: inserts + deletes + reweights both directions."""
    edges = _edge_array(g)
    idx = rng.choice(g.m, size=min(3 * k, g.m), replace=False)
    batch = [(int(edges[i][0]), int(edges[i][1]), None) for i in idx[:k]]
    batch += [(int(edges[i][0]), int(edges[i][1]),
               float(rng.integers(1, 17))) for i in idx[k:2 * k]]
    batch += [(int(rng.integers(g.n)), int(rng.integers(g.n)),
               float(rng.integers(1, 9))) for _ in range(k)]
    return batch


# --------------------------------------------------------------------- #
# blocked-layout rebuild: incremental == from-scratch, block for block
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("algo", ALGOS)
def test_apply_updates_matches_full_rebuild(algo):
    g = make_power_law(70, 210, seed=42)
    rng = np.random.default_rng(0)
    for order in (None, rng.permutation(g.n)):
        bg = build_blocks(g, algo, tile=16, order=order)
        g_cur = g
        for trial in range(3):                 # a mutation *sequence*
            batch = _mixed_batch(g_cur, rng)
            g_cur = g_cur.apply_updates(batch)
            bg, delta = bg.apply_updates(g_cur, batch)
            full = build_blocks(g_cur, algo, tile=16, order=order)
            np.testing.assert_array_equal(np.asarray(bg.bsrc),
                                          np.asarray(full.bsrc))
            np.testing.assert_array_equal(np.asarray(bg.bdst),
                                          np.asarray(full.bdst))
            np.testing.assert_array_equal(np.asarray(bg.blocks),
                                          np.asarray(full.blocks), )
            assert bg.version == g_cur.version == trial + 1
            assert bg.graph_fp == g_cur.fingerprint()


def test_apply_updates_undirected_graph_mirrors():
    """Undirected CSR: one (u, v, w) update must land in both half-edge
    tiles of the rebuilt layout."""
    from repro.graphs import make_road_network
    g = make_road_network(64, seed=2, delete_frac=0.5)
    assert not g.directed
    batch = [(0, int(g.neighbors(0)[0]), 0.25)]
    g2 = g.apply_updates(batch)
    np.testing.assert_array_equal(g2.dense_weights(),
                                  g2.dense_weights().T)
    bg = build_blocks(g, "sssp", tile=16)
    bg2, _ = bg.apply_updates(g2, batch)
    full = build_blocks(g2, "sssp", tile=16)
    np.testing.assert_array_equal(np.asarray(bg2.blocks),
                                  np.asarray(full.blocks))


def test_empty_update_batch_is_noop():
    """An empty batch (e.g. a drained stream tick) rolls the version
    forward and changes nothing else, end to end."""
    g = make_synthetic(40, 110, seed=3)
    eng = FlipEngine.build(g, "sssp", tile=16, relax_mode="jnp")
    prev, _ = eng.execute(2)
    g2 = g.apply_updates([])
    assert g2.version == g.version + 1 and g2.m == g.m
    eng2, delta = eng.apply_updates(g2, [])
    assert (delta.monotone and not delta.shape_changed
            and delta.affected_src.size == 0)
    assert eng2.bg.graph_fp == g2.fingerprint()
    out, steps = eng2.execute(2, warm=eng2.resolve_warm(prev, delta))
    assert steps == 0
    np.testing.assert_array_equal(out, prev)


def test_graph_apply_updates_semantics():
    g = make_synthetic(20, 40, seed=0)
    v0 = g.version
    # delete of an absent edge is a no-op; last write wins in a batch
    g2 = g.apply_updates([(0, 19, None), (0, 19, 5.0), (0, 19, 3.0)])
    assert g2.version == v0 + 1 and g.version == v0
    W = g2.dense_weights()
    assert W[0, 19] == 3.0
    g3 = g2.apply_updates([(0, 19, None)])
    assert g3.dense_weights()[0, 19] == np.inf
    assert g3.m == g.m                     # insert + delete round-trips
    with pytest.raises(ValueError, match="outside the fixed vertex set"):
        g.apply_updates([(0, 99, 1.0)])
    # fingerprints separate versions even with identical structure
    assert g.fingerprint() != g3.fingerprint()


# --------------------------------------------------------------------- #
# monotonicity detection (Semiring.monotone_under)
# --------------------------------------------------------------------- #
def test_monotone_under_per_semiring():
    # insert: ⊕-identity -> value is always improving (idempotent ⊕)
    assert MIN_PLUS.monotone_under([MIN_PLUS.zero], [3.0])
    assert MAX_MIN.monotone_under([MAX_MIN.zero], [3.0])
    assert OR_AND.monotone_under([OR_AND.zero], [1.0])
    # delete: value -> ⊕-identity never is
    assert not MIN_PLUS.monotone_under([3.0], [MIN_PLUS.zero])
    assert not MAX_MIN.monotone_under([3.0], [MAX_MIN.zero])
    assert not OR_AND.monotone_under([1.0], [OR_AND.zero])
    # reweight direction flips between min- and max-flavoured ⊕
    assert MIN_PLUS.monotone_under([4.0], [2.0])
    assert not MIN_PLUS.monotone_under([2.0], [4.0])
    assert MAX_MIN.monotone_under([2.0], [4.0])
    assert not MAX_MIN.monotone_under([4.0], [2.0])
    # no-op is monotone; non-idempotent ⊕ never warm-starts
    assert MIN_PLUS.monotone_under([2.0], [2.0])
    assert not PLUS_TIMES.monotone_under([0.0], [3.0])


# --------------------------------------------------------------------- #
# incremental recompute: bit-exact vs from-scratch
# --------------------------------------------------------------------- #
def _check_incremental(g, algo, relax_mode, tile, srcs, rng):
    eng = FlipEngine.build(g, algo, tile=tile, relax_mode=relax_mode)
    prev, _ = eng.execute(srcs)
    batch = _monotone_batch(g, algo, rng)
    g2 = g.apply_updates(batch)
    eng2, delta = eng.apply_updates(g2, batch)
    assert delta.monotone == ALGEBRAS[algo].semiring.idempotent
    inc, inc_steps = eng2.execute(srcs, warm=eng2.resolve_warm(prev, delta))
    scr, scr_steps = eng2.execute(srcs)
    np.testing.assert_array_equal(inc, scr)     # bit-exact, every query
    for b, s in enumerate(srcs):
        assert ALGEBRAS[algo].results_match(inc[b],
                                            oracle(algo, g2, int(s)))
    if delta.monotone:
        # the whole point: the delta fixpoint is shorter than scratch
        assert inc_steps.max() <= scr_steps.max()
    return g2, eng2, delta


@pytest.mark.parametrize("batching", ["solo", "b8"])
@pytest.mark.parametrize("algo", ALGOS)
def test_incremental_bitexact_jnp(algo, batching):
    g = make_power_law(48, 140, seed=6)
    srcs = np.array([3]) if batching == "solo" else SRCS8 % g.n
    _check_incremental(g, algo, "jnp", 16, srcs,
                       np.random.default_rng(1))


@pytest.mark.parametrize("batching", ["solo", "b8"])
@pytest.mark.parametrize("algo", ALGOS)
def test_incremental_bitexact_interpret(algo, batching):
    """Same contract through the Pallas kernel body (interpret mode)."""
    g = make_synthetic(24, 70, seed=2)
    srcs = np.array([5]) if batching == "solo" else SRCS8 % g.n
    _check_incremental(g, algo, "interpret", 8, srcs,
                       np.random.default_rng(2))


@pytest.mark.parametrize("algo", ALGOS)
def test_delete_then_reinsert(algo):
    """Delete forces a full recompute; reinserting the same edge is
    monotone again and the warm rerun lands bit-for-bit on the original
    fixpoint (the graph round-tripped)."""
    g = make_power_law(48, 150, seed=9)
    eng = FlipEngine.build(g, algo, tile=16, relax_mode="jnp")
    src = 3
    base, _ = eng.execute(src)
    u = int(g.edge_sources()[7])
    v, w = int(g.indices[7]), float(g.weights[7])

    g_del = g.apply_updates([(u, v, None)])
    eng_del, d1 = eng.apply_updates(g_del, [(u, v, None)])
    assert not d1.monotone                      # delete is never monotone
    # not monotone: falls back to scratch
    mid, _ = eng_del.execute(src, warm=eng_del.resolve_warm(base, d1))
    np.testing.assert_array_equal(mid, eng_del.execute(src)[0])
    assert ALGEBRAS[algo].results_match(mid, oracle(algo, g_del, src))

    g_re = g_del.apply_updates([(u, v, w)])
    eng_re, d2 = eng_del.apply_updates(g_re, [(u, v, w)])
    assert d2.monotone == ALGEBRAS[algo].semiring.idempotent
    fin, _ = eng_re.execute(src, warm=eng_re.resolve_warm(mid, d2))
    np.testing.assert_array_equal(fin, base)    # graph round-tripped
    # and the layout did too
    np.testing.assert_array_equal(np.asarray(eng_re.bg.blocks),
                                  np.asarray(eng.bg.blocks))


@pytest.mark.parametrize("mode", ["jnp", "interpret"])
def test_update_into_carry_only_destination_tile(mode):
    """An update whose destination tile previously had no active inbound
    block (output = pure carry) must re-derive that tile's values."""
    edges = [(0, 1), (1, 2), (2, 3), (16, 8), (17, 9), (0, 17)]
    g = Graph.from_edges(24, edges, weights=[2.0] * len(edges),
                         directed=True)
    eng = FlipEngine.build(g, "sssp", tile=8, relax_mode=mode)
    prev, _ = eng.execute(0)
    assert prev[8] == np.inf                    # tile 1 unreachable from 0
    batch = [(0, 9, 1.5)]                       # open a path into tile 1
    g2 = g.apply_updates(batch)
    eng2, delta = eng.apply_updates(g2, batch)
    assert delta.monotone
    inc, _ = eng2.execute(0, warm=eng2.resolve_warm(prev, delta))
    np.testing.assert_array_equal(inc, eng2.execute(0)[0])
    assert ALGEBRAS["sssp"].results_match(inc, oracle("sssp", g2, 0))
    assert inc[9] == 1.5


def test_update_activates_empty_tile_pair():
    """A batch inserting edges between tiles with no existing block grows
    the block list (shape-changing rebuild) and still matches a full
    rebuild + from-scratch run."""
    edges = [(0, 1), (1, 2), (8, 9), (17, 18)]  # no tile-0 -> tile-2 block
    g = Graph.from_edges(24, edges, weights=[1.0] * len(edges),
                         directed=True)
    bg = build_blocks(g, "sssp", tile=8)
    nb0 = np.asarray(bg.bsrc).size
    batch = [(1, 17, 4.0)]                      # tile 0 -> tile 2
    g2 = g.apply_updates(batch)
    bg2, delta = bg.apply_updates(g2, batch)
    assert delta.shape_changed and delta.monotone
    assert np.asarray(bg2.bsrc).size == nb0 + 1
    full = build_blocks(g2, "sssp", tile=8)
    np.testing.assert_array_equal(np.asarray(bg2.blocks),
                                  np.asarray(full.blocks))
    eng = FlipEngine.build(g, "sssp", tile=8, relax_mode="jnp")
    prev, _ = eng.execute(0)
    eng2, delta = eng.apply_updates(g2, batch)
    inc, _ = eng2.execute(0, warm=eng2.resolve_warm(prev, delta))
    np.testing.assert_array_equal(inc, eng2.execute(0)[0])
    assert inc[17] == 5.0 and inc[18] == 6.0


def test_value_only_update_keeps_layout_arrays():
    """A reweight touching only existing blocks must reuse the layout
    arrays (bsrc/bdst identity) so compiled executables stay hot."""
    g = make_power_law(48, 140, seed=3)
    bg = build_blocks(g, "sssp", tile=16)
    u = int(g.edge_sources()[0])
    batch = [(u, int(g.indices[0]), float(g.weights[0]) * 0.5)]
    g2 = g.apply_updates(batch)
    bg2, delta = bg.apply_updates(g2, batch)
    assert not delta.shape_changed
    assert bg2.bsrc is bg.bsrc and bg2.bdst is bg.bdst
    assert bg2.dst_start is bg.dst_start


# --------------------------------------------------------------------- #
# warm-start plumbing
# --------------------------------------------------------------------- #
def test_warm_start_validation_and_noop():
    g = make_synthetic(40, 110, seed=1)
    eng = FlipEngine.build(g, "pagerank", tile=16, relax_mode="jnp")
    with pytest.raises(ValueError, match="monotone algebra"):
        eng.execute(0, warm=WarmStart(np.zeros(g.n, np.float32),
                                  np.array([0])))
    eng = FlipEngine.build(g, "sssp", tile=16, relax_mode="jnp")
    base, _ = eng.execute(2)
    # empty seed set: nothing to relax, zero steps, result untouched
    out, steps = eng.execute(2,
                             warm=WarmStart(base, np.array([], np.int64)))
    assert steps == 0
    np.testing.assert_array_equal(out, base)


def test_run_distributed_warm_start():
    """The warm-start path through the shard_map engine (1-device mesh
    on CPU CI; real meshes shard the same code)."""
    g = make_power_law(48, 140, seed=5)
    eng = FlipEngine.build(g, "sssp", tile=16)
    prev, _ = eng.execute(3)
    rng = np.random.default_rng(4)
    batch = _monotone_batch(g, "sssp", rng)
    g2 = g.apply_updates(batch)
    eng2, delta = eng.apply_updates(g2, batch)
    assert delta.monotone
    warm = WarmStart(prev, delta.affected_src)
    got, _ = eng2.execute(3, warm=warm, distributed=True)
    np.testing.assert_array_equal(got, eng2.execute(3)[0])


# --------------------------------------------------------------------- #
# serving front-end: interleaved updates + stale-cache regression
# --------------------------------------------------------------------- #
def test_graph_server_update_interleaved_with_queries():
    g = make_power_law(48, 140, seed=4)
    srv = GraphServer(g, batch=4, tile=16, relax_mode="jnp")
    rng = np.random.default_rng(0)
    batch1 = _monotone_batch(g, "sssp", rng)
    g2 = g.apply_updates(batch1)
    batch2 = [(int(g2.edge_sources()[5]),
               int(g2.indices[5]), None)]       # delete: non-monotone
    g3 = g2.apply_updates(batch2)
    stream = ([("sssp", 3), ("bfs", 7), ("update", batch1),
               ("sssp", 3), ("bfs", 7), ("update", batch2),
               ("sssp", 3)])
    reqs = srv.serve(stream)
    assert srv.updates_applied == 2
    graphs = [g, g, g2, g2, g3]
    for r, gg in zip(reqs, graphs):
        assert ALGEBRAS[r.algo].results_match(
            r.result, oracle(r.algo, gg, r.src)), (r.algo, r.src)


def test_graph_server_value_only_update_reuses_engine():
    """A value-only mutation must patch the cached engine in place (same
    layout arrays -> same compiled executables), not rebuild it."""
    g = make_power_law(48, 140, seed=8)
    srv = GraphServer(g, batch=2, tile=16, relax_mode="jnp")
    srv.serve([("sssp", 1), ("sssp", 2)])
    bg_before = srv._engines["sssp"].bg
    u = int(g.edge_sources()[0])
    deltas = srv.update([(u, int(g.indices[0]),
                          float(g.weights[0]) * 0.5)])
    assert not deltas["sssp"].shape_changed
    bg_after = srv._engines["sssp"].bg
    assert bg_after.bsrc is bg_before.bsrc      # layout reused, not rebuilt
    assert bg_after.graph_fp == srv.graph.fingerprint()
    r = srv.serve([("sssp", 1)])[0]             # engine() must not rebuild
    assert srv._engines["sssp"].bg is bg_after
    assert ALGEBRAS["sssp"].results_match(
        r.result, oracle("sssp", srv.graph, 1))


def test_graph_server_update_accepts_one_shot_iterator():
    """Regression: `update()` consumes the batch once per cached engine
    plus once for the graph -- a generator-typed batch must not leave
    engines rebuilt from an exhausted (empty) iterator."""
    g = make_synthetic(40, 110, seed=7)
    srv = GraphServer(g, batch=1, tile=16, relax_mode="jnp")
    srv.serve([("sssp", 3)])
    srv.update(iter([(3, 10, 0.001)]))
    r = srv.serve([("sssp", 3)])[0]
    assert ALGEBRAS["sssp"].results_match(
        r.result, oracle("sssp", srv.graph, 3))
    assert r.result[10] == np.float32(0.001)


def test_graph_server_stale_cache_regression():
    """Regression (pre-fix: engines keyed only by algo): a wholesale
    graph swap must invalidate the cached engine, not silently serve the
    old graph's results."""
    g = make_synthetic(40, 110, seed=5)
    srv = GraphServer(g, batch=1, tile=16, relax_mode="jnp")
    r1 = srv.serve([("sssp", 3)])[0]
    assert ALGEBRAS["sssp"].results_match(r1.result, oracle("sssp", g, 3))
    g2 = make_synthetic(40, 110, seed=6)        # same shape, new content
    srv.graph = g2
    r2 = srv.serve([("sssp", 3)])[0]
    assert ALGEBRAS["sssp"].results_match(r2.result,
                                          oracle("sssp", g2, 3))
    assert not np.array_equal(r1.result, r2.result)
