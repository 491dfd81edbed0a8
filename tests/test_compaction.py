"""Frontier-compacted block streaming: exactness and layout.

Compaction must be invisible to results: a compacted relax step streams
only blocks with an active source tile (inactive slots point at one
all-identity sentinel block), and because the ⊕-identity annihilates ⊗
the outcome is bit-for-bit the dense-streaming result -- across every
registered algebra, on the jnp fallback and the Pallas-interpret kernel,
solo and batched, including the all-inactive and all-active frontier edge
cases and destinations kept alive only by their carry.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (ALGOS, cpu_only, masked_src_vals as _src_vals,
                      tiled_state)

from repro.algebra import ALGEBRAS, get_algebra
from repro.core.engine import FlipEngine
from repro.graphs import Graph, make_power_law, make_synthetic, reference
from repro.kernels.frontier import (build_blocks, compact_block_stream,
                                    frontier_relax, tile_activity)

# named frontier densities; "edge" cases required by the compaction
# contract: all-inactive (everything sentinel) and all-active (compaction
# degenerates to the dense stream)
DENSITIES = ("none", "tile0", 0.5, "all")


def _state(bg, rng, batch):
    return tiled_state(bg, rng, batch)


@pytest.mark.parametrize("batch", [0, 32], ids=["solo", "b32"])
@pytest.mark.parametrize("algo", ALGOS)
def test_compact_bitexact_vs_dense_jnp(algo, batch):
    g = make_power_law(96, 280, seed=7)
    bg = build_blocks(g, algo, tile=16)
    rng = np.random.default_rng(0)
    attrs = _state(bg, rng, batch)
    for density in DENSITIES:
        sv = _src_vals(bg, attrs, rng, density)
        dense = frontier_relax(sv, attrs, bg, mode="jnp", compact=False)
        comp = frontier_relax(sv, attrs, bg, mode="jnp", compact=True)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(comp),
                                      err_msg=f"{algo} density={density}")


@pytest.mark.parametrize("batch", [0, 32], ids=["solo", "b32"])
@pytest.mark.parametrize("algo", ALGOS)
def test_compact_bitexact_vs_dense_interpret(algo, batch):
    """Same contract through the Pallas kernel body (interpret mode):
    the sentinel-indexed block stream and the compacted bsrc/bdst scalar
    prefetch must reproduce the dense grid bit-for-bit."""
    g = make_synthetic(24, 70, seed=2)
    bg = build_blocks(g, algo, tile=8)
    rng = np.random.default_rng(1)
    attrs = _state(bg, rng, batch)
    for density in DENSITIES:
        sv = _src_vals(bg, attrs, rng, density)
        dense = frontier_relax(sv, attrs, bg, mode="interpret",
                               compact=False)
        comp = frontier_relax(sv, attrs, bg, mode="interpret",
                              compact=True)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(comp),
                                      err_msg=f"{algo} density={density}")


@pytest.mark.parametrize("mode", ["jnp", "interpret"])
def test_compact_carry_only_destination(mode):
    """A destination tile whose only incident block has an inactive source
    is fully compacted out of the stream; its output must be the carry,
    bit-for-bit (kernel: input_output_aliases; jnp: segment-⊕ identity)."""
    # tile 1 (vertices 8..15) receives edges only from tile 2; activate
    # only tile 0, so every block writing tile 1 is inactive
    edges = [(0, 1), (1, 2), (2, 3), (16, 8), (17, 9), (0, 17)]
    g = Graph.from_edges(24, edges,
                         weights=[2.0] * len(edges), directed=True)
    bg = build_blocks(g, "sssp", tile=8)
    rng = np.random.default_rng(3)
    attrs = _state(bg, rng, 0)
    mask = np.zeros(attrs.shape, dtype=bool)
    mask[0, :] = True
    sv = jnp.where(jnp.asarray(mask), attrs, np.float32(np.inf))
    dense = np.asarray(frontier_relax(sv, attrs, bg, mode=mode,
                                      compact=False))
    comp = np.asarray(frontier_relax(sv, attrs, bg, mode=mode,
                                     compact=True))
    np.testing.assert_array_equal(dense, comp)
    # the carry-only tile came back untouched
    np.testing.assert_array_equal(comp[1], np.asarray(attrs)[1])
    # and the relax really did something elsewhere (tile 0 improved)
    assert (comp[0] <= np.asarray(attrs)[0]).all()
    assert (comp[0] < np.asarray(attrs)[0]).any()


def test_compact_block_stream_layout():
    """Masked-cumsum compaction: stable (bdst order preserved), active
    prefix exact, inactive tail = sentinel index repeating the last
    active block's tile pair (so consecutive index maps are equal and the
    pipeline skips the re-fetch)."""
    bsrc = jnp.asarray([0, 1, 2, 0, 1], jnp.int32)
    bdst = jnp.asarray([0, 0, 0, 1, 2], jnp.int32)   # (bdst, bsrc)-sorted
    nb = 5
    act = jnp.asarray([True, False, True])
    bsel, bsrc_c, bdst_c, na = compact_block_stream(act, bsrc, bdst)
    assert int(na) == 3
    np.testing.assert_array_equal(np.asarray(bsel), [0, 2, 3, nb, nb])
    np.testing.assert_array_equal(np.asarray(bsrc_c), [0, 2, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(bdst_c), [0, 0, 1, 1, 1])
    assert (np.diff(np.asarray(bdst_c)) >= 0).all()   # still bdst-sorted

    # all-inactive: every slot is the sentinel, tile pair = last block's
    bsel, bsrc_c, bdst_c, na = compact_block_stream(
        jnp.zeros(3, bool), bsrc, bdst)
    assert int(na) == 0
    np.testing.assert_array_equal(np.asarray(bsel), [nb] * nb)
    np.testing.assert_array_equal(np.asarray(bsrc_c), [1] * nb)
    np.testing.assert_array_equal(np.asarray(bdst_c), [2] * nb)

    # all-active: identity selection
    bsel, bsrc_c, bdst_c, na = compact_block_stream(
        jnp.ones(3, bool), bsrc, bdst)
    assert int(na) == nb
    np.testing.assert_array_equal(np.asarray(bsel), np.arange(nb))
    np.testing.assert_array_equal(np.asarray(bsrc_c), np.asarray(bsrc))
    np.testing.assert_array_equal(np.asarray(bdst_c), np.asarray(bdst))


def test_tile_activity_matches_trigger():
    g = make_synthetic(40, 110, seed=4)
    bg = build_blocks(g, "sssp", tile=8)
    rng = np.random.default_rng(0)
    attrs = _state(bg, rng, 4)                       # batched
    mask = rng.random(attrs.shape) < 0.1
    sv = jnp.where(jnp.asarray(mask), attrs, np.float32(np.inf))
    act = np.asarray(tile_activity(sv, bg.semiring))
    want = np.asarray(sv != np.inf).any(axis=(0, 2))
    np.testing.assert_array_equal(act, want)


@pytest.mark.parametrize("algo", ALGOS)
def test_engine_compact_fixpoint_bitexact(algo):
    """End-to-end: the fixpoint over the compacted stream the chip runs
    (compact, the Pallas kernel interpreted) is bit-for-bit the dense
    jnp fixpoint -- results and per-query step counts -- and matches
    the oracle."""
    g = make_power_law(64, 190, seed=3)
    srcs = np.array([3, 11, 0, 27, 42, 8, 19, 33]) % g.n
    dense = FlipEngine.build(g, algo, tile=16, relax_mode="jnp",
                             compact=False)
    comp = FlipEngine.build(g, algo, tile=16, relax_mode="interpret",
                            compact=True)
    o1, s1 = dense.execute(srcs)
    o2, s2 = comp.execute(srcs)
    np.testing.assert_array_equal(o1, o2)
    np.testing.assert_array_equal(s1, s2)
    solo, st = comp.execute(int(srcs[0]))
    np.testing.assert_array_equal(o2[0], solo)
    assert s2[0] == st
    ref, _ = reference.run(algo, g, int(srcs[0]))
    assert ALGEBRAS[algo].results_match(o2[0], ref)


def test_compact_auto_resolution():
    g = make_synthetic(20, 50, seed=0)
    assert FlipEngine.build(g, "bfs", tile=8, mode="data")._use_compact
    assert not FlipEngine.build(g, "bfs", tile=8, mode="op")._use_compact
    assert FlipEngine.build(g, "bfs", tile=8, mode="op",
                            compact=True)._use_compact
    assert not FlipEngine.build(g, "bfs", tile=8, mode="data",
                                compact=False)._use_compact


@cpu_only
def test_pallas_mode_off_tpu_raises_clear_error():
    g = make_synthetic(20, 50, seed=0)
    bg = build_blocks(g, "bfs", tile=8)
    attrs = _state(bg, np.random.default_rng(0), 0)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        frontier_relax(attrs, attrs, bg, mode="pallas")


# ------------------------------------------------------------------ #
# vectorized build_blocks: exact vs the per-edge reference algorithm
# ------------------------------------------------------------------ #
def _build_blocks_ref(graph, algo, tile, order=None):
    """The pre-vectorization per-edge/dict algorithm, kept as the oracle
    for the numpy key-sort + ufunc.at scatter build."""
    alg = get_algebra(algo)
    sr = alg.semiring
    n = graph.n
    if order is None:
        order = np.arange(n)
    perm = np.empty(n, dtype=np.int64)
    perm[order] = np.arange(n)
    ntiles = max(1, -(-n // tile))
    outdeg = graph.out_degree()
    edges = []
    for u, v, w in graph.edge_list():
        wval = alg.edge_value(u, v, w, outdeg)
        edges.append((perm[u], perm[v], wval))
        if alg.undirected:
            edges.append((perm[v], perm[u], wval))
    by_block = {}
    for pu, pv, w in edges:
        by_block.setdefault((pv // tile, pu // tile), []).append(
            (pu % tile, pv % tile, w))
    for d in range(ntiles):
        by_block.setdefault((d, d), [])
    keys = sorted(by_block)
    blocks = np.full((len(keys), tile, tile), np.float32(sr.zero),
                     dtype=np.float32)
    bsrc = np.empty(len(keys), np.int32)
    bdst = np.empty(len(keys), np.int32)
    for i, (d, s) in enumerate(keys):
        bdst[i], bsrc[i] = d, s
        for su, dv, w in by_block[(d, s)]:
            blocks[i, su, dv] = sr.add_np(blocks[i, su, dv], np.float32(w))
    return blocks, bsrc, bdst


@pytest.mark.parametrize("algo", ALGOS)
def test_build_blocks_matches_python_reference(algo):
    g = make_synthetic(37, 120, seed=2)              # ragged: 37 = 2*16+5
    rng = np.random.default_rng(5)
    for order in (None, rng.permutation(g.n)):
        bg = build_blocks(g, algo, tile=16, order=order)
        blocks, bsrc, bdst = _build_blocks_ref(g, algo, 16, order)
        np.testing.assert_array_equal(np.asarray(bg.bsrc), bsrc)
        np.testing.assert_array_equal(np.asarray(bg.bdst), bdst)
        np.testing.assert_array_equal(np.asarray(bg.blocks), blocks)


def test_blocked_graph_layout_helpers():
    g = make_power_law(96, 280, seed=7)
    bg = build_blocks(g, "sssp", tile=16)
    nb = bg.blocks.shape[0]
    # sentinel extension: one extra all-⊕-identity block at index nb
    ext = np.asarray(bg.blocks_ext)
    assert ext.shape == (nb + 1, bg.tile, bg.tile)
    np.testing.assert_array_equal(ext[:nb], np.asarray(bg.blocks))
    assert (ext[nb] == np.float32(bg.semiring.zero)).all()
    # per-destination segment layout covers the sorted list exactly
    ds = np.asarray(bg.dst_start)
    bdst = np.asarray(bg.bdst)
    assert ds[0] == 0 and ds[-1] == nb
    for d in range(bg.ntiles):
        seg = bdst[ds[d]:ds[d + 1]]
        assert (seg == d).all() and len(seg) >= 1   # diag guarantees >=1
