"""The benchmark's generators: seeded, vectorized, and the sizes the
configurations state."""
import numpy as np
import pytest

from bench import graphs
from bench.graphs import kronecker
from bench.run import Components

KRON15 = {"generator": "kronecker", "dataset_seed": 0, "scale": 15,
          "edgefactor": 16,
          "initiator": [0.57, 0.19, 0.19, 0.05]}


def blocks(csr, tile=128):
    """Tile pairs the program stores, the diagonal included."""
    nt = -(-csr.n // tile)
    key = (csr.indices // tile).astype(np.int64) * nt + csr.sources() // tile
    return np.unique(np.concatenate([key, np.arange(nt) * (nt + 1)])).size


def same(a, b):
    return (np.array_equal(a.indptr, b.indptr)
            and np.array_equal(a.indices, b.indices)
            and np.array_equal(a.weights, b.weights))


def test_generators_repeat_from_a_seed():
    # the dataset seed makes the graph; every run seed gets that graph
    a = graphs.generate(KRON15, 2**31 + 11)
    b = graphs.generate(KRON15, 12)
    c = graphs.generate(dict(KRON15, dataset_seed=1), 12)
    assert same(a, b)
    assert not same(a, c)


def test_graph500_s15_sizes():
    g = graphs.generate(KRON15, 0)
    comp = Components(g)
    giant = np.argmax(comp.vertices)
    assert (g.n, g.m) == (32768, 882_860)
    assert comp.vertices[giant] == 24_190
    assert comp.half_edges[giant] == 2 * 441_422
    assert np.count_nonzero(np.diff(g.indptr) == 0) == 8_562
    # 65,448 of the 65,536 tile pairs: 4.29 GB of dense blocks
    assert blocks(g) == 65_448


def test_from_undirected_matches_from_edges():
    from repro.graphs.csr import Graph
    rng = np.random.default_rng(0)
    u, v = rng.integers(0, 50, 400), rng.integers(0, 50, 400)
    w = rng.random(400, dtype=np.float32)
    got = graphs.from_undirected(50, u, v, w)
    keep = u != v
    pairs = list(zip(np.minimum(u, v)[keep], np.maximum(u, v)[keep]))
    want = Graph.from_edges(50, pairs, w[keep], directed=False)
    assert same(got, want)


def test_kronecker_edges_follow_the_initiator():
    # at every level an edge takes quadrant (1, 1) with probability D,
    # and a set bit of u (v) with probability C + D (B + D)
    u, v = kronecker.edges(10, 64, 0.57, 0.19, 0.19,
                           np.random.default_rng(0))
    for level in range(10):
        ub, vb = (u >> level) & 1, (v >> level) & 1
        assert abs(ub.mean() - 0.24) < 0.01
        assert abs(vb.mean() - 0.24) < 0.01
        assert abs((ub & vb).mean() - 0.05) < 0.005


def test_components_are_the_least_id_of_each_component():
    g = graphs.from_undirected(7, np.array([0, 1, 3, 5]),
                               np.array([1, 2, 4, 3]),
                               np.ones(4, np.float32))
    assert graphs.components(g).tolist() == [0, 0, 0, 3, 3, 3, 6]


def test_kronecker_runs_hold_one_dataset_relabelled():
    # the specification's permutation is applied, drawn from the dataset
    # seed: ids carry no locality, and every run holds the same graph
    small = dict(KRON15, scale=10)
    a, b = graphs.generate(small, 3), graphs.generate(small, 2**31 + 4)
    assert same(a, b)
    u, v = kronecker.edges(10, 16, 0.57, 0.19, 0.19,
                           np.random.default_rng(0))
    hub = np.bincount(np.concatenate([u, v])).argmax()
    assert hub == 0                    # the initiator favours low ids
    assert np.diff(a.indptr).argmax() != 0


def test_every_seed_gets_the_same_calls_in_another_order():
    from bench import load
    small = dict(KRON15, scale=10)
    for batch in (1, 8):
        mix = {"program": "sssp", "batch": batch,
               "roots": {"draw": "nonisolated", "count": 64,
                         "set_seed": 0}}
        seen = []
        for seed in (5, 2**31 + 6):
            g = graphs.generate(small, seed)
            calls = load.calls(mix, g, seed)
            assert len(calls) == 64 // batch
            assert all(np.diff(g.indptr)[np.atleast_1d(c)].min() > 0
                       for c in calls)
            seen.append([tuple(np.atleast_1d(c)) for c in calls])
        assert sorted(seen[0]) == sorted(seen[1]) and seen[0] != seen[1]
