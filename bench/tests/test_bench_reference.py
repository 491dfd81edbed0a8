"""`bench.reference` agrees with the program's own oracles in
`repro.graphs.reference` on small graphs, and its control differs."""
import ml_dtypes
import numpy as np
import pytest

from bench import graphs
from bench.reference import pagerank, sssp

GRAPHS = {
    "kron": {"generator": "kronecker", "dataset_seed": 0, "scale": 10,
             "edgefactor": 16,
             "initiator": [0.57, 0.19, 0.19, 0.05]},
}


def as_graph(csr):
    from repro.graphs.csr import Graph
    return Graph(indptr=csr.indptr, indices=csr.indices,
                 weights=csr.weights, directed=False)


@pytest.mark.parametrize("name", sorted(GRAPHS))
@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_sssp_agrees_with_the_program_oracle(name, seed):
    from repro.graphs import reference
    csr = graphs.generate(GRAPHS[name], seed)
    for src in np.flatnonzero(np.diff(csr.indptr))[:3]:
        got = sssp.solve(csr, int(src))
        want, _ = reference.sssp(as_graph(csr), int(src))
        assert np.array_equal(np.isinf(got), np.isinf(want))
        fin = np.isfinite(want)
        # the oracle sums a path in float64 and rounds once; float32
        # rounds every sum: equal for whole weights, a few ulp apart else
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-6)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_pagerank_agrees_with_the_program_oracle(name):
    from repro.graphs import reference
    csr = graphs.generate(GRAPHS[name], 1)
    want, _ = reference.pagerank(as_graph(csr), damping=0.85, tol=1e-12)
    np.testing.assert_allclose(pagerank.solve(csr, 0), want, rtol=1e-6)


def test_sssp_is_the_least_fixpoint():
    csr = graphs.generate(GRAPHS["kron"], 3)
    d = sssp.solve(csr, int(np.argmax(np.diff(csr.indptr))))
    u = csr.sources()
    relaxed = (d[u] + csr.weights).astype(np.float32)
    assert np.all(d[csr.indices] <= relaxed)          # no edge improves


def test_compare_is_exact_for_sssp_and_relative_for_pagerank():
    want = np.array([0.0, 1.5, np.inf], np.float32)
    assert sssp.compare(want.copy(), want) == {"dist_mismatch": 0}
    got = want.copy()
    got[1] = np.nextafter(got[1], np.float32(2))
    assert sssp.compare(got, want) == {"dist_mismatch": 1}
    got = want.copy()
    got[2] = 7.0
    assert sssp.compare(got, want) == {"dist_mismatch": 1}
    r = np.array([1e-5, 2e-5])
    assert pagerank.compare(r * (1 + 1e-4), r)["rank_rel_gap"] == \
        pytest.approx(1e-4)
    assert pagerank.compare(np.array([np.nan, 2e-5]), r)[
        "rank_rel_gap"] == 1e30


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_bfloat16_control_misses_the_reference(name):
    csr = graphs.generate(GRAPHS[name], 0)
    bf = ml_dtypes.bfloat16
    src = int(np.argmax(np.diff(csr.indptr)))
    d = sssp.compare(sssp.solve(csr, src, bf), sssp.solve(csr, src))
    assert d["dist_mismatch"] > 0
    p = pagerank.compare(pagerank.solve(csr, 0, bf), pagerank.solve(csr, 0))
    assert p["rank_rel_gap"] > pagerank.LIMITS["rank_rel_gap"]
