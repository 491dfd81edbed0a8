"""Single-source shortest paths by frontier Bellman-Ford.

Round k relaxes every out-edge of the vertices whose distance improved
in round k-1, so the distances are the least fixpoint of

    d(v) = min(d(v), min_{u -> v} d(u) + w(u, v)),   d(src) = 0,

with every sum rounded to `dtype`. Rounded addition is monotone, so that
fixpoint does not depend on the order in which edges are relaxed: any
exact min-plus solver in the same precision returns the same bits.
"""
from __future__ import annotations

import numpy as np


def _out_edges(indptr: np.ndarray, front: np.ndarray):
    """(source, edge index) of every out-edge of the vertices in `front`."""
    start = indptr[front].astype(np.int64)
    count = indptr[front + 1].astype(np.int64) - start
    first = np.cumsum(count) - count
    edge = np.repeat(start - first, count) + np.arange(count.sum())
    return np.repeat(front, count), edge


def sssp(csr, src: int, dtype=np.float32) -> np.ndarray:
    """(n,) distances from `src` in `dtype` (inf where unreached)."""
    n = csr.n
    w = csr.weights.astype(dtype)
    dist = np.full(n, np.inf, dtype=dtype)
    dist[src] = 0
    front = np.asarray([src], dtype=np.int64)
    best = np.full(n, np.inf, dtype=np.float64)
    while front.size:
        u, e = _out_edges(csr.indptr, front)
        v = csr.indices[e]
        cand = dist[u] + w[e]                      # rounded to dtype
        np.minimum.at(best, v, cand.astype(np.float64))
        dst = np.unique(v)
        gain = best[dst] < dist[dst].astype(np.float64)
        front = dst[gain]
        dist[front] = best[front].astype(dtype)
        best[dst] = np.inf
    return dist


# the harness's view: a traversal covers its source's component, and the
# comparison is exact -- the count of vertices whose distance differs
# from the reference's in any bit (unreached on both sides agrees)
SOURCED = True
LIMITS = {"dist_mismatch": 0}


def solve(csr, src: int, dtype=np.float32) -> np.ndarray:
    return sssp(csr, src, dtype)


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    return {"dist_mismatch": int(np.count_nonzero(~(got == want)))}
