"""PageRank without dangling-mass redistribution.

The fixpoint of

    p(v) = (1 - damping) / n + sum_{u -> v} damping * p(u) / outdeg(u)

by Jacobi iteration from p = 0, until no entry moves by `tol` or more
(the definition `repro.graphs.reference.pagerank` states, copied here so
that the benchmark's reference cannot move with the program). Each
edge's factor damping / outdeg(u), each product and each p(v) are
rounded to `dtype`; the sum into p(v) is taken in float64.
"""
from __future__ import annotations

import numpy as np


def pagerank(csr, damping: float = 0.85, tol: float = 1e-12,
             max_iters: int = 1000, dtype=np.float64) -> np.ndarray:
    """(n,) ranks in `dtype`."""
    n = csr.n
    u = csr.sources()
    deg = np.diff(csr.indptr).astype(np.float64)
    factor = (damping / deg[u]).astype(dtype)
    base = (1.0 - damping) / n
    p = np.zeros(n, dtype=dtype)
    for _ in range(max_iters):
        push = (p[u] * factor).astype(np.float64)
        new = (base + np.bincount(csr.indices, weights=push,
                                  minlength=n)).astype(dtype)
        delta = np.abs(new.astype(np.float64) - p.astype(np.float64)).max()
        p = new
        if delta < tol:
            break
    return p


# the harness's view: a query covers the whole graph; the number compared
# is the largest relative gap of any vertex's rank (a NaN or infinite
# rank reads as a gap of 1e30). The program stops pushing a vertex's
# residual below its tolerance 1e-9, which leaves up to 2.1e-4 of the
# smallest ranks unpushed at scale 15; the bfloat16 control misses by
# 1.2e-2. The limit sits between them, nearer the control's side.
SOURCED = False
LIMITS = {"rank_rel_gap": 2e-3}


def solve(csr, src: int, dtype=np.float64) -> np.ndarray:
    del src
    return pagerank(csr, dtype=dtype)


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    gap = np.nan_to_num(np.abs(got - want) / want, nan=1e30, posinf=1e30)
    return {"rank_rel_gap": float(gap.max())}
