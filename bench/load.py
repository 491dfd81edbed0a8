"""The one traffic generator: turns a mix file into the window's calls.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

    program   the program every call runs ("sssp", "pagerank", ...)
    batch     sources per `query` call (1: a scalar source)
    roots     which roots the calls take:
              {"draw": "nonisolated", "count": K, "set_seed": s}
                  K distinct vertices of degree > 0 of the dataset,
                  drawn uniformly with `set_seed`: the same set in every
                  run;
              {"draw": "none"}
                  programs that take no source (PageRank): vertex 0.

The roots are grouped into calls of `batch` in the order drawn, and the
run's seed orders the calls. The loop is closed: one call in flight, the
next sent when the answer is on the host, cycling through the calls.
Every seed thus gets the same calls in another order, so the seed does
not change the work a window holds.
"""
from __future__ import annotations

import numpy as np


def draw_roots(roots: dict, csr) -> np.ndarray:
    kind = roots["draw"]
    if kind == "nonisolated":
        cand = np.flatnonzero(np.diff(csr.indptr) > 0)
        return np.random.default_rng(int(roots["set_seed"])).choice(
            cand, size=int(roots["count"]), replace=False)
    if kind == "none":
        return np.zeros(1, dtype=np.int64)
    raise ValueError(f"unknown root draw {kind!r}")


def calls(traffic: dict, csr, seed: int) -> list:
    """The window's calls, in order: ints (batch 1) or (B,) arrays."""
    rng = np.random.default_rng([int(seed), 1])
    roots = draw_roots(traffic["roots"], csr)
    batch = int(traffic["batch"])
    if roots.size % batch:
        raise ValueError(f"{roots.size} roots do not fill batches of "
                         f"{batch}")
    groups = [roots[i:i + batch] for i in range(0, roots.size, batch)]
    order = rng.permutation(len(groups))
    if batch == 1:
        return [int(groups[i][0]) for i in order]
    return [groups[i] for i in order]
