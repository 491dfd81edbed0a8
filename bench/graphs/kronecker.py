"""Graph500 Kronecker graph (graph500.org specification, kernel 1 input).

A copy of the specification's reference generator in numpy: each of the
``edgefactor * 2**scale`` edges descends ``scale`` levels of the 2x2
initiator (A, B, C, 1-A-B-C), one random quadrant per level, and the
vertex ids are then randomly permuted so that ids carry no locality.
The kernel-3 (SSSP) weight of each generated edge is drawn from U[0, 1)
in float32. The graph is undirected: self-loops are dropped, and of
parallel edges the lightest is kept.

The edges, weights and the permutation all come from the
configuration's `dataset_seed`, not from the run's seed: every run holds
the same graph, so every seed gets the same work (the mean SSSP step
count over 64 roots swings by 6% from one Kronecker graph to the next)
and the same block layout, whose shapes the program compiles for once.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import CSR, from_undirected


def edges(scale: int, edgefactor: int, a: float, b: float, c: float,
          rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(u, v) of the ``edgefactor * 2**scale`` generated edges, before the
    vertex permutation."""
    m = edgefactor << scale
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        u_bit = rng.random(m) > ab
        v_bit = rng.random(m) > np.where(u_bit, c_norm, a_norm)
        u += u_bit.astype(np.int64) << level
        v += v_bit.astype(np.int64) << level
    return u, v


def generate(config: dict, seed: int) -> CSR:
    del seed                    # one dataset for every run (module doc)
    rng = np.random.default_rng(int(config["dataset_seed"]))
    scale = int(config["scale"])
    a, b, c = config["initiator"][:3]
    u, v = edges(scale, int(config["edgefactor"]), a, b, c, rng)
    w = rng.random(u.size, dtype=np.float32)
    perm = rng.permutation(1 << scale)
    return from_undirected(1 << scale, perm[u], perm[v], w)
