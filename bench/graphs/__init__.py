"""Seeded graph generators of the benchmark, found by name.

A configuration file names its generator (``"generator": "kronecker"``);
`generate` imports ``bench.graphs.<generator>`` and calls its
``generate(config, seed) -> CSR``. Every generator is vectorized numpy
(no per-edge Python) and depends on nothing of the program under test, so
the plain reference and the harness read the same arrays the program is
given.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class CSR:
    """Undirected weighted graph stored as both half-edges, sorted by
    (source, destination), with no self-loops and no parallel edges."""
    indptr: np.ndarray    # (n+1,) int32
    indices: np.ndarray   # (m,)   int32, destination of each half-edge
    weights: np.ndarray   # (m,)   float32

    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    @property
    def m(self) -> int:
        return len(self.indices)

    def sources(self) -> np.ndarray:
        """(m,) int64 source vertex of each half-edge."""
        return np.repeat(np.arange(self.n, dtype=np.int64),
                         np.diff(self.indptr))


def from_undirected(n: int, u: np.ndarray, v: np.ndarray,
                    w: np.ndarray) -> CSR:
    """CSR holding both half-edges of each undirected edge (u, v, w).
    Self-loops are dropped; of parallel edges the lightest is kept (the
    only one a shortest path can use)."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    w = np.asarray(w, dtype=np.float32)
    keep = u != v
    lo, hi, w = np.minimum(u, v)[keep], np.maximum(u, v)[keep], w[keep]
    key = lo * n + hi
    order = np.lexsort((w, key))               # lightest first per key
    key, w = key[order], w[order]
    first = np.ones(key.size, dtype=bool)
    first[1:] = key[1:] != key[:-1]
    key, w = key[first], w[first]
    lo, hi = key // n, key % n
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    ww = np.concatenate([w, w])
    order = np.argsort(src * n + dst, kind="stable")
    src, dst, ww = src[order], dst[order], ww[order]
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(src, minlength=n))]).astype(np.int32)
    return CSR(indptr=indptr, indices=dst.astype(np.int32),
               weights=ww.astype(np.float32))


def _roots(parent: np.ndarray) -> np.ndarray:
    """Follow parent pointers to the root of every vertex."""
    while True:
        grand = parent[parent]
        if np.array_equal(grand, parent):
            return parent
        parent = grand


def _hook(parent: np.ndarray, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Union the sets of every pair (u, v); returns root labels (the
    least vertex of each set)."""
    parent = _roots(parent)
    while True:
        ru, rv = parent[u], parent[v]
        cut = ru != rv
        if not cut.any():
            return parent
        ru, rv = ru[cut], rv[cut]
        np.minimum.at(parent, np.maximum(ru, rv), np.minimum(ru, rv))
        parent = _roots(parent)
        u, v = u[cut], v[cut]


def components(csr: CSR) -> np.ndarray:
    """(n,) component label of each vertex: its component's least id."""
    u = csr.sources()
    return _hook(np.arange(csr.n, dtype=np.int64), u,
                 csr.indices.astype(np.int64))


def generate(config: dict, seed: int) -> CSR:
    """The graph a configuration describes, drawn from `seed`."""
    name = config["generator"]
    if not name.replace("_", "").isalnum():
        raise ValueError(f"bad generator name {name!r}")
    mod = importlib.import_module(f"bench.graphs.{name}")
    return mod.generate(config, int(seed))
