"""Public ops for the frontier relaxation kernel.

`build_blocks` converts a CSR graph (+ optional FLIP mapping, whose
vertex->PE placement becomes the vertex->tile permutation: the compiled
placement minimizes cross-tile edges exactly like it minimizes NoC hops)
into the block-sparse tile form the kernel consumes. The algorithm's
`VertexAlgebra` decides the stored ⊗ operand per edge (`edge_value`) and
the fill for absent edges (the semiring's ⊕-identity, so empty lanes drop
out of every reduction). The build is fully vectorized: one numpy
key-sort + `ufunc.at` semiring scatter, no per-edge Python loop.

`frontier_relax` dispatches: Pallas on TPU, Pallas-interpret when forced
(tests), and a vectorized segment-reduce jnp fallback elsewhere (CPU).

Frontier-compacted block streaming (``compact=True``): FLIP's headline
win is that *inactive vertices cost nothing*, and on a memory-bound relax
kernel that has to include the memory system, not just the ALUs. Each
step we derive per-tile activity from the source values (a tile is active
iff any lane differs from the ⊕-identity -- exactly the kernel's
packet-trigger condition), map it onto the block list, and compact the
active blocks to the front of a *fixed-size* index list with a masked
cumsum + scatter (the list is pre-sorted by ``bdst``, so a stable
compaction preserves the consecutive-visit accumulation order -- no sort
at runtime). Inactive slots all point at one designated all-identity
sentinel block (`BlockedGraph.blocks_ext`), so the Pallas index map
re-fetches one tiny VMEM-resident block instead of streaming dead weight
blocks: HBM traffic drops from O(nb·T²) to O(active·T²) + ε per step
while every shape stays static (no recompiles). Because the ⊕-identity
annihilates ⊗, the sentinel relax is an exact no-op, so compacted results
are bit-for-bit the dense-streaming results.

Where the kernel runs its source-run body (`frontier.relax_walk` ==
'src': B a multiple of 8 under an idempotent ⊕), the stream is walked
source-major instead, through a static permutation per layout built
once on the host: `BlockedGraph.src_order` (a stable argsort of bsrc)
and `src_tiles` (the source tile of each slot in that order). The
compaction reads the activity mask through `src_tiles` and scatters
`src_order`'s entries, so the blocks stay in place, in one layout, and
the active prefix names them in (bsrc, bdst) order: the same masked
cumsum and scatter, never a sort on the device, and no more gathers.

On the jnp/CPU path the relax streams every block, compacted or not:
static shapes under `jit` cannot shrink, the dense stream is the
compacted one's fixed-size bound, and the results are the same.

Sharded layout (`BlockKeys.build(mesh=...)`, `BlockedGraph.shard`): for
a graph whose blocks one device cannot hold, device k of a mesh axis
owns a contiguous range of destination tiles and the blocks that write
them -- one contiguous range of the bdst-sorted list. Each device's
slab is its blocks, ⊕-identity padding up to the largest slab, and one
sentinel, built on the host one device at a time and placed on its own
device only: the whole layout never exists on one device, and each
device holds its weights once (the slab is both the dense and the
sentinel-extended stream). Padding slots are not `live`, so compaction
never streams them. Each slab has its own `src_order` and `src_tiles`,
over its slots.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.algebra import MIN_PLUS, Semiring, VertexAlgebra, get_algebra
from repro.graphs.csr import Graph
from repro.kernels.frontier.frontier import (frontier_relax_pallas,
                                             relax_body, relax_grid_steps,
                                             relax_path, relax_walk)
from repro.obs.spans import span


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """How a sharded `BlockedGraph` lies over a mesh axis. Device k owns
    destination tiles [k * tiles_per_dev, (k + 1) * tiles_per_dev) and
    the blocks that write them, positions starts[k]:starts[k+1] of the
    bdst-sorted list, as a slab of `slots` stream slots (its blocks,
    then padding) and one trailing sentinel."""
    mesh: Mesh
    axis: str
    tiles_per_dev: int
    slots: int                  # stream slots per device (largest slab)
    starts: np.ndarray          # (ndev+1,) slab bounds in the sorted list
    keys: np.ndarray            # (nb,) i64 bdst * ntiles + bsrc, sorted

    @property
    def ndev(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def ntiles_p(self) -> int:
        """Tiles of the state the devices exchange: a multiple of ndev."""
        return self.tiles_per_dev * self.ndev

    @property
    def n_blocks(self) -> int:
        return int(self.keys.size)

    def rows(self, pos: np.ndarray) -> np.ndarray:
        """Rows of the sharded block array holding the blocks at
        positions `pos` of the bdst-sorted list."""
        k = np.searchsorted(self.starts, pos, side="right") - 1
        return k * (self.slots + 1) + (pos - self.starts[k])

    def key(self) -> tuple:
        return mesh_key(self.mesh, self.axis)


def mesh_key(mesh: Mesh, axis: str) -> tuple:
    """What identifies a mesh for compiled programs: its device ids in
    order and its axis names (plus the axis the tiles shard over)."""
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), tuple(mesh.devices.shape), axis)


@dataclasses.dataclass
class BlockedGraph:
    """Block-sparse tiled adjacency over one algebra's semiring. With
    `shards` set, the arrays are a sharded layout's per-device streams
    concatenated over the mesh axis (module doc): device k's `bsrc`/
    `bdst`/`live` are its `slots` entries (destination tiles local to
    it), and its `blocks` (= `blocks_ext`) its slots plus sentinel."""
    n: int                      # true vertex count
    tile: int                   # T
    ntiles: int
    blocks: jnp.ndarray         # (nb, T, T) f32, ⊕-identity = no edge
    bsrc: jnp.ndarray           # (nb,) i32, sorted by (bdst, bsrc)
    bdst: jnp.ndarray           # (nb,) i32
    perm: np.ndarray            # original vertex id -> tiled position
    inv_perm: np.ndarray        # tiled position -> original vertex id
    algebra: VertexAlgebra = None
    # (nb+1, T, T): `blocks` plus one trailing all-⊕-identity sentinel
    # block. Compacted streaming points every inactive slot at index nb,
    # so the sentinel is fetched once and stays VMEM-resident while the
    # dead blocks it stands in for never leave HBM.
    blocks_ext: jnp.ndarray = None
    # (ntiles+1,) i32 per-destination segment layout: the blocks writing
    # destination tile d occupy bdst-sorted positions
    # dst_start[d]:dst_start[d+1] (a sharded layout splits the list at
    # these bounds).
    dst_start: np.ndarray = None
    version: int = 0            # Graph.version this layout was built from
    graph_fp: str = None        # Graph.fingerprint() of that graph, so
                                # engine caches can detect stale layouts
    # (nslots,) bool: the stream slots that hold a block (None: all do).
    # A sharded slab's padding is not live, so compaction skips it.
    live: jnp.ndarray = None
    shards: ShardLayout = None  # set on a sharded layout
    # the source walk (`frontier.relax_walk`, `_src_walk`), per device
    # on a sharded layout: (nslots,) i32 slot positions in source-major
    # (bsrc, bdst) order, and each one's source tile (-1: no block). The
    # blocks stay where they are.
    src_order: jnp.ndarray = None
    src_tiles: jnp.ndarray = None

    def __post_init__(self):
        if self.bsrc is None or isinstance(self.bsrc, jax.core.Tracer):
            return      # a program's view (`static`): nothing to derive
        # precompute eagerly (construction always happens on the host):
        # materializing these lazily inside a trace would cache tracers
        if self.blocks_ext is None and self.algebra is not None:
            sentinel = jnp.full((1, self.tile, self.tile),
                                np.float32(self.semiring.zero), jnp.float32)
            self.blocks_ext = jnp.concatenate([self.blocks, sentinel],
                                              axis=0)
        if self.dst_start is None:
            self.dst_start = np.searchsorted(
                np.asarray(self.bdst),
                np.arange(self.ntiles + 1)).astype(np.int32)
        if self.src_order is None:
            self.src_order, self.src_tiles = map(
                jnp.asarray, _src_walk(np.asarray(self.bsrc)))

    def static(self) -> "BlockedGraph":
        """This layout's static fields alone, every array None: what a
        compiled fixpoint program closes over. The program rebuilds its
        view from its traced arguments, so it holds none of a layout's
        arrays, and programs are shared across layouts
        (`FlipEngine._dense_fixpoint_jit`)."""
        sh = self.shards and dataclasses.replace(self.shards, starts=None,
                                                 keys=None)
        return BlockedGraph(n=self.n, tile=self.tile, ntiles=self.ntiles,
                            blocks=None, bsrc=None, bdst=None, perm=None,
                            inv_perm=None, algebra=self.algebra, shards=sh)

    @property
    def padded_n(self) -> int:
        return self.ntiles * self.tile

    @property
    def n_blocks(self) -> int:
        """Real weight blocks (padding and sentinels excluded)."""
        if self.shards is not None:
            return self.shards.n_blocks
        return int(self.bsrc.shape[0])

    def shard(self, mesh: Mesh, axis: str = "data") -> "BlockedGraph":
        """This (local) layout re-laid over `mesh`'s `axis` (module doc),
        copying its blocks to the host once."""
        if self.shards is not None:
            raise ValueError("the layout is sharded already")
        keys = (np.asarray(self.bdst, np.int64) * self.ntiles
                + np.asarray(self.bsrc, np.int64))
        blocks = np.asarray(self.blocks)

        def fill(s, e, out):
            out[:e - s] = blocks[s:e]
        return _sharded(keys, mesh, axis, fill, n=self.n, tile=self.tile,
                        ntiles=self.ntiles, perm=self.perm,
                        inv_perm=self.inv_perm, algebra=self.algebra,
                        version=self.version, graph_fp=self.graph_fp)

    @property
    def semiring(self) -> Semiring:
        if self.algebra is None:
            raise ValueError("BlockedGraph built without an algebra; "
                             "construct it via build_blocks(graph, algo)")
        return self.algebra.semiring

    def to_tiled(self, attrs_orig: np.ndarray, fill=None,
                 features: bool = False) -> jnp.ndarray:
        """(n,) -> (ntiles, T), or batched (B, n) -> (B, ntiles, T);
        padded lanes hold `fill` (default: the ⊕-identity).
        `features=True` treats the trailing axis as the feature width d:
        (n, d) -> (ntiles, T, d), (B, n, d) -> (B, ntiles, T, d)."""
        if fill is None:
            fill = np.float32(self.semiring.zero)
        attrs_orig = np.asarray(attrs_orig)
        if features:
            lead, d = attrs_orig.shape[:-2], attrs_orig.shape[-1]
            out = np.full(lead + (self.padded_n, d), fill, dtype=np.float32)
            out[..., self.perm, :] = attrs_orig
            return jnp.asarray(
                out.reshape(lead + (self.ntiles, self.tile, d)))
        lead = attrs_orig.shape[:-1]
        out = np.full(lead + (self.padded_n,), fill, dtype=np.float32)
        out[..., self.perm] = attrs_orig
        return jnp.asarray(out.reshape(lead + (self.ntiles, self.tile)))

    def to_orig(self, attrs_tiled, features: bool = False) -> np.ndarray:
        """(ntiles, T) -> (n,), or batched (B, ntiles, T) -> (B, n);
        with `features=True` the trailing feature axis rides along:
        (…, ntiles, T, d) -> (…, n, d)."""
        flat = np.asarray(attrs_tiled)
        if features:
            d = flat.shape[-1]
            flat = flat.reshape(flat.shape[:-3] + (-1, d))
            return flat[..., self.perm, :]
        flat = flat.reshape(flat.shape[:-2] + (-1,))
        return flat[..., self.perm]

    # ------------------------------------------------------------------ #
    # streaming mutations: rebuild only the touched tiles
    # ------------------------------------------------------------------ #
    def apply_updates(self, new_graph: Graph,
                      updates) -> tuple["BlockedGraph", "UpdateDelta"]:
        """Incremental re-block against `new_graph` (the post-update
        Graph, i.e. ``graph.apply_updates(updates)``), reusing this
        layout's vertex permutation and tiling.

        Only the tile pairs touched by `updates` are recomputed, through
        the same vectorized semiring `ufunc.at` scatter as `build_blocks`.
        When every touched pair keeps a (non-empty) block, the update is
        value-only: `bsrc`/`bdst` (and every shape) are reused unchanged,
        so compiled relax executables keyed on them stay hot. A batch
        that activates a previously empty tile pair appends blocks, and
        one that empties an off-diagonal block drops it (diagonal blocks
        always stay: they seed the carry); either way the key order is
        re-sorted and `shape_changed=True`. The resulting layout is
        always block-for-block identical to a from-scratch
        `build_blocks` over `new_graph`, so layouts never accumulate
        cruft across long mutation streams.

        Returns ``(new_bg, delta)``; `delta` carries the per-algebra
        warm-start verdict (`Semiring.monotone_under` over the changed
        cells) and the affected source vertices that seed the resumed
        frontier.
        """
        alg, sr, t, ntiles = self.algebra, self.semiring, self.tile, \
            self.ntiles
        if alg is None:
            raise ValueError("BlockedGraph built without an algebra")
        if new_graph.n != self.n:
            raise ValueError(
                f"apply_updates keeps the vertex set fixed: layout has "
                f"n={self.n}, updated graph has n={new_graph.n}")
        perm = self.perm

        # dirty (u, v) endpoint pairs in every stored direction: the
        # graph's own mirroring (undirected CSR) and the algebra's
        # both-half-edges rule (WCC) each add the reverse pair
        uu, vv = [], []
        for upd in updates:
            u, v = int(upd[0]), int(upd[1])
            uu.append(u), vv.append(v)
            if not new_graph.directed or alg.undirected:
                uu.append(v), vv.append(u)
        # degree-dependent ⊗ operands (delta-PageRank): a changed
        # out-degree re-values every surviving out-edge of the source,
        # so all of its tiles are dirty, not just the updated cell
        if alg.weight_rule == "degree_damped":
            for s in sorted(set(uu)):
                for x in new_graph.neighbors(s):
                    uu.append(s), vv.append(int(x))
        u_arr = np.asarray(uu, dtype=np.int64)
        v_arr = np.asarray(vv, dtype=np.int64)
        pu, pv = perm[u_arr], perm[v_arr]
        dkeys = np.unique((pv // t) * ntiles + (pu // t))
        if dkeys.size == 0:                    # empty batch: version-only
            new_bg = dataclasses.replace(
                self, version=new_graph.version,
                graph_fp=new_graph.fingerprint())
            return new_bg, UpdateDelta(
                monotone=sr.monotone_under([], []), shape_changed=False,
                affected_src=np.zeros(0, dtype=np.int64),
                n_blocks_rebuilt=0, version=new_graph.version)

        # rebuild the dirty tiles from the new graph's edges -- the same
        # key-sort + semiring-scatter path as build_blocks, restricted to
        # edges that land in a dirty tile pair
        eu = new_graph.edge_sources()
        ev = new_graph.indices.astype(np.int64)
        w = alg.edge_values(eu, ev, new_graph.weights,
                            new_graph.out_degree())
        if alg.undirected:
            eu, ev = np.concatenate([eu, ev]), np.concatenate([ev, eu])
            w = np.concatenate([w, w])
        peu, pev = perm[eu], perm[ev]
        ekey = (pev // t) * ntiles + (peu // t)
        kpos = np.searchsorted(dkeys, ekey)
        sel = np.flatnonzero(
            (kpos < dkeys.size)
            & (dkeys[np.minimum(kpos, dkeys.size - 1)] == ekey))
        fresh = np.full((dkeys.size, t, t), np.float32(sr.zero),
                        dtype=np.float32)
        lin = (kpos[sel] * t + peu[sel] % t) * t + pev[sel] % t
        _scatter_edges(sr, fresh.reshape(-1), lin,
                       w[sel].astype(np.float32))

        # old values of the same cells (⊕-identity where no block exists
        # yet) drive the monotonicity verdict and the frontier seeds;
        # only the dirty blocks are gathered from the device array --
        # the full block tensor never round-trips through the host on
        # the (common) value-only path
        sh = self.shards
        old_keys = (sh.keys if sh is not None
                    else np.asarray(self.bdst, dtype=np.int64) * ntiles
                    + np.asarray(self.bsrc, dtype=np.int64))
        nb = old_keys.size
        opos = np.searchsorted(old_keys, dkeys)
        exists = ((opos < nb)
                  & (old_keys[np.minimum(opos, nb - 1)] == dkeys))
        opos_e = opos[exists]
        rows = opos_e if sh is None else sh.rows(opos_e)
        old = np.full_like(fresh, np.float32(sr.zero))
        if opos_e.size:
            old[exists] = np.asarray(self.blocks[rows])
        monotone = sr.monotone_under(old, fresh)

        # affected sources: original ids of the lanes whose out-edge
        # cells changed -- the warm-start frontier seed
        changed_rows = (old != fresh).any(axis=2)        # (ndirty, t)
        blk, row = np.nonzero(changed_rows)
        pos = (dkeys[blk] % ntiles) * t + row            # tiled positions
        pos = pos[pos < self.n]                          # drop padding
        affected = np.unique(self.inv_perm[pos]).astype(np.int64)

        fp = new_graph.fingerprint()
        # keep the layout identical to a from-scratch build: a missing
        # tile pair only grows the list if it actually gained edges (a
        # delete of an absent edge stays a no-op), and an off-diagonal
        # block emptied by deletions is dropped (diagonal blocks always
        # stay -- they initialize the carry for their destination tile)
        empty = ~(fresh != np.float32(sr.zero)).any(axis=(1, 2))
        diag = (dkeys // ntiles) == (dkeys % ntiles)
        grow = ~exists & ~empty
        drop = exists & empty & ~diag
        if not grow.any() and not drop.any():
            upd = self.blocks
            if opos_e.size:                # dirty tiles patched on device
                upd = upd.at[rows].set(jnp.asarray(fresh[exists]))
            # a local layout re-derives its sentinel copy; a sharded one
            # holds its slabs once
            new_bg = dataclasses.replace(
                self, blocks=upd, blocks_ext=None if sh is None else upd,
                version=new_graph.version, graph_fp=fp)
            shape_changed = False
        elif sh is not None:               # re-lay the slabs from scratch
            new_bg = block_keys(new_graph, alg, t, order=self.inv_perm
                                ).build(mesh=sh.mesh, axis=sh.axis)
            shape_changed = True
        else:
            blocks = np.asarray(self.blocks).copy()
            blocks[opos_e] = fresh[exists]
            keep = np.ones(nb, dtype=bool)
            keep[opos[drop]] = False
            keys2 = np.concatenate([old_keys[keep], dkeys[grow]])
            blocks2 = np.concatenate([blocks[keep], fresh[grow]])
            order2 = np.argsort(keys2, kind="stable")
            keys2 = keys2[order2]
            new_bg = BlockedGraph(
                n=self.n, tile=t, ntiles=ntiles,
                blocks=jnp.asarray(blocks2[order2]),
                bsrc=jnp.asarray((keys2 % ntiles).astype(np.int32)),
                bdst=jnp.asarray((keys2 // ntiles).astype(np.int32)),
                perm=perm, inv_perm=self.inv_perm, algebra=alg,
                version=new_graph.version, graph_fp=fp)
            shape_changed = True
        delta = UpdateDelta(monotone=monotone, shape_changed=shape_changed,
                            affected_src=affected,
                            n_blocks_rebuilt=int(dkeys.size),
                            version=new_graph.version)
        return new_bg, delta


@dataclasses.dataclass(frozen=True)
class UpdateDelta:
    """What one `BlockedGraph.apply_updates` batch did, and whether the
    previous fixpoint may warm-start the recompute."""
    monotone: bool            # every changed cell ⊕-improved under an
                              # idempotent ⊕: resume from the old fixpoint
    shape_changed: bool       # block list grew (empty tile pair
                              # activated) or shrank (off-diagonal block
                              # emptied): compiled fns keyed on the block
                              # shapes will retrace
    affected_src: np.ndarray  # original ids of sources whose out-edge
                              # cells changed -- the warm frontier seed
    n_blocks_rebuilt: int     # dirty tiles recomputed by this batch
    version: int              # Graph.version the new layout tracks


def _scatter_edges(sr: Semiring, flat: np.ndarray, lin: np.ndarray,
                   w: np.ndarray) -> None:
    """⊕-combine edge values into flattened block storage in place
    (parallel edges merge through the semiring). Shared by the full
    build and the incremental tile rebuild so the two can never drift:
    the ufunc `.at` fast path, with a slow exact fallback for
    non-ufunc ⊕."""
    if hasattr(sr.add_np, "at"):
        sr.add_np.at(flat, lin, w)
    else:
        for j, x in zip(lin, w):
            flat[j] = sr.add_np(flat[j], x)


@dataclasses.dataclass
class BlockKeys:
    """A graph's block structure under one algebra, before any block is
    filled: the sorted block keys (one `np.unique`) and where each
    stored edge lands. What a layout will cost is known here, before
    anything is allocated (`device_bytes`, `shard_bytes`); `build` then
    fills the blocks, on one device or sharded over a mesh axis."""
    n: int
    tile: int
    ntiles: int
    perm: np.ndarray            # original vertex id -> tiled position
    order: np.ndarray           # tiled position -> original vertex id
    algebra: VertexAlgebra
    keys: np.ndarray            # (nb,) i64 bdst * ntiles + bsrc, sorted
    edge_block: np.ndarray      # (m,) each stored edge's block position
    edge_cell: np.ndarray       # (m,) its cell in the block: row * T + col
    weights: np.ndarray         # (m,) f32 ⊗ operands
    version: int = 0
    graph_fp: str | None = None

    @property
    def n_blocks(self) -> int:
        return int(self.keys.size)

    @property
    def block_bytes(self) -> int:
        return self.tile * self.tile * 4

    def device_bytes(self) -> int:
        """Device bytes of the one-device layout: the blocks held twice
        (`blocks` and the sentinel-extended `blocks_ext`)."""
        return (2 * self.n_blocks + 1) * self.block_bytes

    def shard_bytes(self, ndev: int) -> int:
        """Device bytes on the fullest device of the layout sharded over
        `ndev` devices: its slab (the largest) and one sentinel."""
        counts = np.diff(_split(self.keys, self.ntiles, ndev)[1])
        return (max(1, int(counts.max())) + 1) * self.block_bytes

    def build(self, mesh: Mesh | None = None,
              axis: str = "data") -> "BlockedGraph":
        """The layout on the default device, or with `mesh` sharded over
        its `axis` (module doc)."""
        sr, t = self.algebra.semiring, self.tile
        fields = dict(n=self.n, tile=t, ntiles=self.ntiles, perm=self.perm,
                      inv_perm=np.asarray(self.order), algebra=self.algebra,
                      version=self.version, graph_fp=self.graph_fp)
        if mesh is not None:
            # each slab scatters its own edges, taken in the order the
            # whole build takes them, so parallel edges ⊕-combine alike
            order = np.argsort(self.edge_block, kind="stable")
            ecut = np.searchsorted(self.edge_block[order],
                                   np.arange(self.n_blocks + 1))

            def fill(s, e, out):
                sel = order[ecut[s]:ecut[e]]
                lin = (self.edge_block[sel] - s) * (t * t) \
                    + self.edge_cell[sel]
                _scatter_edges(sr, out.reshape(-1), lin, self.weights[sel])
            return _sharded(self.keys, mesh, axis, fill, **fields)
        blocks = np.full((self.n_blocks, t, t), np.float32(sr.zero),
                         dtype=np.float32)
        lin = self.edge_block * (t * t) + self.edge_cell
        _scatter_edges(sr, blocks.reshape(-1), lin, self.weights)
        return BlockedGraph(
            blocks=jnp.asarray(blocks),
            bsrc=jnp.asarray((self.keys % self.ntiles).astype(np.int32)),
            bdst=jnp.asarray((self.keys // self.ntiles).astype(np.int32)),
            **fields)


def block_keys(graph: Graph, algo: str | VertexAlgebra = "sssp",
               tile: int = 128,
               order: np.ndarray | None = None) -> BlockKeys:
    """The block structure of `graph` under an algebra (`BlockKeys`).

    algo: a registered algorithm name ('bfs', 'sssp', 'wcc', 'pagerank',
    'widest', 'reach', ...) or a `VertexAlgebra` directly. `order`:
    optional vertex ordering (e.g. from the FLIP mapping compiler);
    order[k] = original id of the vertex at tiled position k.

    Fully vectorized: edges come straight out of the CSR arrays, the ⊗
    operands from the algebra's vectorized `edge_values`, block ids from
    one `np.unique` over (bdst, bsrc) keys (already the required sort
    order) -- no per-edge Python loop.
    """
    alg = algo if isinstance(algo, VertexAlgebra) else get_algebra(algo)
    n = graph.n
    if order is None:
        order = np.arange(n)
    perm = np.empty(n, dtype=np.int64)     # original -> position
    perm[order] = np.arange(n)

    ntiles = max(1, -(-n // tile))
    outdeg = graph.out_degree()
    u = graph.edge_sources()
    v = graph.indices.astype(np.int64)
    w = alg.edge_values(u, v, graph.weights, outdeg)
    if alg.undirected:
        u, v = np.concatenate([u, v]), np.concatenate([v, u])
        w = np.concatenate([w, w])
    pu, pv = perm[u], perm[v]

    # block key = bdst * ntiles + bsrc: np.unique sorts by (bdst, bsrc),
    # exactly the consecutive-destination-visit order the kernel needs.
    # every destination tile must appear at least once so its output block
    # is initialized from the carry (all-identity blocks act as identity):
    # the diagonal keys guarantee that.
    key = (pv // tile) * ntiles + (pu // tile)
    diag = np.arange(ntiles, dtype=np.int64) * (ntiles + 1)
    uniq, inv = np.unique(np.concatenate([key, diag]), return_inverse=True)
    return BlockKeys(n=n, tile=tile, ntiles=ntiles, perm=perm, order=order,
                     algebra=alg, keys=uniq, edge_block=inv[:key.size],
                     edge_cell=(pu % tile) * tile + pv % tile,
                     weights=w.astype(np.float32), version=graph.version,
                     graph_fp=graph.fingerprint())


def build_blocks(graph: Graph, algo: str | VertexAlgebra = "sssp",
                 tile: int = 128,
                 order: np.ndarray | None = None) -> BlockedGraph:
    """Block-sparse semiring adjacency for any registered algebra, on the
    default device (`block_keys` for the arguments): parallel edges
    ⊕-combine through the semiring ufunc's `.at` scatter."""
    return block_keys(graph, algo, tile, order).build()


def _src_walk(bsrc: np.ndarray,
              live: np.ndarray | None = None) -> tuple[np.ndarray,
                                                       np.ndarray]:
    """The source walk of a (bdst, bsrc)-sorted stream: ``(order, tiles)``,
    (nslots,) i32 each -- its slots' positions in (bsrc, bdst) order (a
    stable sort by source keeps each source tile's destinations in
    order) and the source tile of each, -1 where the slot holds no block
    (not `live`)."""
    order = np.argsort(bsrc, kind="stable").astype(np.int32)
    tiles = bsrc[order].astype(np.int32)
    if live is not None:
        tiles = np.where(live[order], tiles, np.int32(-1))
    return order, tiles


def _split(keys: np.ndarray, ntiles: int,
           ndev: int) -> tuple[int, np.ndarray]:
    """``(tiles_per_dev, starts)`` of a layout sharded over `ndev`
    devices: tiles padded to a multiple of `ndev`, device k owning
    destination tiles [k * tiles_per_dev, (k + 1) * tiles_per_dev) and
    the positions starts[k]:starts[k+1] of the sorted key list."""
    tpd = -(-ntiles // ndev)
    bounds = np.arange(ndev + 1, dtype=np.int64) * tpd * ntiles
    return tpd, np.searchsorted(keys, bounds)


def _sharded(keys: np.ndarray, mesh: Mesh, axis: str, fill,
             **fields) -> BlockedGraph:
    """A layout sharded over `mesh`'s `axis` (module doc): `keys` is its
    sorted key list, ``fill(s, e, out)`` writes the blocks at sorted
    positions s:e into the first e - s rows of a slab, and `fields` are
    the other `BlockedGraph` fields. Device k's slab is built on the
    host and placed on the devices of axis index k before the next one
    is built."""
    ndev = int(mesh.shape[axis])
    ntiles, t = fields["ntiles"], fields["tile"]
    tpd, starts = _split(keys, ntiles, ndev)
    counts = np.diff(starts)
    # >= 1 so a device owning no block still has a slot, never live
    slots = max(1, int(counts.max()))
    bsrc = np.zeros((ndev, slots), np.int32)
    bdst = np.zeros((ndev, slots), np.int32)
    live = np.arange(slots)[None, :] < counts[:, None]
    for k in range(ndev):
        s, e = int(starts[k]), int(starts[k + 1])
        if e == s:
            continue
        bsrc[k, :e - s] = keys[s:e] % ntiles
        bdst[k, :e - s] = keys[s:e] // ntiles - k * tpd
        # padding repeats the last block's tile pair, so a dense sweep
        # never revisits an earlier destination
        bsrc[k, e - s:], bdst[k, e - s:] = bsrc[k, e - s - 1], \
            bdst[k, e - s - 1]
    sharding = NamedSharding(mesh, P(axis))
    shape = (ndev * (slots + 1), t, t)
    zero = np.float32(fields["algebra"].semiring.zero)
    with span("flip.shard", devices=ndev, slots=slots):
        placed = {}
        for dev, idx in sharding.addressable_devices_indices_map(
                shape).items():
            k = (idx[0].start or 0) // (slots + 1)
            placed.setdefault(k, []).append(dev)
        shards = []
        for k, devs in sorted(placed.items()):
            slab = np.full((slots + 1, t, t), zero, np.float32)
            fill(int(starts[k]), int(starts[k + 1]), slab)
            shards += [jax.device_put(slab, dev) for dev in devs]
            del slab
        blocks = jax.make_array_from_single_device_arrays(shape, sharding,
                                                          shards)
        # each slab's source walk indexes its own slots; padding repeats
        # the slab's last pair, so it sorts after that source's blocks
        walk = [_src_walk(b, lv) for b, lv in zip(bsrc, live)]
        stream = [jax.device_put(a.reshape(-1), sharding)
                  for a in (bsrc, bdst, live, np.stack([o for o, _ in walk]),
                            np.stack([t for _, t in walk]))]
    layout = ShardLayout(mesh=mesh, axis=axis, tiles_per_dev=tpd,
                         slots=slots, starts=starts, keys=keys)
    return BlockedGraph(
        blocks=blocks, blocks_ext=blocks, bsrc=stream[0], bdst=stream[1],
        live=stream[2], src_order=stream[3], src_tiles=stream[4],
        shards=layout,
        dst_start=np.searchsorted(keys // ntiles,
                                  np.arange(ntiles + 1)).astype(np.int32),
        **fields)


# --------------------------------------------------------------------- #
# frontier compaction: per-tile activity -> compacted block stream
# --------------------------------------------------------------------- #
def tile_activity(src_vals, semiring: Semiring, features: bool = False):
    """(…, ntiles, T[, d]) source values -> (ntiles,) bool per-tile
    activity.

    A tile is active iff any of its lanes (for any query of the batch,
    any feature lane when `features=True`) differs from the ⊕-identity --
    the same condition as the kernel's packet trigger, so a block whose
    source tile is inactive contributes exactly nothing (the ⊕-identity
    annihilates ⊗) and may be dropped from the stream without changing a
    single bit of the result.
    """
    axes = (-2, -1) if features else (-1,)
    act = jnp.any(src_vals != np.float32(semiring.zero), axis=axes)
    if act.ndim > 1:                       # batched: active for any query
        act = jnp.any(act, axis=tuple(range(act.ndim - 1)))
    return act


def block_activity(tile_act, bsrc, live=None):
    """(nslots,) bool: slots whose source tile is active and that hold a
    block -- the blocks a compacted step streams."""
    act = jnp.take(tile_act, bsrc)
    return act if live is None else jnp.logical_and(act, live)


def walk_activity(tile_act, src_tiles):
    """`block_activity` of the source walk's slots, in its order, from
    the layout's `src_tiles` (-1: no block)."""
    return jnp.logical_and(src_tiles >= 0,
                           jnp.take(tile_act, src_tiles, mode="clip"))


@jax.jit
def compact_block_stream(tile_act, bsrc, bdst, live=None, walk=None):
    """Stable compaction of the active blocks to the front of a fixed-size
    index list (masked cumsum + scatter -- never a sort: the list is
    already (bdst, bsrc)-sorted and stability preserves that, keeping the
    kernel's consecutive-destination accumulation semantics intact).
    `live` ((nb,) bool, optional) marks the slots that hold a block; a
    slot that does not is never active. `walk` (optional; a layout's
    ``(src_order, src_tiles)``) walks the slots in source order instead,
    so the active blocks come out in (bsrc, bdst) order; `src_tiles`
    marks the slots without a block then, and `live` is not read.

    Returns ``(bsel, bsrc_c, bdst_c, n_active)``:
      * bsel   (nb,) i32 -- slot i's index into ``blocks_ext``; slots
        ``>= n_active`` hold the sentinel index nb.
      * bsrc_c/bdst_c (nb,) i32 -- slot tile coordinates; inactive slots
        repeat the last active block's pair (or block nb-1 when nothing is
        active) so consecutive grid steps keep identical index-map
        outputs and Pallas skips the re-fetch entirely.
      * n_active -- traced active-block count.
    """
    nb = bsrc.shape[0]
    if walk is None:
        act = block_activity(tile_act, bsrc, live)
    else:
        act = walk_activity(tile_act, walk[1])
    pos = jnp.cumsum(act.astype(jnp.int32)) - 1
    n_active = jnp.sum(act.astype(jnp.int32))
    sel = jnp.full((nb,), nb, dtype=jnp.int32)
    sel = sel.at[jnp.where(act, pos, nb)].set(
        jnp.arange(nb, dtype=jnp.int32) if walk is None else walk[0],
        mode="drop")
    last = jnp.minimum(sel[jnp.maximum(n_active - 1, 0)], nb - 1)
    fill = jnp.where(jnp.arange(nb) < n_active, sel, last)
    return (sel, jnp.take(bsrc, fill), jnp.take(bdst, fill), n_active)


@functools.partial(jax.jit, static_argnames=("semiring", "features"))
def _relax_jnp(src_vals, carry, blocks, bsrc, bdst,
               semiring: Semiring = MIN_PLUS, features: bool = False):
    """Vectorized fallback: per-block ⊗-combine + segment-⊕ by bdst.

    Accepts (ntiles, T) state or batched (B, ntiles, T): the combine
    broadcasts the shared blocks over the query axis (XLA fuses the
    ⊗+reduce, so the (B, nb, T, T) product is never materialized) and the
    segment-⊕ maps over queries. `features=True` switches to vector state
    ((…, ntiles, T, d)): the combine becomes the semiring's (T, T) × (T, d)
    tile contraction (a matmul for (+, ×)) and the segment-⊕ carries the
    feature axis along.
    """
    tax = -3 if features else -2
    ntiles = carry.shape[tax]
    blocks = blocks[:bsrc.shape[0]]    # a sharded slab's sentinel stays out
    sv = jnp.take(src_vals, bsrc, axis=tax)          # (..., nb, T[, d])
    if features:
        cand = semiring.contract_jnp(sv, blocks)     # (..., nb, T, d)
    else:
        cand = semiring.add_reduce_jnp(
            semiring.mul_jnp(sv[..., :, None], blocks), axis=-2)
    def seg(x):
        return semiring.segment_reduce_jnp(x, bdst, ntiles)
    batched = cand.ndim == (4 if features else 3)
    best = jax.vmap(seg)(cand) if batched else seg(cand)
    return semiring.add_jnp(carry, best)


def resolve_relax_mode(mode: str) -> str:
    """The single 'auto' dispatch rule: Pallas on TPU, jnp elsewhere.
    Shared with `FlipEngine` so the engine's telemetry and program keys
    can never disagree with the kernel dispatch below."""
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return mode


def relax_grid(bg: BlockedGraph, batch: int, mode: str = "auto",
               feature_dim: int = 1) -> tuple[str, int, str, str]:
    """``(path, grid steps per relax step, walk, body)`` that
    `frontier_relax` takes for a (B, ntiles, T[, d]) state over `bg`,
    from static shapes and the algebra alone: the Pallas kernel's
    'grouped' or 'slab' grid, the order it walks the stream in
    (`relax_walk`) and the grouped grid's body (`relax_body`; '' on the
    slab grid), or ('jnp', 0, '', '') off the Pallas paths. On a sharded
    layout, those of each device's call."""
    if resolve_relax_mode(mode) == "jnp":
        return "jnp", 0, "", ""
    sh = bg.shards
    if sh is not None:      # per device: the whole state into its slab
        ns, ntiles, nslots = sh.ntiles_p, sh.tiles_per_dev, sh.slots
    else:
        ns = ntiles = bg.ntiles
        nslots = int(bg.bsrc.shape[0])
    path = relax_path(batch, ns, ntiles, bg.tile, feature_dim)
    body = (relax_body(batch, ns, ntiles, bg.tile, bg.semiring)
            if path == "grouped" else "")
    return (path, relax_grid_steps(path, nslots, batch),
            relax_walk(path, batch, bg.semiring), body)


def frontier_relax(src_vals, carry, bg: BlockedGraph, mode: str = "auto",
                   compact: bool = False, feature_dim: int = 1):
    """One frontier relaxation step over a BlockedGraph.

    src_vals: (ntiles, T) f32 -- attrs where active, ⊕-identity where
              not -- or (B, ntiles, T) for a batch of B queries. At
              feature_dim d > 1 the state grows a trailing feature axis:
              (ntiles, T, d) / (B, ntiles, T, d).
    carry:    same shape; values merged into every destination.
    mode: 'auto' | 'pallas' | 'interpret' | 'jnp'.
    compact: frontier-compacted block streaming -- stream only blocks
             with an active source tile (any query); exact (bit-for-bit
             the dense result). On the pallas/interpret path the
             compaction runs on-device with static shapes; the jnp path
             streams every block either way (its fixed-size bound).
    feature_dim: static feature width d; must match the state's trailing
             axis when > 1 (explicit, because (ntiles, T, d) and
             (B, ntiles, T) are rank-ambiguous).
    """
    sr = bg.semiring
    features = feature_dim > 1
    if features and src_vals.shape[-1] != feature_dim:
        raise ValueError(
            f"frontier_relax: state trailing axis {src_vals.shape[-1]} "
            f"!= feature_dim {feature_dim} (state shape "
            f"{tuple(src_vals.shape)})")
    mode = resolve_relax_mode(mode)
    if mode == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"frontier_relax(mode='pallas') needs a TPU backend, but "
            f"jax.default_backend() is {jax.default_backend()!r}; use "
            "mode='interpret' (Pallas interpreter, exact but slow) or "
            "mode='jnp' (vectorized fallback)")
    if mode == "jnp":
        return _relax_jnp(src_vals, carry, bg.blocks, bg.bsrc, bg.bdst,
                          semiring=sr, features=features)
    interpret = mode == "interpret"
    batch = src_vals.shape[0] if src_vals.ndim == 3 + features else 1
    walk = (relax_grid(bg, batch, mode, feature_dim)[2] == "src")
    if not compact:
        if not walk:
            return frontier_relax_pallas(src_vals, carry, bg.blocks,
                                         bg.bsrc, bg.bdst, semiring=sr,
                                         interpret=interpret,
                                         feature_dim=feature_dim)
        order = bg.src_order
        return frontier_relax_pallas(
            src_vals, carry, bg.blocks, jnp.take(bg.bsrc, order),
            jnp.take(bg.bdst, order), semiring=sr, interpret=interpret,
            bsel=order, feature_dim=feature_dim)
    bsel, bsrc_c, bdst_c, n_active = compact_block_stream(
        tile_activity(src_vals, sr, features), bg.bsrc, bg.bdst, bg.live,
        (bg.src_order, bg.src_tiles) if walk else None)
    return frontier_relax_pallas(src_vals, carry, bg.blocks_ext, bsrc_c,
                                 bdst_c, semiring=sr, interpret=interpret,
                                 bsel=bsel, feature_dim=feature_dim,
                                 n_active=n_active)
