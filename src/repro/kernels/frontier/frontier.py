"""Pallas TPU kernel: frontier-masked semiring relaxation, batched.

TPU-native form of FLIP's data-centric PE array (DESIGN.md Sec. 2): graph
vertices are tiled onto the 8x128 VPU lane grid; one grid step relaxes all
edges between a source tile and a destination tile held as a dense weight
block in VMEM (absent edge = the semiring's ⊕-identity). One kernel body
serves every registered algebra: the merge ⊕, combine ⊗, and reduction
are closed over as static ops, so each (semiring, tile) pair specializes
to its own executable at trace time -- tropical (min,+) for BFS/SSSP/WCC,
(max,min) for widest-path, (or,and) for reachability, (+,x) for
delta-PageRank.

The frontier bitmask plays FLIP's packet-trigger role: a block whose
source tile holds only ⊕-identity lanes is skipped entirely (`pl.when`),
so inactive regions cost (almost) no *compute* -- the kernel preserves
the paper's "only active vertices scatter" property. Because the
⊕-identity annihilates ⊗, skipping such a block is exact, not
approximate.

Compacted block streaming extends that skip to the *memory system*, where
a memory-bound relax kernel actually spends its time: the block stream is
indexed through a scalar-prefetched selection list ``bsel`` (see
`ops.compact_block_stream`), whose active prefix names real blocks in
(bdst, bsrc) order and whose inactive tail repeats one all-identity
sentinel block index. The weight BlockSpec's index map reads ``bsel[i]``,
so consecutive sentinel slots produce identical indices and the Pallas
pipeline skips their copies -- the sentinel is fetched into VMEM once and
every dead weight block stays in HBM. Per-step HBM traffic is therefore
(active + 1)·T²·4 B instead of nb·T²·4 B; the sentinel slots still run
the (T, T) VPU combine, but that compute is free under the memory bound.
The dense path is the special case ``bsel = arange(nb)``.

Block-sparsity replaces the Inter-/Intra-Tables: `bsrc/bdst` (scalar-
prefetched, so index maps can read them) name the tile pair of each block;
position inside the block is the DRF register. Blocks are sorted by
destination tile so a destination's partial ⊕ accumulates in VMEM across
consecutive grid steps (revisit-friendly "arbitrary" dimension semantics);
a compacted stream preserves that order because the compaction is stable.

Batched execution (serving-style multi-query workloads): the state is
(B, ntiles, T) -- B independent queries over one shared block structure --
and the grid gains a trailing query dimension, grid = (nb, B). The weight
block's index map ignores the query index, so each block is fetched into
VMEM once and stays resident while all B queries relax against it (the
whole point of batching: amortize the block stream over the batch). The
output/carry specs cover the destination slab of all B queries and also
ignore the query index, so every visit to one output slab is consecutive
and the single-query accumulation semantics carry over unchanged. The
packet trigger is per query: block i is skipped for query b exactly when
that query's source tile holds only ⊕-identity lanes.

Scalar state slabs: Mosaic only accepts blocks whose last two dims are
multiples of (8, 128) or span the whole array, so a one-tile (1, T)
block of the (B, ntiles, T) state is refused. The scalar state therefore
moves in slabs of SLAB = 8 consecutive tiles -- (1, 8, T) source and
(B, 8, T) carry/output blocks at tile index ``bsrc // 8`` / ``bdst // 8``
-- and the kernel reads and writes its tile's row inside the slab. The
block list is (bdst, bsrc)-sorted, so the blocks of one destination slab
are still consecutive; a slab is seeded from the carry on its first
visit. A state whose tile count is not a multiple of 8 is padded with
⊕-identity tiles inside the call. The alternative, a (B, ntiles, 1, T)
view with (1, 1, 1, T) blocks, also compiles, but HBM tiles its (1, T)
minor dims as (8, T); at T=256 on the 2^20-vertex graph (B=32) it costs
a 128 MiB relayout copy per call, which the slabs do not.

Vector-valued vertex state (feature_dim d > 1): the state blocks grow a
trailing feature axis -- (B, ntiles, T, d) -- and one grid step becomes a
(T, T) × (T, d) tile contraction via `Semiring.contract_jnp`: a true MXU
matmul (`W.T @ sv`) for (+, ×), a d-slab-swept broadcast-⊕-reduce on the
VPU for the tropical/boolean pairs. The weight block stays resident in
VMEM while the B query visits spin against it, so each streamed block is
amortized over B·d lanes instead of B -- the same HBM traffic now feeds
d× the math, which is exactly the memory-bound regime's win.

Layout: tile size T is a multiple of 128 (lane width). Per grid step
the pipeline holds one buffer each of the source slab, the carry slab,
the weight block and the output slab, and double-buffers them:

  d = 1:  2 * (8*T + B*8*T + T*T + B*8*T) * 4 B
  d > 1:  2 * (T*d' + B*T*d' + T*T + B*T*d') * 4 B
          + 8*T*d' * 4 B (min/max contraction transient)

where d' = d rounded up to 128 lanes (a (T, d) f32 slab is laid out in
(8, 128) tiles). Examples: 648 KiB for T=128, B=32, d=1; 2.8 MiB for
T=128, B=8, d=8; 1 MiB for d=128 solo -- all inside the 16 MiB scoped
VMEM budget. In HBM the scalar state is (B, ntiles, T) f32 with no
padding when ntiles is a multiple of 8. ops.py picks T; plan.resolve
validates d.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.algebra import MIN_PLUS, Semiring


# Tiles per scalar state slab. A (B, ntiles, T) state array is tiled in
# HBM and VMEM as (8, 128) f32 tiles over its last two axes, and Mosaic
# only accepts blocks whose last two dims are multiples of (8, 128) or
# span the whole axis: a one-tile (1, T) block is refused. So the scalar
# state moves in slabs of 8 consecutive tiles and the kernel picks its
# tile's row inside the slab.
SLAB = 8


@functools.lru_cache(maxsize=None)
def _make_relax_kernel(semiring: Semiring, feature_dim: int = 1,
                       rows: int = 1):
    """Specialize the kernel body for one contraction shape.

    The cache key is the full (semiring, feature_dim, rows) triple -- the
    d = 1 body relaxes one (T,) row of an (R, T) state slab (R = `rows`
    tiles per slab) while d > 1 bodies contract (1, T, d) slabs, so
    per-shape specializations must not collide on the semiring alone.
    """
    zero = float(semiring.zero)        # python literal: safe to close over
    add, mul = semiring.add_jnp, semiring.mul_jnp
    add_reduce = semiring.add_reduce_jnp
    contract = semiring.contract_jnp

    def _contract(src_ref, block_ref):
        """(T, d) feature contraction of the slab against the block.
        (+, ×) runs the semiring's MXU matmul. The idempotent semirings
        sweep the source axis in SLAB-row chunks loaded straight from the
        refs (Mosaic refuses lane slices narrower than 128, so the
        d-slab sweep of `contract_jnp` does not lower for d > 8); the
        (SLAB, T, d) broadcast transient stays small at any d, and ⊕ is
        idempotent, so the chunk order is exact."""
        if not semiring.idempotent:
            return contract(src_ref[0, 0], block_ref[0])
        acc = None
        for k in range(0, block_ref.shape[1], SLAB):
            part = add_reduce(mul(src_ref[0, 0, k:k + SLAB][:, None, :],
                                  block_ref[0, k:k + SLAB][:, :, None]),
                              axis=0)
            acc = part if acc is None else add(acc, part)
        return acc

    def _relax_kernel(bsrc_ref, bdst_ref, bsel_ref, src_ref, carry_ref,
                      block_ref, out_ref):
        del bsel_ref                   # consumed by the block index map
        i = pl.program_id(0)           # weight block (outer: stays resident
        b = pl.program_id(1)           # query in the batch    while b spins)
        prev = bdst_ref[jnp.maximum(i - 1, 0)]
        is_first = jnp.logical_or(i == 0,
                                  bdst_ref[i] // rows != prev // rows)

        # First visit of this destination slab: seed all B rows with the
        # carry values (current attrs for monotone algebras -- the ⊕-merge
        # folds "no update" in; the un-absorbed residual for delta-PR).
        @pl.when(jnp.logical_and(is_first, b == 0))
        def _init():
            out_ref[...] = carry_ref[...]

        # rows of this block's source and destination tiles in their slabs
        rs = bsrc_ref[i] % rows if rows > 1 else 0
        rd = bdst_ref[i] % rows if rows > 1 else 0
        src = src_ref[0, pl.ds(rs, 1)]  # (1, T[, d]) query b's source tile
        # FLIP trigger rule, per query: skip the block if none of this
        # query's sources is active (⊕-identity where inactive). Sentinel
        # slots may still fire -- their all-identity block makes the merge
        # an exact no-op, and the compute is free under the memory bound.
        @pl.when(jnp.any(src != zero))
        def _relax():
            if feature_dim > 1:
                cand = _contract(src_ref, block_ref)[None]     # (1, T, d)
            else:
                cand = add_reduce(mul(src[0][:, None], block_ref[0]),
                                  axis=0)[None]                # (1, T)
            cur = out_ref[pl.ds(b, 1), pl.ds(rd, 1)]
            out_ref[pl.ds(b, 1), pl.ds(rd, 1)] = add(cur, cand[None])

    return _relax_kernel


@functools.partial(jax.jit,
                   static_argnames=("semiring", "interpret", "feature_dim"))
def frontier_relax_pallas(src_vals: jnp.ndarray,  # (B?, ns, T[, d]) f32
                          carry: jnp.ndarray,     # (B?, ntiles, T[, d]) f32
                          blocks: jnp.ndarray,    # (nb[+1], T, T) f32
                          bsrc: jnp.ndarray,      # (nslots,) i32, sorted by
                          bdst: jnp.ndarray,      # (nslots,) i32 (bdst, bsrc)
                          semiring: Semiring = MIN_PLUS,
                          interpret: bool = False,
                          bsel: jnp.ndarray | None = None,
                          feature_dim: int = 1) -> jnp.ndarray:
    """One relaxation step: new[b, d] = carry[b, d] ⊕ (⊕_s sv[b, s] ⊗ W[s, d]).

    `src_vals`/`carry` are (ntiles, T) for one query or (B, ntiles, T) for
    a batch of B independent queries sharing the block structure; the
    result has the carry's shape. `bsrc` indexes the source tiles and
    `bdst` the carry's tiles, so the two may hold different tile counts:
    a device of the distributed fixpoint relaxes the whole source state
    into its own slab of destination tiles. Destination tiles with no
    incident block keep their carry (callers ensure every tile has at
    least one block, or accept identity via the input_output_aliasing
    below).

    `feature_dim` d > 1 switches to vector-valued vertex state: the state
    arrays carry a trailing feature axis ((ntiles, T, d) solo /
    (B, ntiles, T, d) batched) and each grid step runs the (T, T) × (T, d)
    tile contraction instead of the scalar broadcast-reduce. `feature_dim`
    is an explicit static argument (not inferred from ndim) because
    (ntiles, T, d) and (B, ntiles, T) are indistinguishable by rank alone.

    `bsel` (optional, (nslots,) i32) streams the weight blocks through an
    indirection: grid slot i fetches ``blocks[bsel[i]]``. Dense streaming
    is ``bsel = None`` (identity). Compacted streaming passes the output
    of `ops.compact_block_stream` together with the sentinel-extended
    block array and the compacted `bsrc`/`bdst` slot coordinates.
    """
    features = feature_dim > 1
    if features and src_vals.shape[-1] != feature_dim:
        raise ValueError(
            f"state carries feature_dim {src_vals.shape[-1]} but the "
            f"kernel was asked for feature_dim {feature_dim}")
    squeeze = src_vals.ndim == 2 + features
    if squeeze:
        src_vals, carry = src_vals[None], carry[None]
    if (src_vals.ndim != carry.ndim or src_vals.shape[0] != carry.shape[0]
            or src_vals.shape[2:] != carry.shape[2:]):
        raise ValueError(f"src_vals {src_vals.shape} / carry "
                         f"{carry.shape} state shapes disagree")
    t = blocks.shape[-1]
    nslots = bsrc.shape[0]
    if bsel is None:
        bsel = jnp.arange(nslots, dtype=jnp.int32)
    batch, ntiles = carry.shape[0], carry.shape[1]
    # state slab: R tiles per block (one at d > 1, whose (T, d) minor
    # axes already span the whole array); tile counts are padded up to a
    # multiple of R with ⊕-identity tiles that no block references
    rows = 1 if features else SLAB

    def pad_tiles(x):
        pad = -x.shape[1] % rows
        if not pad:
            return x
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)
        return jnp.pad(x, widths, constant_values=semiring.zero)

    src_vals, carry = pad_tiles(src_vals), pad_tiles(carry)
    tail = (t, feature_dim) if features else (t,)
    zeros = (0,) * len(tail)
    in_specs = [
        pl.BlockSpec((1, rows) + tail,                          # src vals
                     lambda i, b, bs, bd, sel: (b, bs[i] // rows) + zeros),
        pl.BlockSpec((batch, rows) + tail,                      # carry
                     lambda i, b, bs, bd, sel: (0, bd[i] // rows) + zeros),
        pl.BlockSpec((1, t, t),                                 # block
                     lambda i, b, bs, bd, sel: (sel[i], 0, 0)),
    ]
    out_spec = pl.BlockSpec((batch, rows) + tail,
                            lambda i, b, bs, bd, sel: (0, bd[i] // rows)
                            + zeros)
    out_shape = jax.ShapeDtypeStruct(carry.shape, jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nslots, batch),
        in_specs=in_specs,
        out_specs=out_spec,
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    out = pl.pallas_call(
        _make_relax_kernel(semiring, feature_dim, rows),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={4: 0},   # alias carry -> out: untouched tiles
        interpret=interpret,           # keep their carry values
        **kwargs,
    )(bsrc, bdst, bsel, src_vals, carry, blocks)
    out = out[:, :ntiles]
    return out[0] if squeeze else out
