"""Pallas TPU kernel: frontier-masked semiring relaxation, batched.

TPU-native form of FLIP's data-centric PE array (DESIGN.md Sec. 2): graph
vertices are tiled onto the 8x128 VPU lane grid; one weight block holds
all edges between a source tile and a destination tile as a dense (T, T)
array (absent edge = the semiring's ⊕-identity). One kernel body serves
every registered algebra: the merge ⊕, combine ⊗, and reduction are
closed over as static ops, so each (semiring, tile) pair specializes to
its own executable at trace time -- tropical (min,+) for BFS/SSSP/WCC,
(max,min) for widest-path, (or,and) for reachability, (+,x) for
delta-PageRank.

The frontier bitmask plays FLIP's packet-trigger role: a block whose
source tile holds only ⊕-identity lanes leaves a query's output
untouched (a `pl.when` on the slab grid, a select on the grouped grid,
and on the source-run body the identity itself), per query -- the
kernel preserves the paper's "only active vertices scatter" property.
Because the ⊕-identity annihilates ⊗, skipping such a block is exact.

Block-sparsity replaces the Inter-/Intra-Tables: `bsrc/bdst` (scalar-
prefetched) name the tile pair of each block; position inside the block
is the DRF register. Blocks are sorted by destination tile, so a
destination's partial ⊕ accumulates in VMEM across consecutive slots.

Compacted block streaming extends the skip to the memory system: the
block stream is indexed through a scalar-prefetched selection list
``bsel`` (see `ops.compact_block_stream`), whose first ``n_active`` slots
name the live blocks in the order the call walks them and whose tail
repeats one all-identity sentinel block index. A stable compaction keeps
the layout's destination order, or through the layout's `src_order` the
source order (`relax_walk`). The dense stream is ``bsel = arange(nb)``
(``src_order`` under the source walk), ``n_active = nb``.

The state is (B, ntiles, T) -- B independent queries over one shared
block structure -- or (B, ntiles, T, d) for vector-valued vertex state.
Two grids run it; the shapes alone choose (`relax_path`):

Grouped grid (scalar state whose source and output fit
GROUPED_VMEM_BUDGET in VMEM). The source and output state are whole
VMEM blocks with a constant index map, so they are copied in once and
the output written back once per call; the carry stays in HBM and is
copied into the output at the first step. The weight blocks stay in
HBM (`pl.ANY`). Grid step g walks slots [g*GROUP, (g+1)*GROUP) below
``n_active`` in order: it copies ``blocks[bsel[j]]`` by hand into a ring
of WEIGHT_BUFFERS VMEM buffers, the copies of the next slots in flight
while slot j relaxes against all B rows, so the grid has
ceil(nslots / GROUP) steps instead of nslots * B and slots at or past
``n_active`` (the sentinel tail) do no copy and no compute. The trigger
is read from a scalar-prefetched bitmask (`_row_triggers`, one bit per
row and source tile) and applied as a select, so the rows of a slot
run without a branch between them; at B a multiple of 8 the source
tile of 8 rows is moved onto the sublane axis by one transpose. Each
row's candidate and its ⊕ into the output are the slab grid's, in the
same slot order, so both grids give the same bits. A device of the
distributed fixpoint, whose source state holds more tiles than its
destination slab, takes this grid too.

Source-run body (grouped grid, B a multiple of 8, idempotent ⊕:
`source_runs`). The row-by-row body rebuilds, every slot, a broadcast
that depends only on (row, source tile). Here the call walks the stream
source-major (`relax_walk` == 'src'), so each source tile owns one run
of consecutive slots, and the body keeps the broadcast in a VMEM
scratch: xs[c, i] is an (8, T) tile whose sublane r holds lane i of row
8c + r's source tile on every lane, built at the call's first slot and
where the source tile changes. Each slot then combines its block against
it with no lane broadcast: acc = ⊕_i xs[c, i] ⊗ W[i, :] (W's row i
broadcast over the sublanes, CHAINS interleaved partial sums), and one
(8, T) merge into the 8 output rows of its destination. No trigger is
read: an inactive row's broadcast is the ⊕-identity. Each candidate is
one ⊗ of the same two f32 values as on the slab grid, and ⊕ is exact in
any order, so the bits are the slab grid's. (+, ×) keeps the
destination walk and the row-by-row body: its sums would change with
the order.

Slab grid (d > 1, or a state over the budget): grid = (nslots, B), one
(slot, query) pair per step. The weight block's index map reads
``bsel[i]`` and ignores the query index, so each block is fetched once
and stays resident while the B queries relax against it, and
consecutive sentinel slots repeat one index, so the pipeline skips their
copies (the sentinel slots still run the combine, an exact no-op).
Mosaic only accepts blocks whose last two dims are multiples of
(8, 128) or span the whole array, so scalar state moves in slabs of
SLAB = 8 consecutive tiles -- (1, 8, T) source and (B, 8, T)
carry/output blocks at tile index ``bsrc // 8`` / ``bdst // 8`` -- and
the kernel reads and writes its tile's row inside the slab; a slab is
seeded from the carry on its first visit, and a tile count that is not
a multiple of 8 is padded with ⊕-identity tiles inside the call. At
d > 1 the state blocks grow a trailing feature axis and one step becomes
a (T, T) × (T, d) tile contraction via `Semiring.contract_jnp`: an MXU
matmul (`W.T @ sv`) for (+, ×), a swept broadcast-⊕-reduce on the VPU
for the tropical/boolean pairs, so each streamed block feeds B·d lanes.

VMEM (T a multiple of 128, f32; d' = d rounded up to 128 lanes):

  grouped:  B * (ns' + ntiles') * T * 4 B + WEIGHT_BUFFERS * T*T * 4 B
            + (B a multiple of 8) B * T*T * 4 B source broadcast
            (ns', ntiles' = tile counts rounded up to 8; one copy of
            each, as the block index never changes)
  slab d=1: 2 * (8*T + B*8*T + T*T + B*8*T) * 4 B
  slab d>1: 2 * (T*d' + B*T*d' + T*T + B*T*d') * 4 B
            + 8*T*d' * 4 B (min/max contraction transient)

Examples: grouped 3 MiB at graph500-s15 (256 tiles, T=128) for B=8,
8.5 MiB for the 2^20-vertex road graph solo (8192 tiles), 64 MiB budget
(v5e has 128 MiB; the call raises its scoped limit to what it holds plus
8 MiB); slab 648 KiB for T=128, B=32, d=1; 2.8 MiB for T=128, B=8,
d=8; 1 MiB for d=128 solo -- inside the 16 MiB default scoped VMEM. In
HBM the scalar state is (B, ntiles, T) f32. ops.py picks T;
plan.resolve validates d.
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


# Slots per grid step of the grouped path: one step walks GROUP
# consecutive slots of the block stream.
GROUP = 32
# Weight blocks the grouped path keeps in flight: slot j waits for its
# copy while the copies of slots j+1 .. j+WEIGHT_BUFFERS-1 run.
WEIGHT_BUFFERS = 8
# VMEM the grouped path may hold resident: the source and output state
# plus the weight buffers. v5e has 128 MiB of VMEM; states above this
# take the slab grid.
GROUPED_VMEM_BUDGET = 64 << 20
# independent ⊕ chains of the source-run body's slot: ⊕ over a block's
# T source lanes as CHAINS interleaved partial sums, so consecutive
# vector ops do not wait on each other (exact: ⊕ is idempotent there)
CHAINS = 8
# scoped VMEM granted above the resident bytes, for Mosaic's own scratch
# and the (T, T) combine transient of one row
_VMEM_HEADROOM = 8 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def grouped_vmem_bytes(batch: int, ns: int, ntiles: int, t: int) -> int:
    """VMEM the grouped path holds at state (B, ns | ntiles, T): one copy
    each of the source and output state (their block index never
    changes, so the pipeline keeps a single buffer), laid out in
    (8, 128) f32 tiles, plus WEIGHT_BUFFERS (T, T) weight buffers, plus
    at B a multiple of 8 the (B/8, T, 8, T) source broadcast of the
    source-run body (B * T * T * 4 B)."""
    lanes = _round_up(t, 128)
    state = batch * (_round_up(ns, 8) + _round_up(ntiles, 8)) * lanes * 4
    runs = batch * t * lanes * 4 if batch % SLAB == 0 else 0
    return state + WEIGHT_BUFFERS * _round_up(t, 8) * lanes * 4 + runs


def relax_path(batch: int, ns: int, ntiles: int, t: int,
               feature_dim: int = 1) -> str:
    """Which grid a relax call at these shapes runs: 'grouped' (GROUP
    slots of the stream per grid step, all B rows, state resident in
    VMEM) for scalar state that fits GROUPED_VMEM_BUDGET, else 'slab'
    (one (slot, row) pair per grid step). Shapes alone decide."""
    if (feature_dim == 1
            and grouped_vmem_bytes(batch, ns, ntiles, t)
            <= GROUPED_VMEM_BUDGET):
        return "grouped"
    return "slab"


def relax_grid_steps(path: str, nslots: int, batch: int) -> int:
    """Grid steps of one relax call: ceil(nslots / GROUP) on the grouped
    path (dead groups run an empty step), nslots * B on the slab grid."""
    return -(-nslots // GROUP) if path == "grouped" else nslots * batch


def source_runs(batch: int, semiring: Semiring) -> bool:
    """Whether the grouped grid relaxes a batch with the source-run body
    (module doc): B a multiple of 8 under an idempotent ⊕, whose merges
    are exact in any slot order."""
    return batch % SLAB == 0 and semiring.idempotent


def relax_walk(path: str, batch: int, semiring: Semiring) -> str:
    """The order a relax call on `path` walks its block stream in: 'src'
    (source-major, (bsrc, bdst)) where the grouped grid runs the
    source-run body, else 'dst' (the layout's (bdst, bsrc) order)."""
    if path == "grouped" and source_runs(batch, semiring):
        return "src"
    return "dst"


def _row_triggers(src_vals, zero) -> jnp.ndarray:
    """(B, ns, T) source state -> (words * ns,) i32 trigger bitmask, words
    = ceil(B / 32): bit b % 32 of entry (b // 32) * ns + s is set iff row
    b's source tile s holds a lane other than the ⊕-identity -- the
    packet-trigger condition, read by the kernel as a scalar."""
    batch, ns, _ = src_vals.shape
    words = -(-batch // 32)
    act = jnp.any(src_vals != zero, axis=-1).astype(jnp.int32)   # (B, ns)
    act = jnp.pad(act, ((0, words * 32 - batch), (0, 0)))
    shift = jnp.arange(32, dtype=jnp.int32)[None, :, None]
    return jnp.sum(act.reshape(words, 32, ns) << shift,
                   axis=1).reshape(words * ns)


@functools.lru_cache(maxsize=None)
def _make_grouped_kernel(semiring: Semiring, batch: int, group: int):
    """Kernel body of the grouped path: grid step g relaxes slots
    [g*group, min((g+1)*group, n_active)) of the block stream, each
    against all B rows of the VMEM-resident source state, into the
    VMEM-resident output. Weight blocks stay in HBM and are copied in by
    hand through a ring of WEIGHT_BUFFERS buffers, the copies of the
    next slots in flight while slot j relaxes, across group boundaries
    too. At B a multiple of 8 under an idempotent ⊕ the body is the
    source-run one (`source_runs`, module doc), whose broadcast scratch
    `xs` is the last argument."""
    add, mul = semiring.add_jnp, semiring.mul_jnp
    add_reduce = semiring.add_reduce_jnp
    ahead = WEIGHT_BUFFERS - 1
    runs = source_runs(batch, semiring)

    def _grouped_relax_kernel(nact_ref, bsrc_ref, bdst_ref, bsel_ref,
                              trig_ref, src_ref, carry_hbm, blocks_hbm,
                              out_ref, wbuf, wsem, csem, *xs):
        g = pl.program_id(0)
        n = nact_ref[0]
        ns, t = src_ref.shape[1], src_ref.shape[2]

        def fetch(j):
            k = j % WEIGHT_BUFFERS
            return pltpu.make_async_copy(blocks_hbm.at[bsel_ref[j]],
                                         wbuf.at[k], wsem.at[k])

        # seed the resident output with the carry (current attrs for
        # monotone algebras, the un-absorbed residual for delta-PR) and
        # put the first blocks in flight
        @pl.when(g == 0)
        def _seed():
            seed = pltpu.make_async_copy(carry_hbm, out_ref, csem)
            seed.start()
            for j in range(ahead):
                pl.when(j < n)(lambda j=j: fetch(j).start())
            seed.wait()

        def slot(j, carry):
            pl.when(j + ahead < n)(lambda: fetch(j + ahead).start())
            fetch(j).wait()
            w = wbuf.at[j % WEIGHT_BUFFERS]
            s, d = bsrc_ref[j], bdst_ref[j]

            if runs:
                # a run of slots with one source tile shares the tile's
                # broadcast: xs[c, i] is (8, T), sublane r holding row
                # 8c + r's lane i on every lane. It is built at the
                # call's first slot and where the source tile changes;
                # grid steps run in order, so it outlives them
                @pl.when(jnp.logical_or(
                        j == 0, s != bsrc_ref[jnp.maximum(j - 1, 0)]))
                def _build():
                    def build(c, carry):
                        b0 = pl.multiple_of(c * SLAB, SLAB)
                        v = src_ref[pl.ds(b0, SLAB), s, :]      # (8, T)
                        for i in range(t):
                            xs[0][c, i] = jnp.broadcast_to(
                                v[:, i:i + 1], (SLAB, t))
                        return carry
                    jax.lax.fori_loop(0, batch // SLAB, build, 0)

                # 8 rows at once: ⊕_i xs[c, i] ⊗ W[i, :], W's row i
                # broadcast over the sublanes, summed in CHAINS
                # independent chains, then one (8, T) merge. An inactive
                # row's broadcast is the ⊕-identity, which annihilates ⊗,
                # so it needs no trigger: its merge is an exact no-op
                def rows8_runs(c, carry):
                    b0 = pl.multiple_of(c * SLAB, SLAB)
                    acc = [None] * CHAINS
                    for i in range(t):
                        p = mul(xs[0][c, i], w[pl.ds(i, 1), :])
                        k = i % CHAINS
                        acc[k] = p if acc[k] is None else add(acc[k], p)
                    while len(acc) > 1:
                        acc = [add(acc[k], acc[k + 1]) if k + 1 < len(acc)
                               else acc[k] for k in range(0, len(acc), 2)]
                    rows = (pl.ds(b0, SLAB), d, slice(None))
                    out_ref[rows] = add(out_ref[rows], acc[0])
                    return carry

                return jax.lax.fori_loop(0, batch // SLAB, rows8_runs,
                                         carry)

            def merge(b, cand):                         # cand: (1, T)
                cur = out_ref[b, pl.ds(d, 1)]
                # FLIP trigger rule, per query: the block leaves row b
                # untouched if none of its sources is active. A select on
                # the scalar trigger, not a branch: rows without a branch
                # between them interleave, which on a v5e costs less than
                # the compute a branch would skip
                fire = (trig_ref[(b // 32) * ns + s] >> (b % 32)) & 1
                out_ref[b, pl.ds(d, 1)] = jnp.where(
                    fire != 0, add(cur, cand), cur)

            if batch % SLAB == 0:
                # one (8, T) -> (T, 8) transpose puts the source tile of 8
                # rows on the sublane axis at once; below 8 rows the
                # per-row (1, T) -> (T, 1) relayout costs less
                def rows8(c, carry):
                    b0 = pl.multiple_of(c * SLAB, SLAB)
                    cols = src_ref[pl.ds(b0, SLAB), s, :].T  # (T, 8)
                    for r in range(SLAB):
                        merge(b0 + r, add_reduce(
                            mul(cols[:, r:r + 1], w[...]), axis=0)[None])
                    return carry

                return jax.lax.fori_loop(0, batch // SLAB, rows8, carry)

            def row(b, carry):
                src = src_ref[b, pl.ds(s, 1)]               # (1, T)
                merge(b, add_reduce(mul(src[0][:, None], w[...]),
                                    axis=0)[None])
                return carry

            return jax.lax.fori_loop(0, batch, row, carry,
                                     unroll=batch <= 8)

        base = g * group
        jax.lax.fori_loop(base, jnp.minimum(base + group, n), slot, 0)

    return _grouped_relax_kernel


def _relax_grouped(src_vals, carry, blocks, bsrc, bdst, bsel, n_active,
                   semiring, interpret, group=GROUP):
    """The grouped grid: ceil(nslots / group) steps over (B, ns, T)
    source and (B, ntiles, T) carry, both whole in VMEM."""
    batch, ns, t = src_vals.shape
    ntiles = carry.shape[1]
    nslots = bsrc.shape[0]
    # the source-run body's broadcast scratch; it reads no trigger bits
    runs = ([pltpu.VMEM((batch // SLAB, t, SLAB, t), jnp.float32)]
            if source_runs(batch, semiring) else [])
    trig = (jnp.zeros((1,), jnp.int32) if runs
            else _row_triggers(src_vals, semiring.zero))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(-(-nslots // group),),
        in_specs=[
            pl.BlockSpec((batch, ns, t), lambda g, *_: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),              # carry
            pl.BlockSpec(memory_space=pl.ANY),              # blocks
        ],
        out_specs=pl.BlockSpec((batch, ntiles, t), lambda g, *_: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((WEIGHT_BUFFERS, t, t), jnp.float32),
                        pltpu.SemaphoreType.DMA((WEIGHT_BUFFERS,)),
                        pltpu.SemaphoreType.DMA(())] + runs,
    )
    kwargs = {}
    if interpret:
        kwargs["interpret"] = interpret
    else:
        need = grouped_vmem_bytes(batch, ns, ntiles, t)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + _VMEM_HEADROOM)
    return pl.pallas_call(
        _make_grouped_kernel(semiring, batch, group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(carry.shape, jnp.float32),
        input_output_aliases={6: 0},   # alias carry -> out
        **kwargs,
    )(jnp.reshape(n_active, (1,)).astype(jnp.int32), bsrc, bdst, bsel,
      trig, src_vals, carry, blocks)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "interpret", "feature_dim"))
def frontier_relax_pallas(src_vals: jnp.ndarray,  # (B?, ns, T[, d]) f32
                          carry: jnp.ndarray,     # (B?, ntiles, T[, d]) f32
                          blocks: jnp.ndarray,    # (nb[+1], T, T) f32
                          bsrc: jnp.ndarray,      # (nslots,) i32, sorted by
                          bdst: jnp.ndarray,      # (nslots,) i32 (bdst, bsrc)
                          semiring: Semiring = MIN_PLUS,
                          interpret=False,
                          bsel: jnp.ndarray | None = None,
                          feature_dim: int = 1,
                          n_active: jnp.ndarray | None = None
                          ) -> jnp.ndarray:
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
    block array and the compacted `bsrc`/`bdst` slot coordinates, and
    its traced active count `n_active`: the grouped grid relaxes only
    slots below it (the default, None, is every slot).

    The grid is chosen from the shapes (`relax_path`; module docstring).
    `interpret` is False on the chip, True for the Pallas interpreter,
    or a `pltpu.InterpretParams` for the TPU interpreter, which also
    runs the grouped grid's manual copies.
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
    if relax_path(batch, src_vals.shape[1], ntiles, t,
                  feature_dim) == "grouped":
        out = _relax_grouped(src_vals, carry, blocks, bsrc, bdst, bsel,
                             nslots if n_active is None else n_active,
                             semiring, interpret)
    else:
        out = _relax_slab(src_vals, carry, blocks, bsrc, bdst, bsel,
                          semiring, interpret, feature_dim)
    return out[0] if squeeze else out


def _relax_slab(src_vals, carry, blocks, bsrc, bdst, bsel, semiring,
                interpret, feature_dim):
    """The slab grid: (nslots, B) steps, one (slot, row) pair each, over
    R-tile slabs of the (B, ns | ntiles, T[, d]) state."""
    features = feature_dim > 1
    t = blocks.shape[-1]
    nslots = bsrc.shape[0]
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
    return out[:, :ntiles]
