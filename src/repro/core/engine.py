"""FLIP JAX engine: the TPU-native data-centric execution layer.

Two execution modes, matching the paper's dual-mode fabric (Sec. 3.4):

  * data-centric  -- frontier-driven: each step relaxes only blocks with
    active sources (the Pallas kernel skips inactive tiles), and the new
    frontier is the set of vertices the algebra marks active (attribute
    ⊕-improved for monotone algebras, residual above tolerance for
    delta-PageRank). This is FLIP's packet-triggered execution,
    vectorized.
  * op-centric    -- classic CGRA analogue: a full (unmasked) relaxation
    sweep every step (Bellman-Ford / power-iteration style), no
    data-driven skipping.

Data-centric mode additionally streams the weight blocks *compacted* by
the runtime frontier (``compact``, default on for data mode): only blocks
whose source tile is active for some query leave HBM; the rest are stood
in for by one VMEM-resident sentinel block (see
`repro.kernels.frontier.ops`). The compaction runs on the device inside
the `while_loop` with static shapes; the jnp/CPU relax streams every
block, which gives the same results. Compaction is exact (the
⊕-identity annihilates ⊗), so results and step counts are bit-for-bit
the dense-streaming ones.

The algorithm is any registered `VertexAlgebra` (bfs, sssp, wcc,
pagerank, widest, reach, ...): the engine itself only threads the
algebra's scatter/carry/post-step hooks around the semiring relax kernel,
so a new algebra runs here unchanged.

Execution is batched over independent queries: the state is
(B, ntiles, T) -- B sources relaxing against one shared block structure
inside one `jax.lax.while_loop` fixpoint, the one compiled program
(`jit_flip_fixpoint`) every call runs on every backend; a deadline runs
it one step per call, so the host sees each step boundary.
`FlipEngine.execute` is the single entry point (scalar source = the B=1
view; `distributed=True` switches to the shard_map fixpoint; `warm=`
resumes a prior result), and `repro.api` ( `flip.compile(graph,
program, plan).query(srcs)` ) is the intended front door. Queries whose
frontier has emptied are frozen by a per-query convergence mask, so a
long-tail query never perturbs finished ones and batched results are
bit-for-bit the per-source results.

Both paths can execute distributed via `shard_map` over a sharded
layout (`repro.kernels.frontier.ops`): destination tiles are partitioned
over a mesh axis (devices = PE clusters), each device holds only the
blocks that write its tiles, queries stay replicated, each device
relaxes its blocks through the same compacted relax as the local step,
and the updated attribute vector is re-assembled with an all-gather --
the collective is the NoC, and its cost amortizes over the whole batch.
The loop is the local one (`_dense_fixpoint_jit`), compiled once per
mesh; the gather is the only difference.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.algebra import VertexAlgebra
from repro.core.mapping import Mapping
from repro.graphs.csr import Graph
from repro.kernels.frontier.ops import (BlockedGraph, UpdateDelta,
                                        block_activity, build_blocks,
                                        frontier_relax, mesh_key,
                                        relax_grid, resolve_relax_mode,
                                        tile_activity, walk_activity)
from repro.obs.spans import span
from repro.obs.telemetry import DispatchTelemetry, StepTrace
from repro.resilience.errors import InvalidRequest

# default per-step trace row capacity (`execute(trace=True)`): enough for
# any realistic fixpoint (diameters are O(100) even on road networks)
# while keeping the traced stat buffers a few hundred KB. Pass an int as
# `trace` to override; steps beyond the capacity still execute exactly
# (only their trace rows are dropped, flagged `truncated`).
TRACE_CAP_DEFAULT = 4096

# the compiled fixpoint programs, shared by every engine whose trace is
# the same (keyed in `FlipEngine._dense_fixpoint_jit`): sessions and
# tests build many engines over alike layouts, and each would otherwise
# compile its own loop. A program closes over static fields only.
_PROGRAMS: dict = {}


@dataclasses.dataclass
class WarmStart:
    """Resume state for delta-driven incremental recompute.

    `attrs` is the converged result of a prior run on the pre-update
    engine, in original vertex order: `(n,)` (applied to every query of
    the batch) or `(B, n)` matching the batch. `seeds` holds the original
    ids of the vertices whose out-edge ⊗ operands changed
    (`UpdateDelta.affected_src`): they form the initial frontier, so the
    fixpoint relaxes only what the update batch can actually improve and
    converges in O(delta) steps instead of O(graph). Sound only for
    monotone algebras under a `Semiring.monotone_under` update batch --
    `FlipEngine.resolve_warm` applies that dispatch.
    """
    attrs: np.ndarray
    seeds: np.ndarray


@dataclasses.dataclass
class ExecutionDetail:
    """Everything one `execute(detail=True)` dispatch knows about its
    outcome, beyond the bare ``(out, steps)`` tuple:

    `converged` is the engine's per-query convergence mask read at the
    fixpoint's end: True iff that query's frontier emptied (the fixpoint
    was *reached*), False iff it was frozen by a step budget, a
    deadline, or the session-wide `max_steps` valve -- in which case
    `attrs` is a valid partial relaxation, flagged, never silently
    truncated. `deadline_expired` marks which queries the deadline (not
    the step budget) stopped. Shapes follow the query: scalar source ->
    scalar flags, batch -> (B,) arrays."""
    attrs: np.ndarray
    steps: int | np.ndarray
    converged: bool | np.ndarray
    deadline_expired: bool | np.ndarray
    telemetry: DispatchTelemetry | None = None


def _layout_args(bg: BlockedGraph) -> tuple:
    """A layout's arrays as the fixpoint program takes them: a sharded
    layout holds its slabs once and marks its live slots. A program that
    walks its stream in destination order never reads the source walk,
    and the compiled program drops it."""
    walk = (bg.src_order, bg.src_tiles)
    if bg.shards is None:
        return (bg.blocks, bg.blocks_ext, bg.bsrc, bg.bdst) + walk
    return (bg.blocks, bg.bsrc, bg.bdst, bg.live) + walk


def mapping_order(mapping: Mapping) -> np.ndarray:
    """Vertex ordering induced by the FLIP placement: vertices co-located
    on a (copy, PE) become adjacent tile positions, so the compiled
    placement's locality becomes block-sparsity."""
    keys = [(int(mapping.copy_of[v]), int(mapping.pe_of[v]), v)
            for v in range(mapping.graph.n)]
    return np.asarray([v for _, _, v in sorted(keys)], dtype=np.int64)


@dataclasses.dataclass
class FlipEngine:
    """Compiled graph + algorithm, ready to run on CPU or a device mesh."""

    bg: BlockedGraph
    algo: str
    mode: str = "data"          # 'data' (FLIP) or 'op' (classic CGRA)
    relax_mode: str = "auto"    # kernel dispatch: auto/pallas/interpret/jnp
    compact: bool | str = "auto"  # frontier-compacted block streaming:
                                  # 'auto' = on for data mode, off for op
    max_steps: int = 100_000
    feature_dim: int = 1        # feature width d of the vertex state:
                                # d > 1 runs the (T, T) x (T, d) vector
                                # relax ((B, ntiles, T, d) state)

    # -------------------------------------------------------------- #
    @staticmethod
    def build(graph: Graph, algo: str | VertexAlgebra,
              mapping: Mapping | None = None,
              tile: int = 128, mode: str = "data",
              relax_mode: str = "auto",
              compact: bool | str = "auto",
              feature_dim: int | None = None) -> "FlipEngine":
        order = mapping_order(mapping) if mapping is not None else None
        return FlipEngine.over(
            build_blocks(graph, algo=algo, tile=tile, order=order),
            mode=mode, relax_mode=relax_mode, compact=compact,
            feature_dim=feature_dim)

    @staticmethod
    def over(bg: BlockedGraph, mode: str = "data",
             relax_mode: str = "auto", compact: bool | str = "auto",
             feature_dim: int | None = None) -> "FlipEngine":
        """An engine over a built layout, local or sharded."""
        d = bg.algebra.feature_dim if feature_dim is None else feature_dim
        if bg.algebra.feature_dim > 1 and d != bg.algebra.feature_dim:
            raise ValueError(
                f"{bg.algebra.name} natively carries feature_dim "
                f"{bg.algebra.feature_dim}; cannot run it at "
                f"feature_dim {d}")
        return FlipEngine(bg=bg, algo=bg.algebra.name, mode=mode,
                          relax_mode=relax_mode, compact=compact,
                          feature_dim=d)

    @property
    def algebra(self) -> VertexAlgebra:
        return self.bg.algebra

    @property
    def _features(self) -> bool:
        return self.feature_dim > 1

    @property
    def _use_compact(self) -> bool:
        """Resolve the compaction policy: op-mode sweeps relax everything
        by definition, so only data mode compacts by default."""
        if self.compact == "auto":
            return self.mode == "data"
        return bool(self.compact)

    def _resolved_relax_mode(self) -> str:
        return resolve_relax_mode(self.relax_mode)

    # -------------------------------------------------------------- #
    def initial_state(self, srcs, warm: WarmStart | None = None):
        """(attrs, aux, frontier) as (B, ntiles, T) arrays for a batch of
        sources; padded lanes hold the ⊕-identity so they never activate
        or contribute.

        With `warm`, the fixpoint resumes from a prior converged result
        instead of the algebra's initial state: attrs come from
        `warm.attrs` and only `warm.seeds` start active, so relaxation
        propagates exactly the update batch's improvements."""
        bg, alg = self.bg, self.algebra
        d, features = self.feature_dim, self._features
        srcs = np.atleast_1d(np.asarray(srcs, dtype=np.int64))
        b = srcs.shape[0]
        if warm is not None:
            if alg.kind != "monotone":
                raise ValueError(
                    f"warm start needs a monotone algebra; {alg.name} is "
                    f"{alg.kind!r} -- recompute from scratch instead")
            prev = np.asarray(warm.attrs, dtype=np.float32)
            want = (b, bg.n, d) if features else (b, bg.n)
            if features and (prev.ndim < 2 or prev.shape[-1] != d):
                wd = prev.shape[-1] if prev.ndim >= 2 else 1
                raise ValueError(
                    f"warm attrs carry feature_dim {wd} but this "
                    f"engine runs {alg.name} at feature_dim {d}; "
                    f"warm state shape {prev.shape} != {want}")
            if prev.ndim == len(want) - 1:   # shared across the batch
                prev = np.broadcast_to(prev, want)
            if prev.shape != want:
                raise ValueError(
                    f"warm attrs shape {prev.shape} does not match "
                    f"{want} (B={b}, n={bg.n}"
                    + (f", d={d})" if features else ")"))
            attrs = bg.to_tiled(prev, features=features)
            frontier = np.zeros((b, bg.padded_n), dtype=bool)
            seeds = np.asarray(warm.seeds, dtype=np.int64)
            frontier[:, bg.perm[seeds]] = True
        else:
            attrs = bg.to_tiled(
                alg.initial_attrs(bg.n, srcs, feature_dim=d),
                features=features)
            frontier = np.zeros((b, bg.padded_n), dtype=bool)
            frontier[:, bg.perm] = alg.initial_frontier(bg.n, srcs,
                                                        feature_dim=d)
        aux_shape = (b, bg.n, d) if features else (b, bg.n)
        aux = bg.to_tiled(np.zeros(aux_shape, dtype=np.float32), fill=0.0,
                          features=features)
        return attrs, aux, jnp.asarray(
            frontier.reshape(b, bg.ntiles, bg.tile))

    def _relax(self, sv, carry, bg: BlockedGraph):
        """The step's relax over `bg`. Inside the sharded fixpoint `bg` is
        one device's shard of a sharded layout: the device relaxes the
        whole source state into its own destination tiles, and an
        all-gather over the mesh axis puts the state back together --
        the one difference from the local step."""
        relax = functools.partial(frontier_relax, bg=bg,
                                  mode=self.relax_mode,
                                  compact=self._use_compact,
                                  feature_dim=self.feature_dim)
        sh = bg.shards
        if sh is None:
            return relax(sv, carry)
        lo = jax.lax.axis_index(sh.axis) * sh.tiles_per_dev
        mine = jax.lax.dynamic_slice_in_dim(carry, lo, sh.tiles_per_dev,
                                            axis=1)
        return jax.lax.all_gather(relax(sv, mine), sh.axis, axis=1,
                                  tiled=True)

    def _relax_walk(self, bg: BlockedGraph, batch: int) -> str:
        """The order the relax of a batch of `batch` rows over `bg` walks
        its block stream in (`ops.relax_grid`)."""
        return relax_grid(bg, batch, self.relax_mode, self.feature_dim)[2]

    def _step_stats(self, sv, frontier, bg: BlockedGraph):
        """One trace row's worth of per-step stats, computed from the
        exact quantities the compaction machinery derives anyway: the
        frontier entering the step, the per-tile activity of the
        scattered source values (the kernel's packet-trigger condition),
        and the resulting active-block count. Pure extra outputs -- the
        step math never reads them, so traced runs stay bit-identical.

        Returns ``(active_vertices (B,), active_tiles (), fetched ())``
        as i32; `fetched` is the blocks streamed from HBM this step
        (active blocks under compaction, all blocks under dense). On a
        device of the sharded fixpoint `fetched` is summed over the
        devices, and a fourth entry is the largest device's share. Under
        the source walk a last entry counts the runs of one source tile
        in the stream, the broadcasts the kernel builds: the source
        tiles with a streamed block (summed over the devices too)."""
        act = tile_activity(sv, bg.semiring, self._features)  # (ntiles,)
        active_tiles = jnp.sum(act.astype(jnp.int32))
        runs = ()
        if self._relax_walk(bg, sv.shape[0]) == "src":
            # in the walk's order, as the relax streams them: a run starts
            # at each streamed slot whose source differs from the slot
            # before it (the walk keeps a source's slots together)
            tiles = bg.src_tiles
            streamed = (walk_activity(act, tiles) if self._use_compact
                        else tiles >= 0)
            starts = jnp.concatenate([jnp.ones((1,), bool),
                                      tiles[1:] != tiles[:-1]])
            runs = (jnp.sum(jnp.logical_and(streamed, starts)
                            .astype(jnp.int32)),)
        elif self._use_compact:
            streamed = block_activity(act, bg.bsrc, bg.live)
        else:
            streamed = (jnp.ones(bg.bsrc.shape, bool) if bg.live is None
                        else bg.live)
        fetched = jnp.sum(streamed.astype(jnp.int32))
        active_v = jnp.sum(frontier, axis=(1, 2)).astype(jnp.int32)
        stats = (active_v, active_tiles, fetched)
        if bg.shards is None:
            return stats + runs
        axis = bg.shards.axis
        return (active_v, active_tiles, jax.lax.psum(fetched, axis),
                jax.lax.pmax(fetched, axis)) + tuple(
                    jax.lax.psum(r, axis) for r in runs)

    def _trace_columns(self, bg: BlockedGraph, b: int) -> list:
        """``(shape, dtype)`` of each column of a trace row over `bg` at
        batch `b`: active vertices, active tiles, blocks fetched[, the
        largest device's live slots][, source runs], converged."""
        return ([((b,), jnp.int32)] + [((), jnp.int32)] * 2
                + [((), jnp.int32)] * (bg.shards is not None)
                + [((), jnp.int32)] * (self._relax_walk(bg, b) == "src")
                + [((b,), bool)])

    def _masked_step(self, attrs, aux, frontier, live, bg: BlockedGraph,
                     with_stats: bool = False):
        """One relax step over `bg`, the fixpoint program's view of its
        layout, with the per-query freeze applied: queries not in `live`
        ((B,) bool -- frontier emptied, or step budget exhausted) keep
        their state *and their frontier* untouched, so a budget-frozen
        query still reads as non-converged (frontier non-empty) while a
        finished one stays finished (its frontier emptied naturally)."""
        alg, features = self.algebra, self._features
        sv, carry = alg.scatter_carry_jnp(attrs, frontier,
                                          op_mode=(self.mode == "op"),
                                          features=features)
        new = self._relax(sv, carry, bg)
        attrs_n, aux_n, frontier_n = alg.post_step_jnp(
            attrs, aux, sv, new, features=features)
        stats = self._step_stats(sv, frontier, bg) if with_stats else None
        # live broadcasts from the query axis over every trailing state
        # axis: (B, 1, 1) against (B, ntiles, T), one more 1 at d > 1
        ms = live.reshape(live.shape + (1,) * (attrs.ndim - 1))
        out = (jnp.where(ms, attrs_n, attrs),
               jnp.where(ms, aux_n, aux),
               jnp.where(live[:, None, None], frontier_n, frontier))
        return (out, stats) if with_stats else out

    def _fixpoint(self, attrs0, aux0, frontier0, trace_cap: int = 0,
                  budgets=None, deadlines_t=None,
                  bg: BlockedGraph | None = None):
        """Shared (B, ntiles, T) while_loop with per-query convergence
        masking: a query whose frontier emptied is frozen, so late
        queries in the batch cannot perturb finished ones (op-mode
        sweeps and residual aux accumulation would otherwise keep
        touching them) and per-query step counts match solo runs.

        `budgets` ((B,) i32, default: `max_steps` everywhere) is the
        per-query step cap: a query that reaches its budget with a
        non-empty frontier is frozen exactly like a converged one but
        keeps its frontier, so the final per-query convergence mask
        (returned as the 6th element) reads False for it -- a partial
        result is always *flagged*, never silently truncated. Budgets
        are a traced argument of the one compiled while_loop, so
        varying them never retraces.

        Every call runs the one compiled program (`_dense_fixpoint_jit`)
        on every backend; `bg` (default: the engine's layout) may be a
        sharded layout, and the loop then runs as the sharded program
        over its mesh. `deadlines_t` ((B,) absolute `time.monotonic`
        deadlines from `_resolve_deadlines`, None = none) needs step
        boundaries the host sees, so a deadlined call runs the same
        program one step per call (`_fixpoint_deadlined`).

        `trace_cap > 0` additionally records one per-step stats row into
        fixed-shape (trace_cap, ...) buffers riding the carry (see
        `_step_stats`). Returns ``(attrs, aux, frontier, steps,
        read_trace, converged, expired)`` where `read_trace` is None, or
        with `trace_cap` a function of no arguments that reads the stat
        rows back and returns the `(StepTrace, truncated)` pair (left to
        the caller, so the answer is read before the telemetry),
        `converged` is the (B,) bool end-of-run mask, and `expired`
        marks deadline-stopped queries. The final frontier is
        part of the return so a bounded-budget run is *resumable*: the
        continuous-batching scheduler (`repro.serving`) re-enters with
        the same state to run the next segment. The stat buffers are
        write-only extra outputs, so attrs and step counts are
        bit-identical either way."""
        bg = self.bg if bg is None else bg
        b = attrs0.shape[0]
        budgets = self._device_budgets(budgets, b)
        if deadlines_t is not None:
            return self._fixpoint_deadlined(attrs0, aux0, frontier0,
                                            trace_cap, budgets, deadlines_t,
                                            bg)
        with span("flip.launch"):
            out = self._dense_fixpoint_jit(trace_cap, bg)(
                _layout_args(bg), attrs0, aux0, frontier0, budgets)
        attrs, aux, frontier, steps = out[0], out[1], out[2], out[3]
        # the host's first read of the loop's output: it waits here for
        # the device to finish the fixpoint
        with span("flip.wait"):
            converged = ~np.asarray(frontier.any(axis=(1, 2)))
        expired = np.zeros(b, dtype=bool)
        read_trace = (functools.partial(self._read_trace, out[5], out[6],
                                        trace_cap, bg)
                      if trace_cap else None)
        return attrs, aux, frontier, steps, read_trace, converged, expired

    def _device_budgets(self, budgets, b: int):
        """(B,) i32 per-query step budgets on the device (default:
        `max_steps` everywhere); such an array passes through."""
        if budgets is None:
            return jnp.full((b,), self.max_steps, dtype=jnp.int32)
        if (isinstance(budgets, jax.Array) and budgets.shape == (b,)
                and budgets.dtype == jnp.int32):
            return budgets
        return jnp.asarray(np.broadcast_to(
            np.asarray(budgets, dtype=np.int32), (b,)))

    def _read_trace(self, n_iter, bufs, trace_cap: int, bg: BlockedGraph):
        """The device fixpoint's stat buffers read back to the host as
        ``(StepTrace, truncated)``; a sharded run's also give each
        step's largest and mean live slots per device."""
        n_iter = int(n_iter)
        rows = min(n_iter, trace_cap)
        bufs = [np.asarray(x)[:rows] for x in bufs]
        b_av, b_at, b_bf, b_cv = bufs[0], bufs[1], bufs[2], bufs[-1]
        extra = {}
        if bg.shards is not None:
            extra = dict(shard_live_max=bufs[3],
                         shard_live_mean=b_bf / bg.shards.ndev)
        if self._relax_walk(bg, b_av.shape[1]) == "src":
            extra["src_runs"] = bufs[-2]
        trace = StepTrace(active_vertices=b_av, active_tiles=b_at,
                          blocks_fetched=b_bf,
                          blocks_skipped=np.int32(bg.n_blocks) - b_bf,
                          converged=b_cv, **extra)
        return trace, n_iter > trace_cap

    def _dense_fixpoint_jit(self, trace_cap: int,
                            bg: BlockedGraph | None = None):
        """The whole device while_loop compiled as ONE jitted program:
        eager per-call dispatch of the loop would otherwise dominate the
        step cost (and blow the traced/untraced overhead bound). The
        traced variant only adds fixed-shape stat-buffer writes to the
        carry, so both compile to the same fused step with tracing as a
        few extra reductions.

        Programs are shared by every engine whose trace is the same
        (`_PROGRAMS`): the key holds everything the trace reads besides
        its arguments -- the algebra, mode, resolved relax mode and
        compaction, feature width, `trace_cap`, the layout's sizes and
        mesh -- and `jax.jit` keys the argument shapes itself. A program
        closes over static fields only, never a layout's arrays, so a
        dropped layout's blocks are freed.

        The layout's arrays are arguments of the program, not values it
        closes over: a closed-over array is embedded in the program as a
        constant, which at a million vertices means gigabytes of
        constants to lower, compile and hold a second time on the
        device.

        Over a sharded layout the program is `flip_fixpoint_sharded`:
        the same loop inside a `shard_map` over the layout's mesh axis,
        each device holding its own slab and the replicated state
        (padded to a tile count the devices divide), so that per call
        only the (B, ntiles, T) state moves. `bg` defaults to the
        engine's own layout."""
        bg = self.bg if bg is None else bg
        sh = bg.shards
        key = (trace_cap, self.mode, self._resolved_relax_mode(),
               self._use_compact, self.feature_dim, bg.algebra, bg.n,
               bg.tile, bg.ntiles,
               None if sh is None else (sh.key(), sh.tiles_per_dev,
                                        sh.slots))
        fn = _PROGRAMS.get(key)
        if fn is None:
            # the program closes over this engine with its dispatch
            # resolved, over `bg`'s static fields alone
            template = FlipEngine(bg=bg.static(), algo=self.algo,
                                  mode=self.mode, relax_mode=key[2],
                                  compact=key[3],
                                  feature_dim=self.feature_dim)
            fn = _PROGRAMS[key] = template._program(trace_cap)
        return fn

    def _program(self, trace_cap: int):
        """The fixpoint program of this engine over its layout's static
        fields (`BlockedGraph.static`); each call rebuilds the layout's
        view from the program's traced arguments."""
        static = self.bg
        sh = static.shards

        def live_mask(frontier, steps, budgets):
            """(B,) per-query liveness: frontier still active AND the
            step budget not yet exhausted. Budget-capped queries drop
            out of the loop but keep their (non-empty) frontier, which
            is exactly how the final convergence mask spots them."""
            return jnp.logical_and(frontier.any(axis=(1, 2)),
                                   steps < budgets)

        def cond(state):
            frontier, steps, budgets = state[2], state[3], state[4]
            return live_mask(frontier, steps, budgets).any()

        def body(bg, state):
            attrs, aux, frontier, steps, budgets = state[:5]
            live = live_mask(frontier, steps, budgets)
            if not trace_cap:
                attrs, aux, frontier = self._masked_step(
                    attrs, aux, frontier, live, bg)
                return (attrs, aux, frontier,
                        steps + live.astype(jnp.int32), budgets)
            it, bufs = state[5], state[6]
            (attrs, aux, frontier), stats = self._masked_step(
                attrs, aux, frontier, live, bg, with_stats=True)
            # rows past the capacity are dropped, not wrapped: the trace
            # stays a prefix of the run and `truncated` flags the cut
            bufs = tuple(buf.at[it].set(x, mode="drop")
                         for buf, x in zip(bufs, stats + (~live,)))
            return (attrs, aux, frontier, steps + live.astype(jnp.int32),
                    budgets, it + 1, bufs)

        def loop(bg, attrs0, aux0, frontier0, budgets):
            b = attrs0.shape[0]
            state0 = (attrs0, aux0, frontier0, jnp.zeros(b, jnp.int32),
                      budgets)
            if trace_cap:
                bufs0 = tuple(jnp.zeros((trace_cap,) + shape, dtype)
                              for shape, dtype
                              in self._trace_columns(bg, b))
                state0 = state0 + (jnp.int32(0), bufs0)
            return jax.lax.while_loop(cond, functools.partial(body, bg),
                                      state0)

        # the function's name names the program: `jit_flip_fixpoint` on
        # the profiler's `XLA Modules` line
        @jax.jit
        def flip_fixpoint(layout, attrs0, aux0, frontier0, budgets):
            blocks, blocks_ext, bsrc, bdst, src_order, src_tiles = layout
            return loop(dataclasses.replace(static, blocks=blocks,
                                            blocks_ext=blocks_ext,
                                            bsrc=bsrc, bdst=bdst,
                                            src_order=src_order,
                                            src_tiles=src_tiles),
                        attrs0, aux0, frontier0, budgets)

        if sh is None:
            return flip_fixpoint

        zero = np.float32(self.algebra.semiring.zero)
        pad = sh.ntiles_p - static.ntiles

        def widen(x, fill):
            widths = ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)
            return jnp.pad(x, widths, constant_values=fill) if pad else x

        @functools.partial(jax.shard_map, mesh=sh.mesh,
                           in_specs=(P(sh.axis), P(), P(), P(), P()),
                           out_specs=P(), check_vma=False)
        def per_device(layout, attrs0, aux0, frontier0, budgets):
            blocks, bsrc, bdst, live, src_order, src_tiles = layout
            return loop(dataclasses.replace(static, blocks=blocks,
                                            blocks_ext=blocks, bsrc=bsrc,
                                            bdst=bdst, live=live,
                                            src_order=src_order,
                                            src_tiles=src_tiles),
                        attrs0, aux0, frontier0, budgets)

        # `jit_flip_fixpoint_sharded` on the profiler's `XLA Modules` line
        @jax.jit
        def flip_fixpoint_sharded(layout, attrs0, aux0, frontier0,
                                  budgets):
            out = per_device(layout, widen(attrs0, zero), widen(aux0, 0),
                             widen(frontier0, False), budgets)
            return tuple(x[:, :static.ntiles] for x in out[:3]) + out[3:]

        return flip_fixpoint_sharded

    def _fixpoint_deadlined(self, attrs, aux, frontier, trace_cap: int,
                            budgets, deadlines_t, bg: BlockedGraph):
        """The fixpoint under per-query deadlines: the same program,
        called for one step at a time, so that each step boundary is one
        the host sees. Before each call the host reads which queries
        still have a frontier and freezes those whose `deadlines_t`
        entry has passed: they keep their frontier, so they read
        non-converged, and the work already done comes back as a flagged
        partial result. Each query still live and under its step budget
        gets a budget of one step for the call, every other a budget of
        none, so results and steps are bit-for-bit those of one call
        when no deadline bites.

        With `trace_cap`, each call runs the traced program with one
        row, which stays on the device until the loop ends, and the host
        clocks each step from its call to the next boundary's read
        (`StepTrace.step_wall_s`), which one whole-loop call cannot."""
        b = int(attrs.shape[0])
        budgets = np.asarray(budgets)
        program = self._dense_fixpoint_jit(min(trace_cap, 1), bg)
        layout = _layout_args(bg)
        expired = np.zeros(b, dtype=bool)
        steps = np.zeros(b, np.int32)
        rows: list[tuple] = []
        walls: list[float] = []
        n_iter = 0
        t0 = time.perf_counter()
        while True:
            with span("flip.step", step=n_iter):
                # the loop's one sync a step; it also ends the previous
                # traced step's wall
                active = np.asarray(frontier.any(axis=(1, 2)))
                if len(walls) < len(rows):
                    walls.append(time.perf_counter() - t0)
                # a deadline only *expires* a query that still has work
                # left: converged queries met their deadline by definition
                expired |= active & (deadlines_t <= time.monotonic())
                live = active & ~expired & (steps < budgets)
                if not live.any():
                    break
                t0 = time.perf_counter()
                out = program(layout, attrs, aux, frontier,
                              jnp.asarray(live.astype(np.int32)))
                attrs, aux, frontier = out[:3]
                if trace_cap and n_iter < trace_cap:
                    rows.append(out[6])
                steps += live.astype(np.int32)
                n_iter += 1
        read_trace = (functools.partial(self._read_rows, rows, walls,
                                        n_iter, trace_cap, bg, b)
                      if trace_cap else None)
        return attrs, aux, frontier, steps, read_trace, ~active, expired

    def _read_rows(self, rows, walls, n_iter: int, trace_cap: int,
                   bg: BlockedGraph, b: int):
        """The deadlined loop's one-row stat buffers read back as one
        ``(StepTrace, truncated)``, with each step's wall."""
        host = jax.device_get(rows)
        bufs = [np.concatenate([np.zeros((0,) + shape, dtype)]
                               + [r[i] for r in host])
                for i, (shape, dtype)
                in enumerate(self._trace_columns(bg, b))]
        trace, truncated = self._read_trace(n_iter, bufs, trace_cap, bg)
        return dataclasses.replace(
            trace, step_wall_s=np.asarray(walls, dtype=np.float64)), truncated

    # -------------------------------------------------------------- #
    # the one plan-driven executor
    # -------------------------------------------------------------- #
    def execute(self, srcs, *, warm: WarmStart | None = None,
                distributed: bool = False, mesh: Mesh | None = None,
                axis: str = "data", trace: bool | int = False,
                max_steps=None, deadline_s=None, detail: bool = False):
        """The single execution entry point every layer drives.

        One call uniformly covers what used to be four methods: a scalar
        `srcs` is a solo query (`(n,)` result, int steps), a sequence is
        a batch (`(B, n)` / `(B,)`), `warm` resumes from a prior
        converged result (incremental recompute, see `WarmStart` /
        `resolve_warm`), and `distributed=True` runs the shard_map
        fixpoint over `mesh` (default: all local devices) instead of the
        local one. Results are bit-for-bit identical across all of these
        axes -- batching, distribution, and warm starts never change the
        fixpoint, only how it is reached.

        `trace` turns on per-step frontier tracing (True = the default
        `TRACE_CAP_DEFAULT` row capacity, an int = that capacity) and
        makes the call return ``(out, steps, DispatchTelemetry)``
        instead of ``(out, steps)``; results and step counts are
        bit-identical with tracing on, local or sharded.

        `max_steps` (int or (B,) per-query ints) caps each query's
        relaxation steps below the session-wide `self.max_steps` valve;
        `deadline_s` (relative seconds, scalar or (B,) per query) stops
        a query at the first host-observable step boundary past its
        deadline. Either budget can leave a query short of its fixpoint
        -- the partial result is *flagged* via the per-query convergence
        mask, which `detail=True` exposes: the call then returns an
        `ExecutionDetail` (attrs / steps / converged / deadline_expired
        / telemetry) instead of the bare tuple. A deadline runs the
        fixpoint program one step per call (`_fixpoint_deadlined`); a
        distributed plan rejects deadlines.

        `repro.api.CompiledQuery` is the intended driver: it resolves an
        `ExecutionPlan` into these arguments.
        """
        batched = bool(np.ndim(srcs))
        srcs = np.atleast_1d(np.asarray(srcs, dtype=np.int64))
        budgets = self._resolve_budgets(max_steps, len(srcs))
        deadlines_t = self._resolve_deadlines(deadline_s, len(srcs))
        bg = self._layout(distributed, mesh, axis)
        if bg.shards is not None and deadlines_t is not None:
            raise InvalidRequest(
                "deadline_s is not supported on the distributed "
                "(shard_map) fixpoint: deadlines are enforced at "
                "host-observable step boundaries -- use max_steps, "
                "or run on a local plan")
        out, steps, tele, conv, expired = self._execute_local(
            srcs, warm=warm, trace_cap=self._trace_cap(trace),
            budgets=budgets, deadlines_t=deadlines_t, bg=bg)
        if detail:
            if batched:
                return ExecutionDetail(attrs=out, steps=steps,
                                       converged=conv,
                                       deadline_expired=expired,
                                       telemetry=tele)
            return ExecutionDetail(attrs=out[0], steps=int(steps[0]),
                                   converged=bool(conv[0]),
                                   deadline_expired=bool(expired[0]),
                                   telemetry=tele)
        r = (out, steps) if batched else (out[0], int(steps[0]))
        return r + (tele,) if trace else r

    def _layout(self, distributed: bool, mesh: Mesh | None,
                axis: str) -> BlockedGraph:
        """The layout a call runs over: the engine's own, or with
        `distributed` one sharded over `mesh`'s `axis` (default: all
        local devices) -- the engine's own when it is sharded so, else
        a sharded copy of it made once per mesh and kept."""
        bg = self.bg
        if not distributed:
            if bg.shards is not None:
                raise ValueError(
                    "this engine's layout is sharded over a mesh: run it "
                    "with distributed=True")
            return bg
        if mesh is None:
            if bg.shards is not None:
                return bg
            mesh = Mesh(np.array(jax.devices()), (axis,))
        key = mesh_key(mesh, axis)
        if bg.shards is not None:
            if bg.shards.key() != key:
                raise ValueError(
                    "this engine's layout is sharded over another mesh "
                    f"({bg.shards.key()}); asked for {key}")
            return bg
        cache = self.__dict__.setdefault("_shard_cache", {})
        if key not in cache:
            cache[key] = bg.shard(mesh, axis)
        return cache[key]

    def _resolve_budgets(self, max_steps, b: int):
        """Per-query step budgets ((B,) i32) from a caller cap: None
        keeps the session valve; an int or (B,) sequence is validated
        (>= 1) and clipped to `self.max_steps`."""
        if max_steps is None:
            return None
        budgets = np.atleast_1d(np.asarray(max_steps))
        if not np.issubdtype(budgets.dtype, np.integer):
            raise InvalidRequest(
                f"max_steps must be an int or a sequence of ints, got "
                f"dtype {budgets.dtype}", value=max_steps)
        if budgets.shape not in ((1,), (b,)):
            raise InvalidRequest(
                f"max_steps shape {budgets.shape} does not match the "
                f"{b} queries (scalar or one budget per query)",
                value=max_steps)
        if (budgets < 1).any():
            bad = int(budgets[budgets < 1][0])
            raise InvalidRequest(
                f"max_steps must be >= 1, got {bad}", value=bad)
        return np.minimum(
            np.broadcast_to(budgets, (b,)), self.max_steps
        ).astype(np.int32)

    def _resolve_deadlines(self, deadline_s, b: int):
        """Absolute per-query `time.monotonic` deadlines ((B,) f64) from
        relative seconds (scalar or per query; None / non-finite entries
        mean no deadline)."""
        if deadline_s is None:
            return None
        now = time.monotonic()
        rel = np.atleast_1d(np.asarray(
            [np.inf if d is None else float(d)
             for d in np.atleast_1d(deadline_s)], dtype=np.float64))
        if rel.shape not in ((1,), (b,)):
            raise InvalidRequest(
                f"deadline_s shape {rel.shape} does not match the "
                f"{b} queries (scalar or one deadline per query)",
                value=deadline_s)
        # rel <= 0 is legal here: a bucketed query's later chunks may
        # arrive with their deadline already spent -- they come back
        # immediately as flagged partials (the session validates that
        # *caller-supplied* deadlines are positive)
        if not np.isfinite(rel).any():
            return None
        return np.broadcast_to(now + rel, (b,)).copy()

    def _trace_cap(self, trace: bool | int) -> int:
        """0 (off) or the per-step trace row capacity."""
        if not trace:
            return 0
        cap = TRACE_CAP_DEFAULT if trace is True else int(trace)
        return max(1, min(cap, self.max_steps))

    def resolve_warm(self, prev, delta: UpdateDelta) -> WarmStart | None:
        """Warm-start dispatch after `apply_updates`: a `delta.monotone`
        batch on a monotone algebra may resume from `prev` with only
        `delta.affected_src` seeded active; anything else must recompute
        from scratch (returns None)."""
        if delta.monotone and self.algebra.kind == "monotone":
            return WarmStart(attrs=np.asarray(prev, dtype=np.float32),
                             seeds=delta.affected_src)
        return None

    def _execute_local(self, srcs, warm: WarmStart | None = None,
                       trace_cap: int = 0, budgets=None,
                       deadlines_t=None, bg: BlockedGraph | None = None):
        """One fixpoint over a (B,) source array; always batched. It runs
        on one device, or over the devices of `bg` when that is a
        sharded layout (`_layout`). Returns ``(out, steps,
        DispatchTelemetry | None, converged, deadline_expired)`` -- the
        last two are (B,) bool masks."""
        bg = self.bg if bg is None else bg
        with span("flip.prepare"):
            attrs0, aux0, frontier0 = self.initial_state(srcs, warm=warm)
            budgets = self._device_budgets(budgets, len(srcs))
        t0 = time.perf_counter()
        attrs, aux, _, steps, read_trace, converged, expired = \
            self._fixpoint(attrs0, aux0, frontier0, trace_cap,
                           budgets=budgets, deadlines_t=deadlines_t, bg=bg)
        with span("flip.finalize"):
            out = bg.to_orig(self.algebra.finalize(attrs, aux),
                             features=self._features)
            steps = np.asarray(steps)
        if read_trace is None:
            return out, steps, None, converged, expired
        # what only traced dispatches pay: the stat rows' readback
        with span("flip.telemetry") as sp:
            trace, truncated = read_trace()
            sp.set_metadata(rows=len(trace.active_tiles))
            b = int(steps.shape[0])
            path, grid_steps, walk = relax_grid(bg, b, self.relax_mode,
                                                self.feature_dim)
            meta = {}
            sh = bg.shards
            if sh is not None:
                # each step's all-gather: the whole (B, ntiles_p, T[, d])
                # f32 state, assembled on every device
                meta = {"devices": sh.ndev,
                        "tiles_per_device": sh.tiles_per_dev,
                        "slots_per_device": sh.slots,
                        "gather_bytes": b * sh.ntiles_p * bg.tile
                        * self.feature_dim * 4}
            tele = DispatchTelemetry(
                backend=self._resolved_relax_mode(), mode=self.mode,
                compact=self._use_compact, batch=b,
                n=bg.n, ntiles=bg.ntiles,
                n_blocks=bg.n_blocks, steps=steps,
                trace=trace, wall_s=time.perf_counter() - t0,
                truncated=truncated, tile=bg.tile,
                feature_dim=self.feature_dim, relax_path=path,
                relax_grid_steps=grid_steps, relax_walk=walk, meta=meta)
        return out, steps, tele, converged, expired

    # -------------------------------------------------------------- #
    # bounded-segment stepping: the continuous-batching yield surface
    # -------------------------------------------------------------- #
    def idle_state(self, b: int):
        """(B, ntiles, T[, d]) state with every query lane *inert*:
        ⊕-identity attrs, zero aux, empty frontier. An inert lane is
        frozen by the per-query live mask (its frontier never fills), so
        it costs nothing and cannot perturb the other lanes -- the
        rotating batch's empty slots live in this state until a queued
        query is admitted into them (`write_slot`)."""
        bg = self.bg
        zero = np.float32(self.algebra.semiring.zero)
        shape = (b, bg.ntiles, bg.tile)
        if self._features:
            shape = shape + (self.feature_dim,)
        return (jnp.full(shape, zero, dtype=jnp.float32),
                jnp.zeros(shape, dtype=jnp.float32),
                jnp.zeros((b, bg.ntiles, bg.tile), dtype=bool))

    def write_slot(self, state, b: int, src: int,
                   warm: WarmStart | None = None):
        """Admit one query into lane `b` of a rotating-batch state:
        lane `b` of (attrs, aux, frontier) is overwritten with the
        freshly initialized (or warm-resumed) solo state of `src`, all
        other lanes are untouched. Because every fixpoint operation is
        independent along the batch axis (the PR-2 bit-exactness
        contract), the admitted lane then evolves exactly as a solo run
        of `src` would -- regardless of what the other lanes are doing."""
        attrs, aux, frontier = state
        a1, x1, f1 = self.initial_state([int(src)], warm=warm)
        return (jnp.asarray(attrs).at[b].set(jnp.asarray(a1)[0]),
                jnp.asarray(aux).at[b].set(jnp.asarray(x1)[0]),
                jnp.asarray(frontier).at[b].set(jnp.asarray(f1)[0]))

    def run_segment(self, state, budgets):
        """Advance a (B, ...) fixpoint state by a bounded segment: lane
        `b` runs at most ``budgets[b]`` further steps (0 = frozen) and
        stops early the moment its frontier empties. This is the
        step-boundary yield hook the continuous-batching scheduler
        (`repro.serving`) is built on: between segments the host can
        retire converged lanes, admit queued queries into idle lanes,
        and enforce deadlines -- then re-enter with the same state.

        Returns ``(state, steps, converged)``: the advanced (attrs, aux,
        frontier) triple, the (B,) i32 steps actually taken this
        segment, and the (B,) bool end-of-segment convergence mask
        (True = frontier empty; inert/idle lanes read True).

        Segmenting is exact: a segment is a call of the one fixpoint
        program, so K-step segments compose into bit-for-bit the
        single-call fixpoint, per lane (budgets only partition the step
        sequence; they never change it). The program takes budgets as a
        traced argument, so varying segment lengths never retrace."""
        attrs, aux, frontier = state
        budgets = jnp.asarray(np.asarray(budgets, dtype=np.int32))
        attrs, aux, frontier, steps, _, converged, _ = self._fixpoint(
            attrs, aux, frontier, 0, budgets=budgets)
        return ((attrs, aux, frontier), np.asarray(steps),
                np.asarray(converged))

    def finalize_state(self, attrs, aux) -> np.ndarray:
        """Finalize a (tiled) fixpoint state into original-vertex-order
        results: (B, ntiles, T[, d]) -> (B, n[, d]). Lane-independent,
        so a rotating batch can finalize just the retiring lane by
        slicing ``attrs[b:b+1]``."""
        return self.bg.to_orig(self.algebra.finalize(attrs, aux),
                               features=self._features)

    # -------------------------------------------------------------- #
    # streaming graph mutations: delta-driven incremental recompute
    # -------------------------------------------------------------- #
    def apply_updates(self, new_graph: Graph,
                      updates) -> tuple["FlipEngine", "UpdateDelta"]:
        """Incremental re-block after a mutation batch: `new_graph` is
        the post-update Graph (``graph.apply_updates(updates)``). Only
        the touched tiles are rebuilt (`BlockedGraph.apply_updates`);
        value-only rebuilds keep every array shape, so the returned
        engine hits the same compiled executables. Returns
        ``(new_engine, delta)`` -- this engine is left untouched."""
        bg2, delta = self.bg.apply_updates(new_graph, updates)
        return dataclasses.replace(self, bg=bg2), delta
