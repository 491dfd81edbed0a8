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
`repro.kernels.frontier.ops`). On the Pallas/interpret paths the
compaction runs on-device inside the `while_loop` with static shapes; on
the jnp/CPU path static shapes cannot shrink, so the fixpoint is driven
from the host instead (`_fixpoint_host`) and each step runs a
power-of-two-bucketed compacted relax -- the step cost tracks the live
frontier, O(active·T²), instead of O(nb·T²). Compaction is exact (the
⊕-identity annihilates ⊗), so results and step counts are bit-for-bit
the dense-streaming ones.

The algorithm is any registered `VertexAlgebra` (bfs, sssp, wcc,
pagerank, widest, reach, ...): the engine itself only threads the
algebra's scatter/carry/post-step hooks around the semiring relax kernel,
so a new algebra runs here unchanged.

Execution is batched over independent queries: the state is
(B, ntiles, T) -- B sources relaxing against one shared block structure
inside one `jax.lax.while_loop` fixpoint. `FlipEngine.execute` is the
single entry point (scalar source = the B=1 view; `distributed=True`
switches to the shard_map fixpoint; `warm=` resumes a prior result) --
the legacy `run`/`run_batch`/`run_distributed`/`run_updated` methods are
deprecated shims over it, and `repro.api` ( `flip.compile(graph,
program, plan).query(srcs)` ) is the intended front door. Queries whose
frontier has emptied are frozen by a per-query convergence mask, so a
long-tail query never perturbs finished ones and batched results are
bit-for-bit the per-source results.

Both paths can execute distributed via `shard_map`: destination tiles
are partitioned over a mesh axis (devices = PE clusters), queries stay
replicated, each device relaxes its local blocks, and the updated
attribute vector is re-assembled with an all-gather -- the collective is
the NoC, and its cost amortizes over the whole batch.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.algebra import VertexAlgebra
from repro.core.mapping import Mapping
from repro.graphs.csr import Graph
from repro.kernels.frontier.ops import (BlockedGraph, UpdateDelta,
                                        build_blocks, frontier_relax,
                                        resolve_relax_mode, tile_activity)
from repro.obs.telemetry import DispatchTelemetry, StepTrace
from repro.resilience.errors import InvalidRequest

# default per-step trace row capacity (`execute(trace=True)`): enough for
# any realistic fixpoint (diameters are O(100) even on road networks)
# while keeping the traced stat buffers a few hundred KB. Pass an int as
# `trace` to override; steps beyond the capacity still execute exactly
# (only their trace rows are dropped, flagged `truncated`).
TRACE_CAP_DEFAULT = 4096


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
    `FlipEngine.run_updated` applies that dispatch automatically.
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
        bg = build_blocks(graph, algo=algo, tile=tile, order=order)
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

    def _step(self, attrs, aux, frontier, with_stats: bool = False,
              bg: BlockedGraph | None = None):
        """One relax step over `bg` (default: the engine's own layout;
        the device fixpoint passes a view whose arrays are its traced
        arguments)."""
        alg, features = self.algebra, self._features
        bg = self.bg if bg is None else bg
        sv, carry = alg.scatter_carry_jnp(attrs, frontier,
                                          op_mode=(self.mode == "op"),
                                          features=features)
        new = frontier_relax(sv, carry, bg, mode=self.relax_mode,
                             compact=self._use_compact,
                             feature_dim=self.feature_dim)
        out = alg.post_step_jnp(attrs, aux, sv, new, features=features)
        if not with_stats:
            return out
        if bg is self.bg:
            return out, self._step_stats_jit()(sv, frontier)
        return out, self._step_stats(sv, frontier, bg)

    def _step_stats(self, sv, frontier, bg: BlockedGraph | None = None):
        """One trace row's worth of per-step stats, computed from the
        exact quantities the compaction machinery derives anyway: the
        frontier entering the step, the per-tile activity of the
        scattered source values (the kernel's packet-trigger condition),
        and the resulting active-block count. Pure extra outputs -- the
        step math never reads them, so traced runs stay bit-identical.

        Returns ``(active_vertices (B,), active_tiles (), fetched ())``
        as i32; `fetched` is the blocks streamed from HBM this step
        (active blocks under compaction, all blocks under dense)."""
        bg = self.bg if bg is None else bg
        act = tile_activity(sv, bg.semiring, self._features)  # (ntiles,)
        active_tiles = jnp.sum(act.astype(jnp.int32))
        nb = bg.bsrc.shape[0]
        if self._use_compact:
            fetched = jnp.sum(jnp.take(act, bg.bsrc).astype(jnp.int32))
        else:
            fetched = jnp.int32(nb)
        active_v = jnp.sum(frontier, axis=(1, 2)).astype(jnp.int32)
        return active_v, active_tiles, fetched

    def _step_stats_jit(self):
        """`_step_stats` as one cached jitted dispatch: the host-driven
        fixpoint runs its step eagerly (it must read concrete frontiers),
        so fusing the half-dozen stat reductions into a single call keeps
        traced host steps within the overhead bound. Inside the jitted
        while_loop body the same tracing inlines and the wrapper is
        free."""
        fn = self.__dict__.get("_step_stats_fn")
        if fn is None:
            fn = self.__dict__["_step_stats_fn"] = jax.jit(self._step_stats)
        return fn

    def _masked_step(self, attrs, aux, frontier, live,
                     with_stats: bool = False,
                     bg: BlockedGraph | None = None):
        """One relax step with the per-query freeze applied: queries not
        in `live` ((B,) bool -- frontier emptied, or step/deadline budget
        exhausted) keep their state *and their frontier* untouched, so a
        budget-frozen query still reads as non-converged (frontier
        non-empty) while a finished one stays finished (its frontier
        emptied naturally). The single body behind both fixpoint
        drivers, so host-driven and while_loop runs stay bit-for-bit
        identical."""
        stepped = self._step(attrs, aux, frontier, with_stats=with_stats,
                             bg=bg)
        (attrs_n, aux_n, frontier_n), stats = \
            stepped if with_stats else (stepped, None)
        # live broadcasts from the query axis over every trailing state
        # axis: (B, 1, 1) against (B, ntiles, T), one more 1 at d > 1
        ms = live.reshape(live.shape + (1,) * (attrs.ndim - 1))
        out = (jnp.where(ms, attrs_n, attrs),
               jnp.where(ms, aux_n, aux),
               jnp.where(live[:, None, None], frontier_n, frontier))
        return (out, stats) if with_stats else out

    def _fixpoint(self, attrs0, aux0, frontier0, trace_cap: int = 0,
                  budgets=None, deadlines_t=None):
        """Shared (B, ntiles, T) while_loop with per-query convergence
        masking: a query whose frontier emptied is frozen, so late
        queries in the batch cannot perturb finished ones (op-mode
        sweeps and residual aux accumulation would otherwise keep
        touching them) and per-query step counts match solo runs.

        `budgets` ((B,) i32, default: `max_steps` everywhere) is the
        per-query step cap: a query that reaches its budget with a
        non-empty frontier is frozen exactly like a converged one but
        keeps its frontier, so the final per-query convergence mask
        (returned as the 5th element) reads False for it -- a partial
        result is always *flagged*, never silently truncated. Budgets
        are a traced argument of the one compiled while_loop, so
        varying them never retraces.

        `deadlines_t` ((B,) absolute `time.monotonic` deadlines, +inf =
        none) needs host-observable step boundaries, so any finite
        deadline routes the fixpoint through the host driver (same
        body, bit-for-bit results). Compacted jnp streaming routes
        there too (concrete frontiers pick the bucket sizes).

        `trace_cap > 0` additionally records one per-step stats row into
        fixed-shape (trace_cap, ...) buffers riding the carry (see
        `_step_stats`). Returns ``(attrs, aux, frontier, steps, trace,
        converged, expired)`` where `trace` is a `(StepTrace, truncated)`
        pair or None, `converged` is the (B,) bool end-of-run mask, and
        `expired` marks deadline-stopped queries. The final frontier is
        part of the return so a bounded-budget run is *resumable*: the
        continuous-batching scheduler (`repro.serving`) re-enters with
        the same state to run the next segment. The stat buffers are
        write-only extra outputs, so attrs and step counts are
        bit-identical either way."""
        b = attrs0.shape[0]
        if budgets is None:
            budgets = jnp.full((b,), self.max_steps, dtype=jnp.int32)
        else:
            budgets = jnp.asarray(np.broadcast_to(
                np.asarray(budgets, dtype=np.int32), (b,)))
        deadlined = (deadlines_t is not None
                     and bool(np.isfinite(deadlines_t).any()))
        if deadlined or (self._use_compact
                         and self._resolved_relax_mode() == "jnp"):
            return self._fixpoint_host(attrs0, aux0, frontier0, trace_cap,
                                       budgets=budgets,
                                       deadlines_t=deadlines_t)
        bg = self.bg
        out = self._dense_fixpoint_jit(trace_cap)(
            (bg.blocks, bg.blocks_ext, bg.bsrc, bg.bdst), attrs0, aux0,
            frontier0, budgets)
        attrs, aux, frontier, steps = out[0], out[1], out[2], out[3]
        converged = ~np.asarray(frontier.any(axis=(1, 2)))
        expired = np.zeros(b, dtype=bool)
        if not trace_cap:
            return attrs, aux, frontier, steps, None, converged, expired
        n_iter = int(out[5])
        rows = min(n_iter, trace_cap)
        b_av, b_at, b_bf, b_cv = (np.asarray(x)[:rows] for x in out[6])
        nb = int(self.bg.bsrc.shape[0])
        trace = StepTrace(active_vertices=b_av, active_tiles=b_at,
                          blocks_fetched=b_bf,
                          blocks_skipped=np.int32(nb) - b_bf,
                          converged=b_cv)
        return (attrs, aux, frontier, steps,
                (trace, n_iter > trace_cap), converged, expired)

    def _dense_fixpoint_jit(self, trace_cap: int):
        """The whole dense while_loop compiled as ONE jitted program per
        (engine, trace_cap), cached on the instance: eager per-call
        dispatch of the loop would otherwise dominate the step cost (and
        blow the traced/untraced overhead bound). The traced variant only
        adds fixed-shape stat-buffer writes to the carry, so both compile
        to the same fused step with tracing as a few extra reductions.

        The layout's arrays (blocks, sentinel-extended blocks, bsrc,
        bdst) are arguments of the program, not values it closes over:
        a closed-over array is embedded in the program as a constant,
        which at a million vertices means gigabytes of constants to
        lower, compile and hold a second time on the device."""
        cache = self.__dict__.setdefault("_fixpoint_cache", {})
        fn = cache.get(trace_cap)
        if fn is not None:
            return fn

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
                    attrs, aux, frontier, live, bg=bg)
                return (attrs, aux, frontier,
                        steps + live.astype(jnp.int32), budgets)
            it, (b_av, b_at, b_bf, b_cv) = state[5], state[6]
            (attrs, aux, frontier), (av, at, bf) = self._masked_step(
                attrs, aux, frontier, live, with_stats=True, bg=bg)
            # rows past the capacity are dropped, not wrapped: the trace
            # stays a prefix of the run and `truncated` flags the cut
            bufs = (b_av.at[it].set(av, mode="drop"),
                    b_at.at[it].set(at, mode="drop"),
                    b_bf.at[it].set(bf, mode="drop"),
                    b_cv.at[it].set(~live, mode="drop"))
            return (attrs, aux, frontier, steps + live.astype(jnp.int32),
                    budgets, it + 1, bufs)

        @jax.jit
        def run(layout, attrs0, aux0, frontier0, budgets):
            blocks, blocks_ext, bsrc, bdst = layout
            bg = dataclasses.replace(self.bg, blocks=blocks,
                                     blocks_ext=blocks_ext, bsrc=bsrc,
                                     bdst=bdst)
            b = attrs0.shape[0]
            state0 = (attrs0, aux0, frontier0, jnp.zeros(b, jnp.int32),
                      budgets)
            if trace_cap:
                bufs0 = (jnp.zeros((trace_cap, b), jnp.int32),
                         jnp.zeros((trace_cap,), jnp.int32),
                         jnp.zeros((trace_cap,), jnp.int32),
                         jnp.zeros((trace_cap, b), bool))
                state0 = state0 + (jnp.int32(0), bufs0)
            return jax.lax.while_loop(cond, functools.partial(body, bg),
                                      state0)

        cache[trace_cap] = run
        return run

    def _fixpoint_host(self, attrs, aux, frontier, trace_cap: int = 0,
                       budgets=None, deadlines_t=None):
        """Host-driven fixpoint for compacted jnp streaming and for
        deadline-budgeted queries: identical body semantics to the
        while_loop above (same live-mask freezing, same step accounting
        -- bit-for-bit results), but each step reads the concrete
        frontier so `frontier_relax` can bucket the compacted block list
        -- and, because every step boundary is host-observable, this is
        where per-query deadlines are enforced: a query whose
        `deadlines_t` entry has passed is frozen (kept frontier, so it
        reads non-converged) before the next step starts; work already
        done is returned as a flagged partial result.

        With `trace_cap`, stats rows are recorded host-side -- and since
        this loop observes every step from the host anyway, it also
        records real per-step wall times (`StepTrace.step_wall_s`),
        which the on-device while_loop cannot."""
        b = int(attrs.shape[0])
        if budgets is None:
            budgets = np.full(b, self.max_steps, dtype=np.int32)
        budgets = np.asarray(budgets)
        deadlines = (None if deadlines_t is None
                     or not np.isfinite(deadlines_t).any()
                     else np.broadcast_to(np.asarray(deadlines_t,
                                                     dtype=np.float64),
                                          (b,)))
        expired = np.zeros(b, dtype=bool)
        steps = np.zeros(b, np.int32)
        rows: list[tuple] = []
        walls: list[float] = []
        n_iter = 0
        t0 = time.perf_counter()
        while True:
            # this concrete read is the loop's natural per-step sync: it
            # also closes the previous traced step's wall measurement, so
            # tracing adds no extra host<->device round trips
            active = np.asarray(frontier.any(axis=(1, 2)))
            if len(walls) < len(rows):
                walls.append(time.perf_counter() - t0)
            if deadlines is not None:
                # a deadline only *expires* a query that still has work
                # left: converged queries met their deadline by definition
                expired |= active & (deadlines <= time.monotonic())
            live = active & ~expired & (steps < budgets)
            if not live.any():
                break
            t0 = time.perf_counter()
            if trace_cap:
                (attrs, aux, frontier), st = self._masked_step(
                    attrs, aux, frontier, jnp.asarray(live),
                    with_stats=True)
                if n_iter < trace_cap:
                    # stats stay on device until after the loop: only
                    # the row tuple is kept per step
                    av, at, bf = st
                    rows.append((av, at, bf, ~live))
            else:
                attrs, aux, frontier = self._masked_step(
                    attrs, aux, frontier, jnp.asarray(live))
            steps = steps + live.astype(np.int32)
            n_iter += 1
        converged = ~np.asarray(frontier.any(axis=(1, 2)))
        if not trace_cap:
            return (attrs, aux, frontier, jnp.asarray(steps), None,
                    converged, expired)
        nb = int(self.bg.bsrc.shape[0])
        bf = np.asarray([int(r[2]) for r in rows], dtype=np.int32)
        trace = StepTrace(
            active_vertices=(np.stack([np.asarray(r[0]) for r in rows])
                             .astype(np.int32) if rows
                             else np.zeros((0, b), np.int32)),
            active_tiles=np.asarray([int(r[1]) for r in rows],
                                    dtype=np.int32),
            blocks_fetched=bf,
            blocks_skipped=np.int32(nb) - bf,
            converged=(np.stack([r[3] for r in rows]) if rows
                       else np.zeros((0, b), bool)),
            step_wall_s=np.asarray(walls, dtype=np.float64))
        return (attrs, aux, frontier, jnp.asarray(steps),
                (trace, n_iter > trace_cap), converged, expired)

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
        bit-identical with tracing on. Tracing the shard_map fixpoint is
        not supported yet.

        `max_steps` (int or (B,) per-query ints) caps each query's
        relaxation steps below the session-wide `self.max_steps` valve;
        `deadline_s` (relative seconds, scalar or (B,) per query) stops
        a query at the first host-observable step boundary past its
        deadline. Either budget can leave a query short of its fixpoint
        -- the partial result is *flagged* via the per-query convergence
        mask, which `detail=True` exposes: the call then returns an
        `ExecutionDetail` (attrs / steps / converged / deadline_expired
        / telemetry) instead of the bare tuple. Deadlines are a local
        (host-driven) mechanism; a distributed plan rejects them.

        `repro.api.CompiledQuery` is the intended driver: it resolves an
        `ExecutionPlan` into these arguments. The legacy `run*` methods
        are deprecated shims over this method.
        """
        batched = bool(np.ndim(srcs))
        srcs = np.atleast_1d(np.asarray(srcs, dtype=np.int64))
        budgets = self._resolve_budgets(max_steps, len(srcs))
        deadlines_t = self._resolve_deadlines(deadline_s, len(srcs))
        if distributed:
            if trace:
                raise ValueError(
                    "per-step tracing is not supported on the "
                    "distributed (shard_map) fixpoint yet; run the "
                    "trace on a local plan")
            if deadlines_t is not None:
                raise InvalidRequest(
                    "deadline_s is not supported on the distributed "
                    "(shard_map) fixpoint: deadlines are enforced at "
                    "host-observable step boundaries -- use max_steps, "
                    "or run on a local plan")
            out, steps, conv = self._execute_distributed(
                srcs, warm=warm, mesh=mesh, axis=axis, budgets=budgets)
            tele, expired = None, np.zeros(len(srcs), dtype=bool)
        else:
            out, steps, tele, conv, expired = self._execute_local(
                srcs, warm=warm, trace_cap=self._trace_cap(trace),
                budgets=budgets, deadlines_t=deadlines_t)
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
                       deadlines_t=None):
        """Local fixpoint over a (B,) source array; always batched.
        Returns ``(out, steps, DispatchTelemetry | None, converged,
        deadline_expired)`` -- the last two are (B,) bool masks."""
        attrs0, aux0, frontier0 = self.initial_state(srcs, warm=warm)
        t0 = time.perf_counter()
        attrs, aux, _, steps, rec, converged, expired = self._fixpoint(
            attrs0, aux0, frontier0, trace_cap, budgets=budgets,
            deadlines_t=deadlines_t)
        out = self.bg.to_orig(self.algebra.finalize(attrs, aux),
                              features=self._features)
        steps = np.asarray(steps)
        tele = None
        if rec is not None:
            trace, truncated = rec
            tele = DispatchTelemetry(
                backend=self._resolved_relax_mode(), mode=self.mode,
                compact=self._use_compact, batch=int(steps.shape[0]),
                n=self.bg.n, ntiles=self.bg.ntiles,
                n_blocks=int(self.bg.bsrc.shape[0]), steps=steps,
                trace=trace, wall_s=time.perf_counter() - t0,
                truncated=truncated, tile=self.bg.tile,
                feature_dim=self.feature_dim)
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

        Segmenting is exact: the per-step body is `_masked_step` -- the
        same body both fixpoint drivers run -- so K-step segments
        compose into bit-for-bit the single-call fixpoint, per lane
        (budgets only partition the step sequence; they never change
        it). The dense while_loop path takes budgets as a traced
        argument, so varying segment lengths never retrace."""
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

    # -------------------------------------------------------------- #
    def _execute_distributed(self, srcs, warm: WarmStart | None = None,
                             mesh: Mesh | None = None, axis: str = "data",
                             budgets=None):
        """shard_map fixpoint over a (B,) source array; always batched:
        destination tiles sharded over `axis`, queries replicated.
        `warm` resumes from a prior converged result (see `WarmStart`),
        so incremental recompute after a monotone update batch works
        distributed too.

        Each device owns a contiguous slab of destination tiles and the
        blocks that write them; per step it computes its slab's new attrs
        for every query in the batch and the global attribute vector is
        re-formed with an all-gather (the TPU analogue of FLIP's NoC
        scatter) -- one collective per step regardless of B, so the NoC
        cost amortizes over the batch. Works for every registered algebra
        in both 'data' and 'op' modes; a device whose slab holds only
        padded tiles owns zero real blocks and runs identity no-op blocks.

        Because blocks are bdst-sorted, each device's slab is one
        contiguous range of the block list, sliced directly from the
        precomputed per-destination layout (`bg.dst_start`). In data mode
        the per-device frontier compaction is the degenerate exact form:
        a device none of whose local blocks has an active source returns
        its carry without touching the weight slab (`lax.cond`), so
        frontier locality idles whole devices just like FLIP's inactive
        PE clusters.
        """
        if mesh is None:
            devs = np.array(jax.devices())
            mesh = Mesh(devs, (axis,))
        ndev = mesh.shape[axis]
        bg = self.bg
        zero = np.float32(self.algebra.semiring.zero)

        # pad tiles to a multiple of ndev, then slice each device's block
        # slab straight out of the bdst-sorted list via the precomputed
        # per-destination layout (no per-block Python loop)
        ntiles_p = -(-bg.ntiles // ndev) * ndev
        bsrc, bdst = np.asarray(bg.bsrc), np.asarray(bg.bdst)
        tiles_per_dev = ntiles_p // ndev
        bounds = np.minimum(np.arange(0, ntiles_p + 1, tiles_per_dev),
                            bg.ntiles)
        starts = np.asarray(bg.dst_start)[bounds]        # (ndev+1,)
        # >= 1 so a device owning zero blocks still gets a (1, T, T)
        # all-identity slab (exact no-op) instead of a zero-size array
        max_nb = max(1, int(np.diff(starts).max()))
        t = bg.tile
        blocks_sh = np.full((ndev, max_nb, t, t), zero, dtype=np.float32)
        bsrc_sh = np.zeros((ndev, max_nb), dtype=np.int32)
        bdst_sh = np.zeros((ndev, max_nb), dtype=np.int32)
        valid_sh = np.zeros((ndev, max_nb), dtype=bool)
        blocks_np = np.asarray(bg.blocks)
        for dev in range(ndev):
            s, e = int(starts[dev]), int(starts[dev + 1])
            blocks_sh[dev, :e - s] = blocks_np[s:e]
            bsrc_sh[dev, :e - s] = bsrc[s:e]
            # destination indices local to the device slab
            bdst_sh[dev, :e - s] = bdst[s:e] - dev * tiles_per_dev
            valid_sh[dev, :e - s] = True
            # padding slots hold all-⊕-identity blocks (exact no-ops) and
            # repeat the last real slot's tile pair, so the kernel never
            # revisits an earlier destination slab; valid=False keeps
            # them out of the idle-skip predicate. A block-less device
            # keeps tile pair (0, 0) throughout.
            if e > s:
                bsrc_sh[dev, e - s:] = bsrc_sh[dev, e - s - 1]
                bdst_sh[dev, e - s:] = bdst_sh[dev, e - s - 1]

        features = self._features
        attrs0, aux0, frontier0 = self.initial_state(srcs, warm=warm)
        pad = ntiles_p - bg.ntiles
        if pad:
            widths = ((0, 0), (0, pad)) + ((0, 0),) * (attrs0.ndim - 2)
            attrs0 = jnp.pad(attrs0, widths, constant_values=zero)
            aux0 = jnp.pad(aux0, widths)
            frontier0 = jnp.pad(frontier0, ((0, 0), (0, pad), (0, 0)))
        if budgets is None:
            budgets0 = np.full(srcs.shape[0], self.max_steps,
                               dtype=np.int32)
        else:
            budgets0 = np.asarray(budgets, dtype=np.int32)

        # each device receives only its own slab: placing the stacked
        # (ndev, max_nb, T, T) array with one jnp.asarray would first
        # land all of it on the default device
        shard = NamedSharding(mesh, P(axis))
        slabs = [jax.device_put(a, shard)
                 for a in (blocks_sh, bsrc_sh, bdst_sh, valid_sh)]
        attrs_f, aux_f, steps, conv = self._dist_fixpoint(
            mesh, axis, tiles_per_dev)(*slabs, attrs0, aux0, frontier0,
                                       jnp.asarray(budgets0))
        out = self.algebra.finalize(attrs_f, aux_f)
        out = self.bg.to_orig(out[:, :bg.ntiles], features=features)
        return out, np.asarray(steps), np.asarray(conv)

    def _dist_fixpoint(self, mesh: Mesh, axis: str, tiles_per_dev: int):
        """The jitted shard_map fixpoint of `_execute_distributed` over
        `mesh`: arguments are the (ndev, max_nb, ...) per-device slabs
        (sharded over `axis`) and the replicated (B, ntiles_p, T[, d])
        state plus (B,) budgets. Each device relaxes the whole source
        state into its own destination slab through the same
        `frontier_relax` dispatch as the local fixpoint, so every
        destination tile accumulates its blocks in the same order and
        the results are bit-for-bit the local ones."""
        alg = self.algebra
        features = self._features
        op_mode = self.mode == "op"
        skip_idle = self._use_compact

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis),
                      P(None), P(None), P(None), P(None)),
            out_specs=(P(None), P(None), P(None), P(None)),
            check_vma=False)
        def dist_fix(blocks, bsrc_l, bdst_l, valid_l, attrs, aux, frontier,
                     budgets):
            blocks, bsrc_l, bdst_l, valid_l = (blocks[0], bsrc_l[0],
                                               bdst_l[0], valid_l[0])
            # this device's layout view; the dense relax never reads
            # blocks_ext, and passing one keeps __post_init__ from
            # building a sentinel-extended copy inside the trace
            local = dataclasses.replace(self.bg, blocks=blocks,
                                        blocks_ext=blocks, bsrc=bsrc_l,
                                        bdst=bdst_l)

            def cond(state):
                _, _, frontier, steps = state
                return jnp.logical_and(frontier.any(axis=(1, 2)),
                                       steps < budgets).any()

            def relax_local(args):
                sv, carry_local = args
                return frontier_relax(sv, carry_local, local,
                                      mode=self.relax_mode,
                                      feature_dim=self.feature_dim)

            def body(state):
                attrs, aux, frontier, steps = state
                live = jnp.logical_and(frontier.any(axis=(1, 2)),
                                       steps < budgets)
                sv, carry = alg.scatter_carry_jnp(attrs, frontier, op_mode,
                                                  features=features)
                carry_local = jax.lax.dynamic_slice_in_dim(
                    carry, jax.lax.axis_index(axis) * tiles_per_dev,
                    tiles_per_dev, axis=1)
                if skip_idle:
                    # per-device frontier compaction, degenerate exact
                    # form: no active source among the local *real*
                    # blocks (any query) => the local relax is pure
                    # ⊕-identity, so return the carry without touching
                    # the weight slab
                    act = tile_activity(sv, alg.semiring, features)
                    new_local = jax.lax.cond(
                        jnp.any(jnp.logical_and(act[bsrc_l], valid_l)),
                        relax_local, lambda args: args[1],
                        (sv, carry_local))
                else:
                    new_local = relax_local((sv, carry_local))
                new = jax.lax.all_gather(new_local, axis, axis=1,
                                         tiled=True)
                attrs_n, aux_n, frontier_n = alg.post_step_jnp(
                    attrs, aux, sv, new, features=features)
                ms = live.reshape(live.shape + (1,) * (attrs.ndim - 1))
                return (jnp.where(ms, attrs_n, attrs),
                        jnp.where(ms, aux_n, aux),
                        jnp.where(live[:, None, None], frontier_n,
                                  frontier),
                        steps + live.astype(jnp.int32))

            steps0 = jnp.zeros(attrs.shape[0], jnp.int32)
            attrs_f, aux_f, frontier_f, steps = jax.lax.while_loop(
                cond, body, (attrs, aux, frontier, steps0))
            conv = jnp.logical_not(frontier_f.any(axis=(1, 2)))
            return attrs_f, aux_f, steps, conv

        return jax.jit(dist_fix)

    # -------------------------------------------------------------- #
    # deprecated pre-api entry points: thin shims over `execute`
    # -------------------------------------------------------------- #
    @staticmethod
    def _warn_legacy(name: str) -> None:
        warnings.warn(
            f"FlipEngine.{name} is deprecated; compile a session with "
            "flip.compile(graph, program, plan) (repro.api) and call "
            ".query(...), or drive FlipEngine.execute directly",
            DeprecationWarning, stacklevel=3)

    def run(self, src: int = 0, warm: WarmStart | None = None):
        """Deprecated: `execute(src)`. Single-query fixpoint; returns
        the algebra's result vector in original vertex order plus the
        number of relaxation steps taken."""
        self._warn_legacy("run")
        return self.execute(int(src), warm=warm)

    def run_batch(self, srcs, warm: WarmStart | None = None):
        """Deprecated: `execute(srcs)` with a sequence. Batched fixpoint
        over B independent sources sharing one weight-block stream;
        returns ((B, n) results, (B,) per-query step counts), each row
        bit-for-bit the corresponding solo result."""
        self._warn_legacy("run_batch")
        return self.execute(np.atleast_1d(np.asarray(srcs)), warm=warm)

    def run_distributed(self, src=0, mesh: Mesh | None = None,
                        axis: str = "data", warm: WarmStart | None = None):
        """Deprecated: `execute(src, distributed=True)`. shard_map
        fixpoint with destination tiles sharded over `axis`; shapes
        follow `src` like `execute`."""
        self._warn_legacy("run_distributed")
        return self.execute(src, warm=warm, distributed=True,
                            mesh=mesh, axis=axis)

    def run_updated(self, src, prev, delta: UpdateDelta):
        """Deprecated: `execute(src, warm=resolve_warm(prev, delta))`.
        Recompute after `apply_updates`, incrementally when sound (see
        `resolve_warm`); the result is bit-for-bit the from-scratch
        fixpoint on the updated graph either way."""
        self._warn_legacy("run_updated")
        return self.execute(src, warm=self.resolve_warm(prev, delta))
