"""ExecutionPlan: every execution knob of a FLIP query in one typed,
validated place.

The engine layers grew one string/bool knob at a time -- fabric `mode`,
kernel `relax_mode`, frontier `compact`ion, tile size, serving batch
size, device mesh, warm-start policy -- spread over `FlipEngine.build`
arguments, per-call parameters, and CLI flags with their own spellings.
An `ExecutionPlan` captures all of them as one frozen dataclass with a
single `resolve()` step that (a) validates every combination up front
(bad combos fail at compile time with one clear error, not deep inside a
jit trace) and (b) collapses every ``"auto"`` to its concrete choice, so
a resolved plan is a complete, reproducible record of how a query ran.

`flip.compile(graph, program, plan)` takes a plan (default:
`ExecutionPlan.auto()`) and attaches the resolved form to every
`QueryResult`.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import numpy as np
from jax.sharding import Mesh

from repro.algebra import VertexAlgebra
from repro.kernels.frontier.ops import (BlockKeys, mesh_key,
                                        resolve_relax_mode)

MODES = ("data", "op")
RELAX_MODES = ("auto", "pallas", "interpret", "jnp")
WARM_POLICIES = ("auto", "always", "never")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """How a compiled query executes. All fields have working defaults;
    ``"auto"`` values are collapsed by `resolve()`.

    mode        -- 'data' (FLIP packet-triggered frontier execution) or
                   'op' (classic-CGRA full sweep per step).
    relax_mode  -- kernel dispatch: 'auto' (Pallas on TPU, jnp
                   elsewhere), 'pallas', 'interpret', or 'jnp'.
    compact     -- frontier-compacted block streaming: True / False /
                   'auto' (= on exactly for data mode). Always exact.
    tile        -- block tile size (vertices per tile).
    batch       -- serving bucket size: 0 runs any source sequence as
                   one fixpoint; B > 0 dispatches fixed-size, padded
                   buckets of B so every dispatch reuses one compiled
                   (B, ntiles, T) executable (the GraphServer policy).
    distributed -- run the shard_map fixpoint (destination tiles
                   sharded over `mesh_axis`, queries replicated).
                   `flip.compile` also chooses it when the graph's
                   blocks do not fit one device (`place`).
    mesh        -- jax Mesh for distributed runs (None = all local
                   devices); supplying a mesh implies distributed=True.
    mesh_axis   -- mesh axis name the tiles shard over.
    warm        -- incremental-recompute policy for `query(..., warm=)`:
                   'auto' resumes from the prior result whenever sound
                   (monotone algebra + monotone update delta) and falls
                   back to scratch otherwise; 'always' errors instead of
                   falling back; 'never' forbids warm starts.
    feature_dim -- feature width d of the vertex state: 0 ('auto')
                   adopts the program's native width (1 for the scalar
                   programs, d for vector programs like multi_bfs);
                   d > 1 on a scalar program runs it over d broadcast
                   feature lanes ((n, d) results). A vector program can
                   only run at its native width -- `resolve()` rejects
                   mismatches.
    max_steps   -- fixpoint safety valve.
    deadline_s  -- default per-request wall-clock budget in seconds
                   (None = unbounded). `query(deadline_s=...)` overrides
                   per call; queries it stops come back as flagged
                   partials (`deadline_expired`), never silent
                   truncations. Not supported on distributed plans.
    tuned       -- ask the plan autotuner (`repro.autotune`) to pick
                   the performance knobs (tile / relax_mode / compact /
                   batch) for this (graph, program, backend) at compile
                   time, consulting the tuning store first. Pure
                   policy: a tuned plan is bit-exact with the default.
                   `resolve()` alone leaves the flag in place -- it has
                   no graph to tune against; `flip.compile` is where it
                   collapses.
    """

    mode: str = "data"
    relax_mode: str = "auto"
    compact: bool | str = "auto"
    tile: int = 128
    batch: int = 0
    distributed: bool = False
    mesh: object = None          # jax.sharding.Mesh | None
    mesh_axis: str = "data"
    warm: str = "auto"
    feature_dim: int = 0         # 0 = auto (the program's native width)
    max_steps: int = 100_000
    deadline_s: float | None = None
    tuned: bool = False

    # -------------------------------------------------------------- #
    @classmethod
    def auto(cls, **overrides) -> "ExecutionPlan":
        """The default plan (every knob on 'auto'), with overrides."""
        return cls(**overrides)

    def validate(self, algebra: VertexAlgebra | None = None) -> None:
        """Reject inconsistent knob combinations with one clear error.
        With `algebra`, additionally checks algebra-dependent combos
        (warm='always' needs a monotone algebra)."""
        if self.mode not in MODES:
            raise ValueError(
                f"plan.mode must be one of {MODES}, got {self.mode!r}")
        if self.relax_mode not in RELAX_MODES:
            raise ValueError(
                f"plan.relax_mode must be one of {RELAX_MODES}, got "
                f"{self.relax_mode!r}")
        if self.compact not in (True, False, "auto"):
            raise ValueError(
                "plan.compact must be True, False, or 'auto', got "
                f"{self.compact!r}")
        if self.compact is True and self.mode == "op":
            raise ValueError(
                "plan.compact=True is inconsistent with mode='op': an "
                "op-mode sweep relaxes every block by definition, so "
                "there is nothing to compact -- use mode='data' or "
                "compact='auto'")
        if not isinstance(self.tile, int) or self.tile < 1:
            raise ValueError(f"plan.tile must be a positive int, got "
                             f"{self.tile!r}")
        if not isinstance(self.batch, int) or self.batch < 0:
            raise ValueError(
                f"plan.batch must be an int >= 0 (0 = one fixpoint over "
                f"the whole source sequence), got {self.batch!r}")
        if self.warm not in WARM_POLICIES:
            raise ValueError(
                f"plan.warm must be one of {WARM_POLICIES}, got "
                f"{self.warm!r}")
        if not isinstance(self.feature_dim, int) or self.feature_dim < 0:
            raise ValueError(
                f"plan.feature_dim must be an int >= 0 (0 = the "
                f"program's native width), got {self.feature_dim!r}")
        if algebra is not None and algebra.feature_dim > 1 \
                and self.feature_dim not in (0, algebra.feature_dim):
            raise ValueError(
                f"plan.feature_dim={self.feature_dim} conflicts with "
                f"{algebra.name}'s native feature_dim "
                f"{algebra.feature_dim}; vector programs only run at "
                "their native width (use feature_dim=0 to adopt it)")
        if self.max_steps < 1:
            raise ValueError(
                f"plan.max_steps must be >= 1, got {self.max_steps}")
        if self.deadline_s is not None and not (
                isinstance(self.deadline_s, (int, float))
                and self.deadline_s > 0):
            raise ValueError(
                f"plan.deadline_s must be None or a positive number of "
                f"seconds, got {self.deadline_s!r}")
        if self.deadline_s is not None and (
                self.distributed or self.mesh is not None):
            raise ValueError(
                "plan.deadline_s is not supported on distributed plans: "
                "the shard_map fixpoint has no host-observable step "
                "boundary to enforce it at -- use max_steps")
        if not isinstance(self.tuned, bool):
            raise ValueError(
                f"plan.tuned must be a bool, got {self.tuned!r}")
        if self.tuned and (self.distributed or self.mesh is not None):
            raise ValueError(
                "plan.tuned is not supported on distributed plans: the "
                "tuning sweep measures local run_segment segments, "
                "which say nothing about shard_map dispatch -- tune a "
                "local plan, then add the mesh")
        if algebra is not None and self.warm == "always" \
                and algebra.kind != "monotone":
            raise ValueError(
                f"plan.warm='always' needs a monotone algebra; "
                f"{algebra.name} is {algebra.kind!r} (its fixpoint "
                "cannot resume from a prior result) -- use warm='auto' "
                "or 'never'")

    def resolve(self, algebra: VertexAlgebra | None = None) \
            -> "ExecutionPlan":
        """Validate and collapse every 'auto' to its concrete choice:
        relax_mode picks the backend kernel, compact follows the fabric
        mode, and a supplied mesh implies distributed execution. The
        returned plan is a complete record of how queries will run (and
        resolving it again is the identity)."""
        self.validate(algebra)
        relax = resolve_relax_mode(self.relax_mode)
        if relax == "pallas" and jax.default_backend() != "tpu":
            raise ValueError(
                "plan.relax_mode='pallas' needs a TPU backend, but "
                f"jax.default_backend() is {jax.default_backend()!r}; "
                "use 'interpret' (exact, slow) or 'jnp'")
        compact = (self.mode == "data" if self.compact == "auto"
                   else bool(self.compact))
        d = self.feature_dim
        if d == 0:
            d = algebra.feature_dim if algebra is not None else 1
        plan = dataclasses.replace(
            self, relax_mode=relax, compact=compact, feature_dim=d,
            distributed=bool(self.distributed or self.mesh is not None))
        plan.validate(algebra)
        return plan

    def place(self, keys: BlockKeys,
              algebra: VertexAlgebra | None = None) -> "ExecutionPlan":
        """This resolved plan with the layout of `keys` placed: an
        explicit distributed plan or mesh keeps the sharded layout (over
        all local devices when no mesh is given); otherwise the layout
        stays on one device where it fits its memory, and is sharded
        over all local devices where only that fits (`layout_devices`).
        Decided from the key count alone, before anything is allocated;
        the returned plan records the mesh."""
        devices = jax.devices()
        if not self.distributed and layout_devices(
                keys.device_bytes(), keys.shard_bytes(len(devices)),
                device_bytes_limit(), len(devices)) == 1:
            return self
        mesh = (self.mesh if self.mesh is not None
                else Mesh(np.array(devices), (self.mesh_axis,)))
        plan = dataclasses.replace(self, distributed=True, mesh=mesh)
        plan.validate(algebra)
        return plan

    def key(self) -> tuple:
        """Hashable cache key (session caches key on fingerprint+plan).
        The mesh participates by its devices and axis names, so one
        session's key never changes between calls."""
        return (self.mode, self.relax_mode, self.compact, self.tile,
                self.batch, self.distributed,
                None if self.mesh is None
                else mesh_key(self.mesh, self.mesh_axis),
                self.mesh_axis, self.warm, self.feature_dim,
                self.max_steps, self.deadline_s, self.tuned)


def device_bytes_limit() -> int | None:
    """The first device's memory limit (`memory_stats()["bytes_limit"]`),
    or None where the backend reports none (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def layout_devices(local_bytes: int, shard_bytes: int, limit: int | None,
                   ndev: int) -> int:
    """How many devices the default plan lays a graph's blocks over:
    1 where the one-device layout (`local_bytes`, both block copies)
    fits `limit` or no limit is known; `ndev` where it does not, more
    devices exist, and the fullest device of the sharded layout
    (`shard_bytes`) fits. Raises ValueError naming the sizes where
    neither fits."""
    if limit is None or local_bytes <= limit:
        return 1
    if ndev > 1 and shard_bytes <= limit:
        return ndev
    raise ValueError(
        f"the graph's block layout does not fit: {local_bytes:,} B on "
        f"one device" + (f", {shard_bytes:,} B on the fullest of {ndev} "
                         "devices sharded" if ndev > 1 else "")
        + f", against {limit:,} B of device memory")


# ------------------------------------------------------------------ #
# CLI spelling resolution (graph_run and friends)
# ------------------------------------------------------------------ #
def resolve_cli_engine(engine: str, mode: str) -> tuple[str, str]:
    """Collapse deprecated CLI spellings so every option has exactly one
    canonical form. ``--engine op`` is the pre-split spelling of
    ``--engine jax --mode op``: still accepted, warns once (the default
    warning filter deduplicates repeats)."""
    if engine == "op":
        warnings.warn(
            "--engine op is deprecated; use --engine jax --mode op",
            DeprecationWarning, stacklevel=2)
        return "jax", "op"
    return engine, mode


def plan_from_cli(engine: str, mode: str, compact: bool | str = "auto",
                  tile: int = 128, batch: int = 0,
                  feature_dim: int = 0) -> ExecutionPlan:
    """One ExecutionPlan from the graph_run-style CLI surface: folds the
    deprecated ``--engine op`` alias, maps ``--engine dist`` to a
    distributed plan, and threads the remaining knobs through unchanged.
    The 'sim' engine is still not an ExecutionPlan backend -- the cycle
    simulator runs its own mapped-fabric model -- though its cost
    vocabulary does reach plans indirectly, as the analytic bridge of
    the plan autotuner (`repro.autotune.measure`)."""
    engine, mode = resolve_cli_engine(engine, mode)
    if engine not in ("jax", "dist"):
        raise ValueError(
            f"engine {engine!r} has no ExecutionPlan (expected 'jax' or "
            "'dist'; 'sim' runs the cycle-accurate fabric simulator, "
            "which informs plan choice only through the autotuner's "
            "analytic cost bridge, not as an engine backend)")
    return ExecutionPlan(mode=mode, compact=compact, tile=tile,
                         batch=batch, distributed=(engine == "dist"),
                         feature_dim=feature_dim)
