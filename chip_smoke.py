#!/usr/bin/env python3
"""Smoke run of the graph-query path on a TPU.

Drives the main path once through the entry points a user calls --
`flip.compile(...).query`, `GraphServer` and `AsyncGraphServer` -- and
checks every answer against the numpy oracles. Phases, in order:

  device       refuse to run unless JAX's first device is a TPU
  algebras     each scalar algebra on ExtLRN (16,384 vertices): a solo, a
               B=8 and an op-mode query; multi_bfs and labelprop (d=8)
  incremental  one monotone update batch: the warm query must equal a
               query from scratch bit for bit
  serving      32 mixed requests with 2 update batches through both
               servers: nothing fails, is shed, or falls back
  road_1m      pagerank and bfs on a 2^20-vertex road network (the scale
               of SNAP roadNet-PA)

Each phase prints one JSON line (name, wall and compile seconds, the
resolved plan); the last line is ``{"ok": true, "device": {...}}``. Any
failure raises and exits non-zero: no phase is retried or skipped, and
nothing falls back to the CPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the distributed fixpoint over a
                                        # 4-chip mesh, vs one chip

The compile cache goes to $JAX_COMPILATION_CACHE_DIR when that is set,
else to <repo>/.jax_cache (`repro.launch.compile_cache`).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
ROAD_N = 1 << 20
ROAD_BFS_SRC = 524_800   # grid centre (row 512): eccentricity 1024 hops
# bfs/sssp step budget of the 4-chip comparison on the road network: the
# one-chip B=8 reference would otherwise take ~1000 steps
ROAD_FOUR_CHIP_STEPS = 128
SCALAR = ("bfs", "sssp", "wcc", "pagerank", "widest", "reach")
VECTOR = ("multi_bfs", "labelprop")
# jaxpr tracing, lowering to MLIR, and the backend compile (or, on a
# persistent-cache hit, the cache read): together, a program's compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def expect(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def tpu_device():
    """JAX's first device, or exit non-zero if it is not a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX's first device is "
            f"{dev.platform}: {dev.device_kind}); this script measures "
            "the chip and never runs on the CPU")
    return dev


class Phases:
    """Prints one JSON line per phase: wall seconds, compile seconds (the
    union of the time spans JAX reports for tracing, lowering and
    compiling inside the phase: nested jits overlap), and whatever the
    phase adds."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def compile_s(self, since: float) -> float:
        """Compile seconds reported since `since` (a `time.time()`)."""
        total, reach = 0.0, since
        for start, end in sorted(self.spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    @contextlib.contextmanager
    def __call__(self, name: str, **fields):
        rec = {"phase": name}
        rec.update(fields)
        since, t0 = time.time(), time.perf_counter()
        yield rec
        rec["wall_s"] = time.perf_counter() - t0
        rec["compile_s"] = self.compile_s(since)
        print(json.dumps(rec, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, np.generic):
        return x.item()
    raise TypeError(f"not JSON-serialisable: {type(x).__name__}")


def plan_of(plan) -> dict:
    return {"mode": plan.mode, "relax_mode": plan.relax_mode,
            "compact": plan.compact, "tile": plan.tile,
            "batch": plan.batch, "feature_dim": plan.feature_dim,
            "distributed": plan.distributed}


def expect_plan(plan, *, relax_mode="pallas", compact=True) -> dict:
    expect(plan.relax_mode == relax_mode,
           f"resolved relax_mode {plan.relax_mode!r}, expected "
           f"{relax_mode!r}")
    expect(plan.compact is compact,
           f"resolved compact {plan.compact!r}, expected {compact!r}")
    return plan_of(plan)


def checked(res, what: str):
    """`res` after its oracle check (which raises on a non-converged
    fixpoint and returns False on a wrong answer)."""
    expect(res.check(), f"{what}: result differs from the numpy oracle")
    return res


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ #
# one-chip phases
# ------------------------------------------------------------------ #
def algebras_phase(phase, flip, g, srcs) -> None:
    for algo in SCALAR + VECTOR:
        with phase(f"algebras/{algo}") as rec:
            cq = flip.compile(g, algo)
            rec["plan"] = expect_plan(cq.plan)
            solo = checked(cq.query(int(srcs[0])), f"{algo} solo")
            batch = checked(cq.query(srcs), f"{algo} B={len(srcs)}")
            expect(np.array_equal(batch.attrs[0], solo.attrs)
                   and batch.steps[0] == solo.steps,
                   f"{algo}: batch row 0 differs from the solo query")
            rec["steps_solo"] = solo.steps
            rec["steps_batch"] = batch.steps
            if algo in VECTOR:
                continue
            op = flip.compile(g, algo, flip.ExecutionPlan(mode="op"))
            rec["plan_op"] = expect_plan(op.plan, compact=False)
            rec["steps_op"] = checked(op.query(int(srcs[0])),
                                      f"{algo} op mode").steps


def incremental_phase(phase, flip, g, srcs) -> None:
    with phase("incremental") as rec:
        cq = flip.compile(g, "sssp")
        rec["plan"] = expect_plan(cq.plan)
        prev = cq.query(srcs)
        # halve 16 random edge weights: every change ⊕-improves min-plus
        rng = np.random.default_rng(SEED)
        eu = g.edge_sources()
        idx = rng.choice(g.m, size=16, replace=False)
        batch = [(int(eu[i]), int(g.indices[i]), float(g.weights[i]) / 2)
                 for i in idx]
        cq2, delta = cq.update(batch)
        expect(delta.monotone, "a weight-halving batch must be monotone")
        warm = cq2.query(srcs, warm=prev)
        scratch = checked(cq2.query(srcs), "sssp after the update")
        expect(np.array_equal(warm.attrs, scratch.attrs),
               "warm-started result differs from the scratch result")
        rec["steps_warm"] = warm.steps
        rec["steps_scratch"] = scratch.steps


def serving_phase(phase, flip, g) -> None:
    from repro.launch.serve_graph import (GraphServer, demo_stream,
                                          oracle_check)
    from repro.serving import AsyncGraphServer
    algos = ["bfs", "sssp", "pagerank"]
    stream, snapshots = demo_stream(g, algos, 32, 2,
                                    np.random.default_rng(SEED))
    plan = flip.ExecutionPlan(batch=8)
    served = {}
    for name, server in (("bucket", GraphServer(g, plan=plan)),
                         ("continuous", AsyncGraphServer(g, plan=plan))):
        with phase(f"serving/{name}") as rec:
            reqs = server.serve(stream)
            rec["plan"] = expect_plan(server.session(algos[0]).plan)
            rec["requests"] = len(reqs)
            rec["failed"] = server.failed
            rec["shed"] = server.shed
            rec["fallbacks"] = server.metrics.sum_counters("fallback.")
            rec["updates"] = server.updates_applied
            expect(all(r.ok for r in reqs) and server.failed == 0,
                   f"{name} server: {server.failed} requests failed")
            expect(server.shed == 0, f"{name} server shed requests")
            expect(rec["fallbacks"] == 0,
                   f"{name} server fell back down its degradation ladder")
            n_checked, wrong = oracle_check(reqs, snapshots)
            expect(n_checked == len(reqs) and wrong == 0,
                   f"{name} server: {wrong} of {n_checked} responses "
                   "differ from the oracle")
            served[name] = reqs
    for a, b in zip(served["bucket"], served["continuous"]):
        expect(np.array_equal(a.result, b.result) and a.steps == b.steps,
               f"request {a.req_id}: the two servers disagree")


def road_phase(phase, flip) -> None:
    from repro.graphs import make_road_network
    with phase("road_1m/build") as rec:
        g = make_road_network(ROAD_N, seed=SEED, delete_frac=0.56)
        rec["vertices"], rec["half_edges"] = g.n, g.m
    for algo, src in (("pagerank", 0), ("bfs", ROAD_BFS_SRC)):
        with phase(f"road_1m/{algo}") as rec:
            t0 = time.perf_counter()
            cq = flip.compile(g, algo)
            rec["session_build_s"] = time.perf_counter() - t0
            rec["plan"] = expect_plan(cq.plan)
            rec["blocks"] = int(cq.engine.bg.bsrc.shape[0])
            since = time.time()
            res = checked(cq.query(src), f"{algo} on the road network")
            rec["src"], rec["steps"] = src, res.steps
            rec["query_wall_s"] = res.wall_s
            rec["query_compile_s"] = phase.compile_s(since)
            rec["s_per_step"] = ((res.wall_s - rec["query_compile_s"])
                                 / max(res.steps, 1))
            rec["peak_bytes_in_use"] = peak_bytes()
            del cq, res
            gc.collect()


# ------------------------------------------------------------------ #
# four chips: the distributed fixpoint against one chip
# ------------------------------------------------------------------ #
def four_chip_phase(phase, flip, graphs) -> None:
    """For each (name, graph, sources, step budget): bfs, sssp and
    pagerank on one chip, then the distributed plan over a 4-chip mesh;
    steps and results must be equal bit for bit. A budget (None = run to
    the fixpoint) caps the one-chip reference's time: both runs stop at
    the same step and are compared there."""
    from jax.sharding import Mesh
    devs = jax.devices()
    expect(len(devs) >= 4, f"--four-chips needs 4 devices, found "
                           f"{len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    for name, g, srcs, budget in graphs:
        for algo in ("bfs", "sssp", "pagerank"):
            steps = None if algo == "pagerank" else budget
            with phase(f"four_chips/{name}/{algo}", max_steps=steps) as rec:
                one = flip.compile(g, algo)
                rec["plan_one_chip"] = expect_plan(one.plan)
                ref = one.query(srcs, max_steps=steps)
                rec["one_chip_wall_s"] = ref.wall_s
                del one
                gc.collect()
                dist = flip.compile(g, algo, flip.ExecutionPlan(mesh=mesh))
                rec["plan"] = plan_of(dist.plan)
                expect(dist.plan.distributed, "plan is not distributed")
                got = dist.query(srcs, max_steps=steps)
                rec["four_chip_wall_s"] = got.wall_s
                rec["steps"] = got.steps
                rec["converged"] = got.converged
                expect(np.array_equal(got.steps, ref.steps)
                       and np.array_equal(got.attrs, ref.attrs),
                       f"{name}/{algo}: 4-chip result differs from the "
                       "one-chip result")
                del dist, ref, got
                gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed fixpoint over a 4-chip "
                         "mesh and the one-chip results it must equal")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        raise SystemExit(f"chip_smoke: {REPO} is not a checkout of this "
                         "repository (src/repro is missing)")
    sys.path.insert(0, os.path.join(REPO, "src"))
    dev = tpu_device()

    from repro import api as flip
    from repro.graphs import make_dataset
    from repro.launch.compile_cache import enable_compile_cache

    phase = Phases()
    with phase("device") as rec:
        rec["platform"], rec["kind"] = dev.platform, dev.device_kind
        rec["count"], rec["jax"] = len(jax.devices()), jax.__version__
        rec["compile_cache"] = enable_compile_cache()
    g = next(make_dataset("ExtLRN", 1, seed0=SEED))
    srcs = np.random.default_rng(SEED).choice(g.n, size=8, replace=False)
    if args.four_chips:
        from repro.graphs import make_road_network
        road = make_road_network(ROAD_N, seed=SEED, delete_frac=0.56)
        near_centre = ROAD_BFS_SRC + np.arange(8) * 3
        four_chip_phase(phase, flip, (
            ("extlrn", g, srcs, None),
            ("road_1m", road, near_centre, ROAD_FOUR_CHIP_STEPS)))
    else:
        algebras_phase(phase, flip, g, srcs)
        incremental_phase(phase, flip, g, srcs)
        serving_phase(phase, flip, g)
        road_phase(phase, flip)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
