"""Kernel micro-benchmarks (CPU wall time of the jnp paths + interpret
correctness cost; on TPU these dispatch to the Pallas kernels).

Emits the per-algebra frontier-relax rows future PRs track, a batched
(B, ntiles, T) relax row, the feature-width d-sweep
(`bench_features`), and the end-to-end multi-query batching win:
B=32 BFS sources on an LRN road network through one batched
`CompiledQuery.query` fixpoint vs 32 sequential scalar queries on the
same compiled session. Results append to
BENCH_kernels.json via `common.write_json` -- written even when a bench
section fails, so the perf trajectory never silently loses a run.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import bench_features, bench_incremental
from benchmarks.common import RESULTS, emit, timed, write_json
from repro import api as flip
from repro.algebra import ALGEBRAS
from repro.graphs import make_dataset, make_road_network
from repro.kernels.frontier import build_blocks, frontier_relax
from repro.models.attention import attend
from repro.kernels.ssd.ref import ssd_ref


def run():
    fast = bool(os.environ.get("BENCH_FAST"))
    # frontier relax step (jnp path), one timing per registered algebra:
    # future PRs read these rows to track the per-semiring perf trajectory
    # row ids carry the graph size: BENCH_FAST runs a 256-vertex graph,
    # full runs the historical 1k one, and the two must never be compared
    # under one name in the recorded trajectory
    n = 256 if fast else 1024
    size = "256" if fast else "1k"
    g = make_road_network(n, seed=0)
    rng = np.random.default_rng(0)
    bgs = {}
    for algo in sorted(ALGEBRAS):
        if ALGEBRAS[algo].feature_dim != 1:
            continue   # vector programs: bench_features owns the d-sweep
        bg = bgs[algo] = build_blocks(g, algo, tile=128)
        alg = bg.algebra
        vals = (alg.initial_attrs(g.n, 0) if alg.kind == "residual"
                else rng.uniform(0, 10, g.n).astype(np.float32))
        attrs = bg.to_tiled(vals)   # generic mid-run state
        f = jax.jit(lambda s, a, bg=bg: frontier_relax(s, a, bg,
                                                       mode="jnp"))
        f(attrs, attrs).block_until_ready()
        _, us = timed(lambda: f(attrs, attrs).block_until_ready(),
                      repeats=20)
        emit(f"kernel_frontier_relax_{size}_{algo}", us,
             f"semiring={alg.semiring.name} edges={g.m} "
             f"blocks={bg.blocks.shape[0]}")

    # batched relax: B=32 queries against the same resident block stream
    bg = bgs["bfs"]
    batt = bg.to_tiled(rng.uniform(0, 10, (32, g.n)).astype(np.float32))
    fb = jax.jit(lambda s, a: frontier_relax(s, a, bg, mode="jnp"))
    fb(batt, batt).block_until_ready()
    _, us = timed(lambda: fb(batt, batt).block_until_ready(), repeats=20)
    emit(f"kernel_frontier_relax_{size}_bfs_b32", us,
         f"batched B=32 edges={g.m} blocks={bg.blocks.shape[0]}")

    # feature-width (d) sweep: vector-state amortization of the weight
    # stream (matmul contraction vs sequential scalar steps)
    bench_features.run(fast)

    # incremental-vs-scratch recompute after a streaming update batch
    bench_incremental.run(fast)

    bench_batching_win(fast)

    # attention (lax_flash path)
    q = jnp.ones((1, 2048, 4, 64), jnp.float32)
    k = jnp.ones((1, 2048, 2, 64), jnp.float32)
    fa = jax.jit(lambda q, k: attend(q, k, k, True, None,
                                     impl="lax_flash"))
    fa(q, k).block_until_ready()
    _, us = timed(lambda: fa(q, k).block_until_ready(), repeats=3)
    emit("kernel_attention_2k", us, "causal flash, S=2048")

    # SSD chunked scan
    x = jnp.ones((1, 1024, 4, 32), jnp.float32)
    dt = jnp.full((1, 1024, 4), 0.1, jnp.float32)
    bm = jnp.ones((1, 1024, 16), jnp.float32)
    al = jnp.zeros((4,), jnp.float32)
    d = jnp.zeros((4,), jnp.float32)
    fs = jax.jit(lambda x, dt, bm: ssd_ref(x, dt, bm, bm, al, d,
                                           chunk=128)[0])
    fs(x, dt, bm).block_until_ready()
    _, us = timed(lambda: fs(x, dt, bm).block_until_ready(), repeats=5)
    emit("kernel_ssd_1k", us, "chunk=128")


def bench_batching_win(fast: bool):
    """End-to-end multi-query amortization: B=32 BFS sources on the LRN
    dataset, one shared batched fixpoint vs 32 sequential scalar queries
    (same compiled session, same jit cache, same backend)."""
    g = next(make_dataset("LRN", 1, seed0=0))
    rng = np.random.default_rng(0)
    srcs = rng.choice(g.n, size=32, replace=False)
    cq = flip.compile(g, "bfs", flip.ExecutionPlan(tile=128))
    r_solo = cq.query(int(srcs[0]))            # warm the solo executable
    r_bat = cq.query(srcs)                     # warm the batched one
    _, us_seq = timed(lambda: [cq.query(int(s)) for s in srcs],
                      repeats=1 if fast else 3)
    _, us_bat = timed(lambda: cq.query(srcs),
                      repeats=1 if fast else 3)
    emit("frontier_bfs_lrn_seq32", us_seq,
         f"32 sequential scalar queries |V|={g.n}")
    emit("frontier_bfs_lrn_batch32", us_bat,
         "one batched query fixpoint, B=32")
    emit("frontier_bfs_lrn_batch32_speedup", us_seq / us_bat,
         "sequential/batched wall ratio (x, higher is better)")
    # compile-vs-steady split (satellite): the warm-up calls above were
    # the first dispatches of their shapes, so their compile_s is the
    # jit-trace share a cold server pays once per executable
    emit("frontier_bfs_lrn_compile_solo", r_solo.compile_s * 1e6,
         "first solo dispatch compile share (jit trace + lowering)")
    emit("frontier_bfs_lrn_compile_batch32", r_bat.compile_s * 1e6,
         "first B=32 dispatch compile share")
    # telemetry summary rows: traced re-run of the batched fixpoint
    # (tracing compiles its own executable; results stay bit-identical)
    rt = cq.query(srcs, trace=True)
    s = rt.telemetry.summary()
    emit("frontier_bfs_lrn_batch32_active_tile_frac",
         s["mean_active_tile_fraction"] * 100,
         f"mean % of tiles live per step over {s['traced_steps']} "
         f"traced steps")
    emit("frontier_bfs_lrn_batch32_blocks_fetched",
         s["blocks_fetched_total"],
         f"HBM block fetches (skipped={s['blocks_skipped_total']}); "
         f"steps hist {rt.telemetry.steps_histogram()}")


def main():
    start = len(RESULTS)
    try:
        run()
    finally:
        # always persist this module's rows (even partial ones on a bench
        # failure): BENCH_kernels.json is the recorded perf trajectory
        write_json("kernels", rows=RESULTS[start:])


if __name__ == "__main__":
    main()
