"""Telemetry-overhead guard: tracing must stay cheap enough to leave on.

Times steady-state (post-compile) BFS queries with ``trace=`` off vs on
through the one fixpoint program, `jit_flip_fixpoint`, under the
default plan, and fails (exit 1) when the traced/untraced wall ratio
exceeds ``--max-ratio`` (default 1.10, the documented <=10% bound). The graph is sized so the
relax work dominates the fixed-shape stat-buffer writes; medians over
several repeats keep the ratio robust to scheduler noise. Rows append
to BENCH_telemetry.json, so the overhead trajectory is recorded
alongside the kernel benches.

CI runs this as the `telemetry-overhead-smoke` job:

  BENCH_FAST=1 PYTHONPATH=src:. python -m \
      benchmarks.bench_telemetry_overhead --max-ratio 1.10
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from benchmarks.common import RESULTS, emit, write_json
from repro import api as flip
from repro.graphs import make_power_law


def _steady(fn, repeats: int) -> float:
    """Median wall of `repeats` calls (the executable is already warm)."""
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls))


def run(max_ratio: float = 1.10) -> float:
    """Benches the fixpoint program; returns the traced/untraced ratio."""
    fast = bool(os.environ.get("BENCH_FAST"))
    n, m = (1024, 4096) if fast else (4096, 16384)
    repeats = 7 if fast else 11
    g = make_power_law(n, m, seed=0)
    cq = flip.compile(g, "bfs", flip.ExecutionPlan())
    cq.query(0)                     # warm the untraced executable
    cq.query(0, trace=True)         # warm the traced one
    off = _steady(lambda: cq.query(0), repeats)
    on = _steady(lambda: cq.query(0, trace=True), repeats)
    ratio = on / off
    emit("telemetry_overhead_fixpoint_off", off * 1e6,
         f"steady-state BFS |V|={n} |E|={g.m}, trace off")
    emit("telemetry_overhead_fixpoint_on", on * 1e6, "trace=True")
    emit("telemetry_overhead_fixpoint_ratio", ratio,
         f"traced/untraced wall (guard <= {max_ratio:.2f})")
    return ratio


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-ratio", type=float, default=1.10,
                    help="fail when traced/untraced steady-state wall "
                         "exceeds this")
    args = ap.parse_args()
    start = len(RESULTS)
    ratio = None
    try:
        ratio = run(args.max_ratio)
    finally:
        write_json("telemetry", rows=RESULTS[start:])
    print(f"[bench] tracing overhead ratio {ratio:.3f} "
          f"(bound {args.max_ratio:.2f})")
    if ratio > args.max_ratio:
        raise SystemExit(
            f"telemetry overhead {ratio:.3f}x exceeds the "
            f"{args.max_ratio:.2f}x bound")


if __name__ == "__main__":
    main()
