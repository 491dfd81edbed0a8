"""Benchmark harness entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  Fig. 10 (speedup/energy)      -> bench_speedup
  Fig. 11 + Fig. 4 (parallelism)-> bench_parallelism
  Fig. 12 + Sec 5.2.5 (scaling) -> bench_scaling
  Fig. 13 + Table 7 (compiler)  -> bench_compile_time
  Table 8 (mapping quality)     -> bench_mapping_quality
  kernels                       -> bench_kernels
  §Roofline (from dry-run JSON) -> roofline

Fast mode (default) uses reduced graph counts; FULL=1 uses paper-scale
counts (100 graphs/group).

Every invocation appends a run record to BENCH_all.json, and the kernel
section always appends its rows to BENCH_kernels.json (written from
`bench_kernels.main`'s finally-block, so a mid-bench failure still
records the partial run). A bench that raises does not stop the rest;
the harness names it and exits non-zero once the others have run.
"""
from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    from benchmarks import (bench_speedup, bench_parallelism,
                            bench_scaling, bench_compile_time,
                            bench_mapping_quality, bench_kernels,
                            bench_serving, bench_traffic_replay,
                            bench_features, bench_incremental,
                            bench_frontier_density,
                            bench_telemetry_overhead, bench_autotune)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = bool(os.environ.get("BENCH_FAST"))
    calls = [
        (bench_speedup, dict(graphs_per_group=1, sources_per_graph=1,
                             effort=0, skip=(("Syn", "wcc"),))
            if fast else {}),
        (bench_parallelism, dict(graphs_per_group=1, sources=2, effort=0,
                                 skip=(("Syn", "wcc"), ("Syn", "sssp")))
            if fast else {}),
        (bench_scaling, {}),
        (bench_compile_time, {}),
        (bench_mapping_quality, dict(graphs_per_group=1, sources=1)
            if fast else {}),
        (bench_kernels, {}),
        # overhead gate disabled here (inf): the aggregate run records
        # the ratio; the dedicated CI job enforces the <=1.05 bound
        (bench_serving, dict(max_overhead=float("inf"))),
        # speedup gate disabled here (0): recorded only; the
        # serving-replay-smoke CI job enforces the >=1.5x bound
        (bench_traffic_replay, dict(min_speedup=0.0)),
        # kwargs are explicit (the dispatch below only routes to run()
        # on a non-empty kwargs dict); fast honors BENCH_FAST
        (bench_features, dict(fast=fast)),
        (bench_incremental, dict(fast=fast)),
        (bench_frontier_density, dict(fast=fast)),
        # overhead gate disabled here (inf): recorded only; the
        # telemetry-overhead CI job enforces the bound
        (bench_telemetry_overhead, dict(max_ratio=float("inf"))),
        # tuned-vs-default/worst gates disabled here (0): recorded
        # only; the autotune-smoke CI job enforces the bounds
        (bench_autotune, dict(min_vs_default=0.0, min_vs_worst=0.0)),
    ]
    failed = []
    for m, kw in calls:
        try:
            if kw and hasattr(m, "run"):
                m.run(**kw)
            else:
                m.main()
        except Exception:
            print(f"[bench] {m.__name__} FAILED", file=sys.stderr)
            traceback.print_exc()
            failed.append(m.__name__)
    # roofline table only if dry-run results exist
    try:
        from benchmarks import roofline
        if roofline.load_cells():
            roofline.main()
    except Exception:
        traceback.print_exc()
        failed.append("roofline")
    from benchmarks.common import write_json
    write_json("all")
    if failed:
        raise SystemExit(f"[bench] {len(failed)} bench(es) failed: "
                         f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
