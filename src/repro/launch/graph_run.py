"""FLIP graph-workload launcher: the paper's own application path.

Runs any registered algebra (BFS / SSSP / WCC / PageRank / widest-path /
reachability) on a Table-4 dataset through any of the three execution
layers, in either fabric mode:

  --engine sim     cycle-accurate FLIP simulator (paper evaluation vehicle)
  --engine jax     TPU-native frontier engine (single device)
  --engine dist    shard_map frontier engine over all local devices
  --mode data|op   FLIP packet-triggered vs classic-CGRA full-sweep
                   (jax/dist engines; the simulator is data-centric only)

The jax/dist engines run through the unified query API: the CLI flags
fold into one `flip.ExecutionPlan` (`plan_from_cli`, which also accepts
the deprecated ``--engine op`` spelling of ``--engine jax --mode op``
with a one-time warning), and every query goes through
`flip.compile(graph, algo, plan).query(...)`.

Multi-query serving: `--srcs 0,5,9` runs a batch of sources through one
shared fixpoint; `--batch B` additionally routes them through the
`serve_graph.GraphServer` dispatch path in fixed-size buckets of B.

Examples:
  PYTHONPATH=src python -m repro.launch.graph_run --algo sssp \
      --dataset LRN --engine sim --src 5
  PYTHONPATH=src python -m repro.launch.graph_run --algo bfs \
      --dataset LRN --engine jax --srcs 0,5,9,12 --mode op
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro import api as flip
from repro.core import (compile_mapping, simulate, PROGRAMS, baselines)
from repro.graphs import make_dataset, reference
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="bfs", choices=sorted(PROGRAMS))
    ap.add_argument("--dataset", default="LRN",
                    choices=["Tree", "SRN", "LRN", "Syn", "ExtLRN"])
    ap.add_argument("--engine", default="sim",
                    choices=["sim", "jax", "dist", "op"])
    ap.add_argument("--mode", default="data", choices=["data", "op"],
                    help="fabric mode for the jax/dist engines")
    ap.add_argument("--graph-seed", type=int, default=0)
    ap.add_argument("--src", type=int, default=0)
    ap.add_argument("--srcs", default=None,
                    help="comma list of sources: batched multi-query run "
                         "(jax/dist engines)")
    ap.add_argument("--batch", type=int, default=0,
                    help="with --srcs: dispatch through the serving "
                         "front-end in fixed-size buckets of this many "
                         "queries (0 = one fixpoint over all sources)")
    ap.add_argument("--compact", default="auto",
                    choices=["auto", "on", "off"],
                    help="frontier-compacted block streaming for the "
                         "jax/dist engines (auto = on for data mode)")
    ap.add_argument("--feature-dim", type=int, default=0,
                    help="feature width d of the vertex state: 0 adopts "
                         "the program's native width (1 for scalar "
                         "programs, 8 for multi_bfs/labelprop); d > 1 "
                         "on a scalar program runs d broadcast lanes. "
                         "jax/dist engines only")
    ap.add_argument("--updates", default=None, metavar="FILE",
                    help="JSON file of streaming edge mutations: a list "
                         "of [u, v, w] entries (w = null deletes, "
                         "omitted w inserts with weight 1) or a list of "
                         "such batches. Applied after the base query; "
                         "each batch is re-solved incrementally (warm "
                         "start when monotone under the algebra, full "
                         "recompute otherwise). jax/dist engines only.")
    ap.add_argument("--autotune", action="store_true",
                    help="let the plan autotuner pick the performance "
                         "knobs (tile / kernel / compaction / bucket) "
                         "for this graph, consulting the tuning store "
                         "(FLIP_AUTOTUNE_DB). jax engine only; "
                         "bit-exact with the untuned plan")
    ap.add_argument("--effort", type=int, default=1)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write a Chrome-trace JSON (chrome://tracing / "
                         "Perfetto) of the run: per-step frontier spans "
                         "for the jax engine, cycle-level parallelism "
                         "re-emitted through the same schema for sim")
    args = ap.parse_args()
    args.compact = {"auto": "auto", "on": True, "off": False}[args.compact]
    enable_compile_cache()

    # one plan resolution folds every deprecated CLI spelling
    # (--engine op -> --engine jax --mode op, warns once)
    args.engine, args.mode = flip.resolve_cli_engine(args.engine,
                                                     args.mode)
    srcs = ([int(s) for s in args.srcs.split(",")]
            if args.srcs else None)
    if srcs is not None and args.engine == "sim":
        raise SystemExit("--srcs needs --engine jax/dist (the cycle "
                         "simulator runs one query per sweep)")
    if args.batch and args.engine != "jax":
        raise SystemExit("--batch dispatches through the single-device "
                         "serving front-end; use it with --engine jax")
    if args.updates and (args.engine not in ("jax", "dist")
                         or srcs is not None):
        raise SystemExit("--updates replays mutations through the "
                         "incremental engines; use it with --engine "
                         "jax/dist and a single --src")
    if args.trace and args.engine == "dist":
        raise SystemExit("--trace needs --engine sim/jax (per-step "
                         "tracing is not supported on the distributed "
                         "fixpoint yet)")
    if args.trace and args.batch:
        raise SystemExit("--trace traces one query/fixpoint; drop "
                         "--batch (use serve_graph --stats for serving "
                         "telemetry)")
    if args.autotune and args.engine != "jax":
        raise SystemExit("--autotune tunes the single-device jax plan "
                         "(sim has no ExecutionPlan; the distributed "
                         "fixpoint is not tunable) -- use --engine jax")
    if args.engine == "sim" and (args.feature_dim > 1
                                 or PROGRAMS[args.algo].feature_dim > 1):
        raise SystemExit("--engine sim runs scalar vertex state only; "
                         "vector programs / --feature-dim > 1 need "
                         "--engine jax/dist")

    g = next(make_dataset(args.dataset, 1, seed0=args.graph_seed))
    print(f"[graph] {args.dataset}: |V|={g.n} |E|={g.m}")
    t0 = time.time()
    mapping = compile_mapping(g, effort=args.effort,
                              program=PROGRAMS[args.algo])
    print(f"[graph] FLIP compile {time.time() - t0:.2f}s  "
          f"avg routing length {mapping.avg_routing_length():.2f}")

    if srcs is not None:
        ok = _batched_run(args, g, mapping, srcs)
        print(f"[graph] correct vs reference: {ok}")
        raise SystemExit(0 if ok else 1)

    if args.engine == "sim":
        if not PROGRAMS[args.algo].sim_ok:
            raise SystemExit(
                f"--engine sim cannot run {args.algo} (non-idempotent "
                "merge); use --engine jax/dist")
        r = simulate(mapping, PROGRAMS[args.algo], src=args.src)
        attrs = r.attrs
        if args.trace:
            from repro.obs import from_sim, write_chrome_trace
            tele = from_sim(r, freq_mhz=mapping.arch.freq_mhz)
            write_chrome_trace(args.trace, tele,
                               name=f"sim:{args.algo}")
            print(f"[graph] trace: {len(tele.dispatches[0].trace)} "
                  f"cycle spans -> {args.trace}")
        mteps = g.m / (r.cycles / mapping.arch.freq_mhz)
        print(f"[graph] sim: {r.cycles} cycles "
              f"({r.cycles / mapping.arch.freq_mhz:.1f}us @100MHz), "
              f"parallelism avg={r.avg_parallelism:.1f} "
              f"max={r.max_parallelism}, {mteps:.0f} MTEPS, "
              f"pkt wait {r.avg_pkt_wait:.2f}cyc, swaps={r.swaps}")
        if args.algo in ("bfs", "sssp", "wcc"):   # calibrated baselines
            mcu = baselines.mcu_cycles(args.algo, g, args.src)
            cgra = baselines.cgra_cycles(args.algo, g, args.src)
            t_f = r.cycles / mapping.arch.freq_mhz
            print(f"[graph] speedup vs MCU {mcu.time_us / t_f:.1f}x, "
                  f"vs op-centric CGRA {cgra.time_us / t_f:.1f}x")
    else:
        plan = _cli_plan(args)
        cq = flip.compile(g, args.algo, plan, mapping=mapping)
        if cq.tune is not None:
            print(f"[graph] autotune"
                  f"{' (store hit)' if cq.tune.cached else ''}: "
                  f"{cq.tune.why}")
        t0 = time.time()
        res = cq.query(args.src, trace=bool(args.trace))
        attrs = res.attrs
        where = ("local device mesh" if plan.distributed
                 else f"{time.time() - t0:.2f}s wall")
        print(f"[graph] {args.engine}/{args.mode}: fixpoint in "
              f"{res.steps} relaxation steps ({where})")
        if args.trace:
            _write_trace(args.trace, res, args.algo)

        if args.updates:
            g, attrs = _replay_updates(args, g, cq, res)

    ref, _ = reference.run(args.algo, g, args.src)
    ok = PROGRAMS[args.algo].results_match(attrs, ref)
    print(f"[graph] correct vs reference: {ok}")
    raise SystemExit(0 if ok else 1)


def _cli_plan(args, **kw):
    """Fold the CLI knobs into one plan; --autotune sets the tuned flag
    so `flip.compile` routes through the plan autotuner."""
    plan = flip.plan_from_cli(args.engine, args.mode,
                              compact=args.compact,
                              feature_dim=args.feature_dim, **kw)
    if args.autotune:
        plan = dataclasses.replace(plan, tuned=True)
    return plan


def _write_trace(path, res, algo):
    """Write a traced QueryResult as Chrome-trace JSON and print the
    telemetry summary line."""
    from repro.obs import write_chrome_trace
    write_chrome_trace(path, res, name=f"query:{algo}")
    s = res.telemetry.summary()
    print(f"[graph] trace: {s['traced_steps']} step spans over "
          f"{s['dispatches']} dispatch(es), mean active-tile fraction "
          f"{s['mean_active_tile_fraction']:.3f}, compile "
          f"{res.compile_s:.2f}s -> {path}")


def _load_update_batches(path):
    """JSON `--updates` file: a single batch (list of [u, v, w?] entries,
    w = null deletes, omitted w = 1.0) or a list of such batches."""
    with open(path) as f:
        data = json.load(f)

    def is_update(e):
        return (isinstance(e, list) and 2 <= len(e) <= 3
                and all(isinstance(x, (int, float)) for x in e[:2])
                and (len(e) == 2 or e[2] is None
                     or isinstance(e[2], (int, float))))

    if not isinstance(data, list) or not data:
        raise SystemExit("--updates: JSON must be a non-empty list")
    if all(is_update(e) for e in data):        # one flat batch
        data = [data]
    elif not all(isinstance(b, list) and all(is_update(e) for e in b)
                 for b in data):
        raise SystemExit(
            "--updates: entries must be [u, v] / [u, v, w] / [u, v, null]"
            " triples, or a list of batches of them")
    return [[(int(e[0]), int(e[1]),
              (1.0 if len(e) < 3 else
               (None if e[2] is None else float(e[2]))))
             for e in batch] for batch in data]


def _replay_updates(args, g, cq, res):
    """Apply each update batch and re-solve incrementally: the session
    warm-starts from the previous fixpoint when the batch is monotone
    under the algebra, and falls back to a full recompute otherwise
    (the plan's warm='auto' policy) -- uniformly for jax and dist."""
    for i, batch in enumerate(_load_update_batches(args.updates)):
        t0 = time.time()
        cq, delta = cq.update(batch)
        res = cq.query(args.src, warm=res)
        print(f"[graph] update[{i}]: {len(batch)} edges -> "
              f"{delta.n_blocks_rebuilt} tiles rebuilt"
              f"{' (shape changed)' if delta.shape_changed else ''}, "
              f"{'warm' if delta.monotone else 'full'} recompute in "
              f"{res.steps} steps ({time.time() - t0:.2f}s, "
              f"{len(delta.affected_src)} vertices affected)")
    return cq.graph, res.attrs


def _batched_run(args, g, mapping, srcs) -> bool:
    """--srcs path: one batched fixpoint (or serving-bucket dispatch)."""
    t0 = time.time()
    if args.batch:
        from repro.launch.serve_graph import GraphServer
        plan = _cli_plan(args, batch=args.batch)
        srv = GraphServer(g, plan=plan, mapping=mapping)
        reqs = srv.serve((args.algo, s) for s in srcs)
        outs = [r.result for r in reqs]
        steps = [r.steps for r in reqs]
        how = (f"{srv.dispatches} serving dispatches of "
               f"B={args.batch}")
    else:
        plan = _cli_plan(args)
        res = flip.compile(g, args.algo, plan, mapping=mapping).query(
            np.asarray(srcs), trace=bool(args.trace))
        outs, steps = res.attrs, res.steps
        how = f"one {args.engine} batch of B={len(srcs)}"
        if args.trace:
            _write_trace(args.trace, res, args.algo)
    print(f"[graph] {args.engine}/{args.mode}: {len(srcs)} queries via "
          f"{how}, per-query steps {list(map(int, steps))} "
          f"({time.time() - t0:.2f}s wall)")
    ok = True
    for s, out in zip(srcs, outs):
        ref, _ = reference.run(args.algo, g, s)
        ok &= bool(PROGRAMS[args.algo].results_match(out, ref))
    return ok


if __name__ == "__main__":
    main()
