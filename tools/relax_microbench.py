#!/usr/bin/env python
"""Microseconds a slot of the grouped relax kernel on synthetic streams.

    python tools/relax_microbench.py                      # on a TPU
    python tools/relax_microbench.py --json relax_microbench.jsonl
    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/relax_microbench.py \
        --interpret --slots 64 --blocks 16 --tiles 16 --runs 1,4 --reps 1

Times `frontier._relax_grouped` (min-plus, every row active, every slot
below `n_active`) over a stream of --slots slots in which each source
tile owns a run of R consecutive slots, for each R of --runs: the shape
the source walk gives a Graph500 stream (~125-256 slots a source tile),
and R = 1, the destination walk's. Each slot copies a distinct 64 KiB
weight block (the stream cycles over --blocks of them). Bodies: 'row'
at B = 1; at B = 8 'runs', the source-run body the kernel takes, and
'rows8', the per-slot transpose-and-broadcast body it replaced (run by
switching `source_runs` off). --chains also times the source-run body
at other CHAINS values.

Prints one JSON line per (B, body, chains, R) with the best of --repeat
timings in microseconds a slot; off a TPU it refuses to run unless
--interpret is given, and then prints no times.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", default="1,8")
    ap.add_argument("--runs", default="1,32,125,256",
                    help="slots per source-tile run")
    ap.add_argument("--chains", default="",
                    help="extra CHAINS values for the source-run body")
    ap.add_argument("--slots", type=int, default=65536)
    ap.add_argument("--blocks", type=int, default=8192,
                    help="distinct weight blocks the stream cycles over")
    ap.add_argument("--tiles", type=int, default=256,
                    help="tiles of the (B, tiles, 128) state")
    ap.add_argument("--reps", type=int, default=4,
                    help="kernel calls per timing")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--interpret", action="store_true",
                    help="run the TPU interpreter on the CPU (no times)")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="also append the lines to FILE")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from repro.algebra import MIN_PLUS
    from repro.kernels.frontier import frontier as F

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        print(f"relax_microbench: no TPU found ({dev.platform}: "
              f"{dev.device_kind}); pass --interpret to rehearse",
              file=sys.stderr)
        return 1
    interpret = pltpu.InterpretParams() if args.interpret else False
    t, ns, nslots = 128, args.tiles, args.slots
    rng = np.random.default_rng(0)
    blocks = jnp.asarray(rng.random((args.blocks, t, t), np.float32))
    bsel = jnp.asarray(np.arange(nslots) % args.blocks, jnp.int32)
    bdst = jnp.asarray(np.arange(nslots) * 97 % ns, jnp.int32)
    real_runs, real_chains = F.source_runs, F.CHAINS

    def timed(batch, run, body, chains):
        F.source_runs = (real_runs if body != "rows8"
                         else lambda b, sr: False)
        F.CHAINS = chains
        F._make_grouped_kernel.cache_clear()
        bsrc = jnp.asarray(np.arange(nslots) // run % ns, jnp.int32)
        sv = jnp.asarray(rng.random((batch, ns, t), np.float32))
        carry = jnp.asarray(rng.random((batch, ns, t), np.float32) + 1)

        # the arrays are arguments: closed over, they would be compiled
        # into the program as constants
        @jax.jit
        def calls(sv, carry, blocks, bsrc, bdst, bsel):
            def one(_, c):
                return F._relax_grouped(sv, c, blocks, bsrc, bdst, bsel,
                                        nslots, MIN_PLUS, interpret)
            return jax.lax.fori_loop(0, args.reps, one, carry)

        operands = (sv, carry, blocks, bsrc, bdst, bsel)
        calls(*operands).block_until_ready()       # compile, warm up
        best = None
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            calls(*operands).block_until_ready()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best / (args.reps * nslots) * 1e6

    cases = []
    for batch in (int(b) for b in args.batches.split(",")):
        bodies = ([("row", real_chains)] if batch % F.SLAB else
                  [("rows8", real_chains), ("runs", real_chains)]
                  + [("runs", int(c)) for c in args.chains.split(",")
                     if c and int(c) != real_chains])
        cases += [(batch, body, chains, int(run)) for body, chains in bodies
                  for run in args.runs.split(",")]
    out = open(args.json, "a") if args.json else None
    try:
        for batch, body, chains, run in cases:
            us = timed(batch, run, body, chains)
            rec = {"batch": batch, "body": body, "chains": chains,
                   "run": run, "slots": nslots,
                   "us_per_slot": None if args.interpret else us,
                   "device": f"{dev.platform}: {dev.device_kind}"}
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        F.source_runs, F.CHAINS = real_runs, real_chains
        F._make_grouped_kernel.cache_clear()
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    sys.exit(main())
