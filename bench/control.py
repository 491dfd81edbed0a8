"""Readings that set the limits of `correct`, at a cell's own size.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 5

For each of `--seeds`, one short run of the program (the window's calls,
compared with the reference as a benchmark run compares them); for each
of `--control-seeds`, the same with the reference one precision below
the configuration's put in the program's place. One process for all, so
set-up that compiles is paid once. Prints one JSON line per run with the
numbers compared; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from bench import run, spec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    plan = [(int(s), False) for s in args.seeds.split(",") if s]
    plan += [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in plan:
        r = run.run_cell(args.workload, seed, args.seconds, False,
                         control=control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": control, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "failed": r["failed"], "check": r["check"]}),
              flush=True)


if __name__ == "__main__":
    main()
