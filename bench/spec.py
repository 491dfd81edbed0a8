"""What `BENCHMARK.json` names, found by name under the benchmark root.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, so a later change adds a cell by adding files and
entries, never by editing one that is there:

    bench/configs/<config>.json    generator, parameters, source, cuts
    bench/traffic/<mix>.json       program, batch, how roots are drawn
    bench/metrics/<metric>.py      read(window) -> number or None
    bench/reference/<program>.py   plain reference, comparison, limits
    bench/graphs/<generator>.py    seeded generator
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    """One workload of `BENCHMARK.json` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # the cell's end-to-end metric entries
    per_layer: list         # the cell's per-layer metric entries


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    """The workload `name` of `<root>/BENCHMARK.json`."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{w['traffic']}.json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str, root: str = ROOT):
    """`read(window)` of `<root>/bench/metrics/<name>.py`."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def control_dtype(precision: str):
    """The precision one step below a configuration's: the control's."""
    import ml_dtypes
    below = {"float64": np.float32, "float32": ml_dtypes.bfloat16}
    return below[precision]


def program_reference(program: str):
    """`bench.reference.<program>`: the plain reference of a program, the
    numbers compared with it, and their limits."""
    if not program.replace("_", "").isalnum():
        raise ValueError(f"bad program name {program!r}")
    return importlib.import_module(f"bench.reference.{program}")
