"""Shared helpers for the benchmark harness.

Every `emit` prints the historical ``name,us_per_call,derived`` CSV row
AND records it in an in-process results list; `write_json(tag)` appends
the rows collected so far as one run record to ``BENCH_<tag>.json``
(under ``$BENCH_OUT`` if set, else the cwd). The file is append-safe --
each invocation adds a ``{"ts", "meta", "rows"}`` entry to the ``runs``
list instead of overwriting history -- so repo-root files and CI
artifacts accumulate the perf trajectory across runs, and ``meta``
(`run_meta()`: git SHA, hostname, jax version, device kind) keeps every
recorded row attributable to the code and machine that produced it.
"""
from __future__ import annotations

import functools
import json
import os
import platform
import socket
import subprocess
import sys
import time

RESULTS: list[dict] = []


@functools.lru_cache(maxsize=1)
def run_meta() -> dict:
    """Provenance stamped into every recorded run: git SHA, hostname,
    platform, python/jax versions, and the JAX device kind. The git SHA
    is best-effort (a copy of the tree need not be a repository); the
    device probe is not: a row must never be recorded without the
    device it ran on."""
    meta = {
        "hostname": socket.gethostname(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
    }
    try:
        meta["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        meta["git_sha"] = None
    import jax
    meta["jax"] = jax.__version__
    dev = jax.devices()[0]
    meta["device_kind"] = dev.device_kind
    meta["backend"] = dev.platform
    meta["device_count"] = jax.device_count()
    return dict(meta)


def emit(name: str, us_per_call: float, derived: str = ""):
    """CSV row: name, us_per_call, derived (also recorded for JSON)."""
    print(f"{name},{us_per_call:.3f},{derived}")
    RESULTS.append({"name": name, "us_per_call": round(us_per_call, 3),
                    "derived": derived})


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.time()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    return out, (time.time() - t0) * 1e6 / repeats


def write_json(tag: str, rows: list[dict] | None = None) -> str:
    """Append one run record (`rows`, default: everything emitted so far)
    to BENCH_<tag>.json; returns the path. Existing history -- including
    the pre-append single-run {"rows": ...} layout -- is preserved."""
    rows = RESULTS if rows is None else rows
    out_dir = os.environ.get("BENCH_OUT", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{tag}.json")
    runs: list[dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            runs = old.get("runs", [])
            if "rows" in old:              # legacy overwrite-style layout
                runs.insert(0, {"rows": old["rows"]})
        except (json.JSONDecodeError, OSError):
            pass                           # corrupt history: start fresh
    runs.append({"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
                 "meta": run_meta(), "rows": rows})
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"tag": tag, "runs": runs}, f, indent=1)
    os.replace(tmp, path)
    print(f"[bench] appended {len(rows)} rows to {path} "
          f"({len(runs)} recorded runs)")
    return path
