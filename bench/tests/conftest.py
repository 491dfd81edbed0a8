"""Scaffolding of the harness's CPU tests: a benchmark root whose
configurations are cut to a size the CPU runs in seconds.

`tiny_root` copies `BENCHMARK.json` and everything under `bench/` that
the harness finds by name, then rewrites each configuration file to a
small graph of the same generator. The harness under test is the real
one; only its data is small.
"""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(REPO, "src") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "src"))

import pytest  # noqa: E402

TINY = {"kronecker": {"scale": 9}}


def cells() -> list:
    """The workloads `BENCHMARK.json` lists, by name."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def make_tiny_root(dest: str) -> str:
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    for part in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "bench", part),
                        os.path.join(dest, "bench", part))
    shutil.copy(os.path.join(REPO, "bench", "peaks.json"),
                os.path.join(dest, "bench", "peaks.json"))
    cdir = os.path.join(dest, "bench", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            config = json.load(f)
        config.update(TINY[config["generator"]])
        with open(path, "w") as f:
            json.dump(config, f)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def restore_compile_cache():
    """A run turns JAX's persistent cache on for its process; put the
    options back so that the worker's later tests see what they saw."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
