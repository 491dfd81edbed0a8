"""Shared test scaffolding for the whole suite.

Environment setup (CPU platform pin, `src/` on the path) plus the
graph/algebra helpers that used to be copy-pasted across
`test_algebra.py`, `test_batched.py`, and `test_compaction.py`:
oracle-comparison assertions, tiled-state builders, the batched
bit-exactness checker, and the TPU/CPU skip markers for the Pallas
paths. Import them with ``from conftest import ...`` (pytest puts the
tests directory on `sys.path` while collecting).
"""
import os
import sys

# tests must see the real (1-device) platform; the 512-device override is
# dryrun.py-only. Some tests spawn subprocesses that set their own flags.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402
import pytest                  # noqa: E402

from repro.algebra import ALGEBRAS, VertexAlgebra   # noqa: E402
from repro.graphs import reference                  # noqa: E402

# scalar programs only: the shape-sensitive suites ((B, n) results,
# sim parity, solo-vs-batch bit-exactness) run over these; the vector
# programs (feature_dim > 1) have their own (n, d) suites in
# test_features.py / test_fuzz_differential.py.
ALGOS = sorted(a for a in ALGEBRAS if ALGEBRAS[a].feature_dim == 1)
VEC_ALGOS = sorted(a for a in ALGEBRAS if ALGEBRAS[a].feature_dim > 1)
SIM_ALGOS = [a for a in ALGOS if ALGEBRAS[a].sim_ok]
SRCS8 = np.array([3, 11, 0, 27, 42, 8, 19, 33])     # B=8 fixed sources

ON_TPU = jax.default_backend() == "tpu"
tpu_only = pytest.mark.skipif(
    not ON_TPU, reason="compiled Pallas path is TPU-only; CPU covers the "
                       "same kernel body via interpret mode")
cpu_only = pytest.mark.skipif(
    ON_TPU, reason="pallas mode is the real path on TPU")

finite = VertexAlgebra.finite   # shared ±inf-sentinel mapping


def assert_close(got, ref, algo, msg=""):
    """Oracle comparison at the algebra's tolerance, ±inf-safe."""
    alg = ALGEBRAS.get(algo)
    atol = alg.atol if alg is not None else 1e-6
    assert np.allclose(finite(got), finite(ref), atol=atol), \
        f"{algo} {msg}: max|d|=" \
        f"{np.abs(finite(got) - finite(ref)).max()}"


def oracle(algo, g, src):
    """The numpy reference result alone (stats dropped)."""
    out, _ = reference.run(algo, g, src)
    return out


def tiled_state(bg, rng, batch=0):
    """Random mid-run attribute state in tiled (B?, ntiles, T) layout."""
    shape = (batch, bg.n) if batch else (bg.n,)
    vals = rng.uniform(0.5, 9, shape).astype(np.float32)
    return bg.to_tiled(vals)


def masked_src_vals(bg, attrs, rng, density):
    """Frontier-masked source values at a named or numeric density:
    'none' / 'all' / 'tile0' (one active source tile) / a float
    per-lane activation probability."""
    if density == "none":
        mask = np.zeros(attrs.shape, dtype=bool)
    elif density == "all":
        mask = np.ones(attrs.shape, dtype=bool)
    elif density == "tile0":
        mask = np.zeros(attrs.shape, dtype=bool)
        mask[..., 0, :] = True
    else:
        mask = rng.random(attrs.shape) < density
    return jnp.where(jnp.asarray(mask), attrs,
                     np.float32(bg.semiring.zero))


def np_contract(sr, sv, w):
    """Plain-numpy feature contraction oracle for the vector-state
    kernels: out[D, f] = ⊕_s sv[s, f] ⊗ w[s, D], built from the
    semiring's numpy ops (independent of `Semiring.contract_jnp`)."""
    vals = sr.mul_np(sv[:, None, :], w[:, :, None])      # (S, D, d)
    out = vals[0]
    for s in range(1, vals.shape[0]):
        out = sr.add_np(out, vals[s])
    return out


def check_batch(eng, g, srcs, algo):
    """Batched `execute` rows must be bit-for-bit the solo runs and match the
    oracle (the batched-execution contract)."""
    outs, steps = eng.execute(srcs)
    assert outs.shape == (len(srcs), g.n)
    assert steps.shape == (len(srcs),)
    for b, s in enumerate(srcs):
        solo_out, solo_steps = eng.execute(int(s))
        np.testing.assert_array_equal(outs[b], solo_out)
        assert steps[b] == solo_steps
        assert ALGEBRAS[algo].results_match(outs[b], oracle(algo, g,
                                                            int(s))), \
            (algo, b)
