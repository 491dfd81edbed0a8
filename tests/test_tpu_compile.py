"""Ahead-of-time compiles of the frontier relax kernel for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel body on the CPU
and accepts block shapes the chip's compiler refuses. These tests lower
`frontier_relax_pallas` for a described (not attached) v5e and compile
it with the installed TPU compiler, so a layout Mosaic rejects fails
here instead of on the chip. Nothing runs: only shapes are passed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a file that decided at import
whether its tests exist would hand pytest-xdist workers different test
lists. All such compiles live in this one file.

Shapes are the real ones: ExtLRN (16,384 vertices: 128 tiles, 382
blocks at T=128) and the 2^20-vertex road network (8192 tiles, ~35k
blocks).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.algebra import MAX_MIN, MIN_PLUS, OR_AND, PLUS_TIMES
from repro.kernels.frontier.frontier import frontier_relax_pallas

T = 128
EXT_LRN = (128, 382)          # (ntiles, blocks) of make_dataset("ExtLRN")
ROAD_1M = (8192, 34990)       # make_road_network(1 << 20, delete_frac=0.56)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, semiring, state, nblocks, nslots, compact=False,
             feature_dim=1):
    """Lower + compile one relax call for the described chip; returns the
    compiled executable's HLO text."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    idx = sds((nslots,), jnp.int32)
    args = [sds(state), sds(state), sds((nblocks, T, T)), idx, idx]
    if compact:
        args.append(idx)
        fn = jax.jit(lambda sv, c, bl, bs, bd, sel: frontier_relax_pallas(
            sv, c, bl, bs, bd, semiring=semiring, bsel=sel,
            feature_dim=feature_dim))
    else:
        fn = jax.jit(functools.partial(frontier_relax_pallas,
                                       semiring=semiring,
                                       feature_dim=feature_dim))
    return fn.lower(*args).compile().as_text()


SCALAR = [MIN_PLUS, MAX_MIN, OR_AND, PLUS_TIMES]


@pytest.mark.parametrize("semiring", SCALAR, ids=lambda s: s.name)
def test_scalar_solo(one_chip, semiring):
    ntiles, nb = EXT_LRN
    assert "tpu_custom_call" in _compile(one_chip, semiring, (ntiles, T),
                                         nb, nb)


@pytest.mark.parametrize("semiring", SCALAR, ids=lambda s: s.name)
def test_scalar_batch32(one_chip, semiring):
    ntiles, nb = EXT_LRN
    assert "tpu_custom_call" in _compile(one_chip, semiring,
                                         (32, ntiles, T), nb, nb)


@pytest.mark.parametrize("shape", [EXT_LRN, ROAD_1M],
                         ids=["extlrn", "road_1m"])
def test_scalar_compacted(one_chip, shape):
    """bsel over the sentinel-extended block array (nb + 1 blocks)."""
    ntiles, nb = shape
    assert "tpu_custom_call" in _compile(one_chip, MIN_PLUS,
                                         (8, ntiles, T), nb + 1, nb,
                                         compact=True)


def test_scalar_tile_count_not_multiple_of_slab(one_chip):
    """ntiles not a multiple of the 8-tile slab pads inside the call."""
    assert "tpu_custom_call" in _compile(one_chip, MIN_PLUS, (4, 13, T),
                                         40, 40)


@pytest.mark.parametrize("semiring", [MIN_PLUS, PLUS_TIMES],
                         ids=lambda s: s.name)
def test_features_d8_batch8(one_chip, semiring):
    ntiles, nb = EXT_LRN
    assert "tpu_custom_call" in _compile(one_chip, semiring,
                                         (8, ntiles, T, 8), nb + 1, nb,
                                         compact=True, feature_dim=8)


@pytest.mark.parametrize("semiring", [MIN_PLUS, PLUS_TIMES],
                         ids=lambda s: s.name)
def test_features_d128_solo(one_chip, semiring):
    ntiles, nb = EXT_LRN
    assert "tpu_custom_call" in _compile(one_chip, semiring,
                                         (ntiles, T, 128), nb, nb,
                                         feature_dim=128)
