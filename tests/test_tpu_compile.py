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
blocks at T=128), the 2^20-vertex road network (8192 tiles, ~35k
blocks) and the benchmark's graph500-s15 (256 tiles, 65,448 blocks).
Scalar states that fit the VMEM budget compile the grouped grid, the
others and every d > 1 state the slab grid; the grouped cases also
check the scoped VMEM Mosaic reports against the budget.
"""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.algebra import MAX_MIN, MIN_PLUS, OR_AND, PLUS_TIMES
from repro.kernels.frontier.frontier import (GROUPED_VMEM_BUDGET,
                                             _VMEM_HEADROOM,
                                             frontier_relax_pallas,
                                             grouped_vmem_bytes, relax_body,
                                             relax_path, table_vmem_bytes)

T = 128
EXT_LRN = (128, 382)          # (ntiles, blocks) of make_dataset("ExtLRN")
ROAD_1M = (8192, 34990)       # make_road_network(1 << 20, delete_frac=0.56)
GRAPH500_S15 = (256, 65448)   # bench/configs/graph500-s15.json


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
    compiled executable's HLO text. `compact` passes the selection list
    and the traced active count, as `ops.frontier_relax` does."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    idx = sds((nslots,), jnp.int32)
    args = [sds(state), sds(state), sds((nblocks, T, T)), idx, idx]
    if compact:
        args += [idx, sds((), jnp.int32)]
        fn = jax.jit(lambda sv, c, bl, bs, bd, sel, n: frontier_relax_pallas(
            sv, c, bl, bs, bd, semiring=semiring, bsel=sel,
            feature_dim=feature_dim, n_active=n))
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
    """ntiles not a multiple of the 8-tile slab: the grouped grid holds
    the state whole, and the slab grid (a state over the VMEM budget)
    pads it inside the call."""
    assert relax_path(4, 13, 13, T) == "grouped"
    assert "tpu_custom_call" in _compile(one_chip, MIN_PLUS, (4, 13, T),
                                         40, 40)
    assert relax_path(32, 8189, 8189, T) == "slab"
    assert "tpu_custom_call" in _compile(one_chip, MIN_PLUS,
                                         (32, 8189, T), 40, 40)


def _used_vmem(hlo: str) -> int:
    """Scoped VMEM (memory space 1) the compiled kernel uses, from the
    custom call's backend config."""
    line = next(ln for ln in hlo.splitlines() if "tpu_custom_call" in ln)
    cfg = json.loads(re.search(r"backend_config=(\{.*\})", line).group(1))
    return sum(int(c["size"]) for c in cfg["used_scoped_memory_configs"]
               if c["memory_space"] == "1")


@pytest.mark.parametrize("batch", [1, 3, 8])
@pytest.mark.parametrize("semiring", [MIN_PLUS, PLUS_TIMES],
                         ids=lambda s: s.name)
def test_grouped_graph500_s15(one_chip, semiring, batch):
    """The benchmark's shape on the grouped grid, compacted stream: Mosaic
    takes the resident state, the manual weight copies and, below B = 8,
    the table body's (B, 256, T, T) table (48 MiB at B = 3), within the
    scoped VMEM the call grants."""
    ntiles, nb = GRAPH500_S15
    assert relax_path(batch, ntiles, ntiles, T) == "grouped"
    state = (batch, ntiles, T) if batch > 1 else (ntiles, T)
    hlo = _compile(one_chip, semiring, state, nb + 1, nb, compact=True)
    need = grouped_vmem_bytes(batch, ntiles, ntiles, T)
    if batch % 8:
        assert relax_body(batch, ntiles, ntiles, T, semiring) == "table"
        need += table_vmem_bytes(batch, ntiles, T)
    assert need <= GROUPED_VMEM_BUDGET
    # Mosaic's own transients fit the headroom the call grants above the
    # resident bytes
    assert 0 < _used_vmem(hlo) <= need + _VMEM_HEADROOM


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


GRAPH500_S16 = (512, 64353)   # bench/configs/graph500-s16.json over 4
                              # chips: (tiles, slots of the fullest chip)


@pytest.mark.parametrize("trace_cap", [0, 1 << 14])
def test_sharded_fixpoint_graph500_s16_four_chips(topo, monkeypatch,
                                                  trace_cap):
    """The whole sharded SSSP fixpoint at B=8 over a described 2x2 v5e
    (the `kron16-sssp-4chip` cell's program): each chip's arguments are
    its own slab and stream and the replicated state -- nothing else of
    the graph -- it runs the grouped kernel, and the step ends in an
    all-gather."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.algebra import get_algebra
    from repro.core.engine import FlipEngine
    from repro.kernels.frontier.ops import BlockedGraph, ShardLayout
    # the described chips, not this process's CPU, are the target
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices), ("data",))
    ntiles, slots = GRAPH500_S16
    b, n, nb = 8, ntiles * T, 257200
    shard = NamedSharding(mesh, P("data"))
    whole = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    layout = (sds((4 * (slots + 1), T, T), jnp.float32, shard),
              sds((4 * slots,), jnp.int32, shard),
              sds((4 * slots,), jnp.int32, shard),
              sds((4 * slots,), bool, shard),
              sds((4 * slots,), jnp.int32, shard),
              sds((4 * slots,), jnp.int32, shard))
    bg = BlockedGraph(
        n=n, tile=T, ntiles=ntiles, blocks=layout[0], bsrc=layout[1],
        bdst=layout[2], perm=np.arange(n), inv_perm=np.arange(n),
        algebra=get_algebra("sssp"), blocks_ext=layout[0],
        dst_start=np.zeros(ntiles + 1, np.int32), live=layout[3],
        src_order=layout[4], src_tiles=layout[5],
        shards=ShardLayout(mesh=mesh, axis="data", tiles_per_dev=ntiles // 4,
                           slots=slots, starts=np.zeros(5, np.int64),
                           keys=np.zeros(nb, np.int64)))
    eng = FlipEngine(bg=bg, algo="sssp", relax_mode="pallas")
    state = sds((b, ntiles, T), jnp.float32, whole)
    compiled = eng._dense_fixpoint_jit(trace_cap, bg).lower(
        layout, state, state, sds((b, ntiles, T), bool, whole),
        sds((b,), jnp.int32, whole)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    slab = (slots + 1) * T * T * 4
    args = compiled.memory_analysis().argument_size_in_bytes
    assert slab <= args <= slab + (16 << 20)
