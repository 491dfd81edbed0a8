"""The relax kernel's two grids: the grouped path (GROUP slots of the
block stream per grid step, all B rows, state resident in VMEM, weight
blocks copied in by hand) against the slab path and the jnp fallback,
and the shape rule that picks between them.

The grouped path runs under the TPU interpreter
(`pltpu.InterpretParams`), which executes its manual DMAs and
semaphores on the CPU; the slab path under the plain interpreter.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from conftest import SRCS8
from repro import api as flip
from repro.algebra import MAX_MIN, MIN_PLUS, OR_AND, PLUS_TIMES
from repro.graphs import make_road_network
from repro.kernels.frontier import compact_block_stream, tile_activity
from repro.kernels.frontier import frontier as F
from repro.kernels.frontier.ops import _relax_jnp, relax_grid

SCALAR = [MIN_PLUS, MAX_MIN, OR_AND, PLUS_TIMES]
T, NTILES, GROUP = 8, 6, 4
# (bdst, bsrc) blocks sorted by destination: tile 1's blocks fill slots
# 3-5 and tile 2's slots 6-9, so both straddle a group boundary (slots
# 4 and 8); tile 3 has no incident block and must keep its carry
PAIRS = [(0, 0), (0, 2), (0, 5),
         (1, 0), (1, 1), (1, 4),
         (2, 1), (2, 2), (2, 3), (2, 5),
         (4, 0), (4, 4),
         (5, 1), (5, 2), (5, 5)]
# source tiles inactive for every row, per stream
DEAD = {"dense": (), "compact_all": (), "compact_none": tuple(range(NTILES)),
        "compact_ragged": (2, 3)}


def _values(rng, sr, shape):
    """Dyadic values (multiples of 1/8 below 2): every ⊕ and ⊗ of them,
    in any order, is exact in f32, so all three implementations must
    agree bit for bit whatever their reduction order."""
    if sr is OR_AND:
        return (rng.random(shape) < 0.5).astype(np.float32)
    return (rng.integers(1, 16, shape) / 8).astype(np.float32)


def _case(sr, batch, stream, seed=0):
    rng = np.random.default_rng(seed)
    nb = len(PAIRS)
    blocks = _values(rng, sr, (nb, T, T))
    blocks[rng.random(blocks.shape) < 0.4] = sr.zero      # absent edges
    sv = _values(rng, sr, (batch, NTILES, T))
    sv[rng.random(sv.shape) < 0.3] = sr.zero               # inactive lanes
    sv[:, list(DEAD[stream])] = sr.zero
    if batch > 1:                      # per-row trigger: a tile inactive
        for b in range(batch):         # for one row, live for the others
            sv[b, (b + 1) % NTILES] = sr.zero
    carry = _values(rng, sr, (batch, NTILES, T))
    bdst = jnp.asarray([d for d, _ in PAIRS], jnp.int32)
    bsrc = jnp.asarray([s for _, s in PAIRS], jnp.int32)
    return (jnp.asarray(sv), jnp.asarray(carry), jnp.asarray(blocks),
            bsrc, bdst)


@pytest.mark.parametrize("stream", sorted(DEAD))
@pytest.mark.parametrize("batch", [1, 3, 8])
@pytest.mark.parametrize("sr", SCALAR, ids=lambda s: s.name)
def test_grouped_matches_slab_and_jnp(sr, batch, stream):
    sv, carry, blocks, bsrc, bdst = _case(sr, batch, stream)
    nb = bsrc.shape[0]
    want = np.asarray(_relax_jnp(sv, carry, blocks, bsrc, bdst,
                                 semiring=sr))
    if stream == "dense":
        stream_args = (blocks, bsrc, bdst, jnp.arange(nb, dtype=jnp.int32))
        n_active = nb
    else:
        ident = jnp.full((1, T, T), sr.zero, jnp.float32)
        bsel, bsrc_c, bdst_c, n_active = compact_block_stream(
            tile_activity(sv, sr), bsrc, bdst)
        stream_args = (jnp.concatenate([blocks, ident]), bsrc_c, bdst_c,
                       bsel)
        n_active = int(n_active)
        expect = {"compact_all": nb, "compact_none": 0}.get(stream)
        if expect is None:
            assert 0 < n_active < nb and n_active % GROUP
        else:
            assert n_active == expect
    grouped = jax.jit(functools.partial(
        F._relax_grouped, semiring=sr, interpret=pltpu.InterpretParams(),
        group=GROUP))
    got = np.asarray(grouped(sv, carry, *stream_args, n_active))
    slab = np.asarray(F._relax_slab(sv, carry, *stream_args, semiring=sr,
                                    interpret=True, feature_dim=1))
    np.testing.assert_array_equal(got, slab)
    np.testing.assert_array_equal(got, want)
    # the destination no block writes keeps its carry, bit for bit
    np.testing.assert_array_equal(got[:, 3], np.asarray(carry)[:, 3])


@pytest.mark.parametrize("batch", [1, 8, 16, 33])
def test_grouped_default_group_through_public_entry(batch):
    """`frontier_relax_pallas` takes the grouped path at these shapes,
    with the module's own GROUP and n_active left to default (every slot
    of a dense stream). B = 33 reads its triggers from two bitmask
    words."""
    assert F.relax_path(batch, NTILES, NTILES, T) == "grouped"
    sv, carry, blocks, bsrc, bdst = _case(MIN_PLUS, batch, "dense", seed=1)
    trig = np.asarray(F._row_triggers(sv, MIN_PLUS.zero))
    act = np.asarray(jnp.any(sv != MIN_PLUS.zero, axis=-1))     # (B, ns)
    for b in range(batch):
        np.testing.assert_array_equal(
            (trig[(b // 32) * NTILES:(b // 32 + 1) * NTILES] >> (b % 32))
            & 1, act[b])
    got = F.frontier_relax_pallas(sv, carry, blocks, bsrc, bdst,
                                  semiring=MIN_PLUS,
                                  interpret=pltpu.InterpretParams())
    want = _relax_jnp(sv, carry, blocks, bsrc, bdst, semiring=MIN_PLUS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ #
# which grid a call takes: shapes alone decide
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("feature_dim, batch, ntiles, path", [
    (1, 8, 256, "grouped"),        # graph500-s15, B=8: 2 MiB of state
    (1, 1, 8192, "grouped"),       # 2^20-vertex road graph solo: 8 MiB
    (1, 32, 8192, "slab"),         # the same at B=32: 256 MiB, over budget
    (8, 8, 256, "slab"),           # vector state keeps the slab grid
    (128, 1, 128, "slab"),
])
def test_relax_path_by_shape(feature_dim, batch, ntiles, path):
    assert F.relax_path(batch, ntiles, ntiles, 128, feature_dim) == path
    nslots = 65448
    steps = F.relax_grid_steps(path, nslots, batch)
    if path == "grouped":
        assert steps == -(-nslots // F.GROUP)
    else:
        assert steps == nslots * batch


def test_budget_edge():
    """The budget is inclusive and counts the source, the output and
    the weight buffers."""
    t = 128
    per_tile = 2 * 128 * 4                    # one source + one output row
    weights = F.WEIGHT_BUFFERS * t * t * 4
    fit = (F.GROUPED_VMEM_BUDGET - weights) // per_tile
    fit -= fit % 8
    assert F.grouped_vmem_bytes(1, fit, fit, t) <= F.GROUPED_VMEM_BUDGET
    assert F.relax_path(1, fit, fit, t) == "grouped"
    assert F.relax_path(1, fit + 8, fit + 8, t) == "slab"


def test_distributed_shape_takes_grouped_path():
    """A device of the distributed fixpoint relaxes the whole source state
    (ns tiles) into its own slab of destination tiles (ntiles < ns). The
    grouped path keeps both whole in VMEM, so it takes that case, and its
    result matches the jnp fallback bit for bit."""
    ns, ntiles, batch = 6, 2, 3
    assert F.relax_path(batch, ns, ntiles, T) == "grouped"
    rng = np.random.default_rng(2)
    pairs = [(0, 1), (0, 4), (1, 0), (1, 2), (1, 5)]
    bdst = jnp.asarray([d for d, _ in pairs], jnp.int32)
    bsrc = jnp.asarray([s for _, s in pairs], jnp.int32)
    blocks = jnp.asarray(_values(rng, MIN_PLUS, (len(pairs), T, T)))
    sv = jnp.asarray(_values(rng, MIN_PLUS, (batch, ns, T)))
    carry = jnp.asarray(_values(rng, MIN_PLUS, (batch, ntiles, T)))
    got = F.frontier_relax_pallas(sv, carry, blocks, bsrc, bdst,
                                  semiring=MIN_PLUS,
                                  interpret=pltpu.InterpretParams())
    assert got.shape == (batch, ntiles, T)
    want = _relax_jnp(sv, carry, blocks, bsrc, bdst, semiring=MIN_PLUS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("relax_mode, batch, path", [
    ("interpret", 1, "grouped"),
    ("interpret", 4, "grouped"),
    ("jnp", 4, "jnp"),
])
def test_telemetry_reports_relax_grid(relax_mode, batch, path):
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, "sssp", flip.ExecutionPlan(relax_mode=relax_mode,
                                                    tile=64))
    srcs = SRCS8[:batch] if batch > 1 else int(SRCS8[0])
    r = cq.query(srcs)
    rt = cq.query(srcs, trace=True)
    np.testing.assert_array_equal(np.asarray(r.attrs), np.asarray(rt.attrs))
    np.testing.assert_array_equal(np.asarray(r.steps), np.asarray(rt.steps))
    d = rt.telemetry.dispatches[0]
    nslots = d.n_blocks
    want_steps = 0 if path == "jnp" else -(-nslots // F.GROUP)
    assert (d.relax_path, d.relax_grid_steps) == (path, want_steps)
    s = d.summary()
    assert (s["relax_path"], s["relax_grid_steps"]) == (path, want_steps)
    j = d.to_json()
    assert (j["relax_path"], j["relax_grid_steps"]) == (path, want_steps)


def test_relax_grid_slab_for_vector_programs():
    """A d > 1 program's dispatch reports the slab grid: nslots * B grid
    steps per relax step."""
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, "sssp", flip.ExecutionPlan(relax_mode="interpret",
                                                    tile=64))
    bg = cq.engine.bg
    nslots = int(bg.bsrc.shape[0])
    assert relax_grid(bg, 4, "interpret", feature_dim=8) == \
        ("slab", nslots * 4)
    assert relax_grid(bg, 4, "interpret") == ("grouped",
                                              -(-nslots // F.GROUP))
