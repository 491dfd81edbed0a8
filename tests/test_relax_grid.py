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
from repro.kernels.frontier.ops import _relax_jnp, _src_walk, relax_grid

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


def _walk(bsrc):
    """The source walk ``(src_order, src_tiles)`` of a stream, on device."""
    return tuple(map(jnp.asarray, _src_walk(np.asarray(bsrc))))


def _stream(sr, sv, blocks, bsrc, bdst, stream, walk=None):
    """``(blocks, bsrc, bdst, bsel, n_active)`` of a dense or compacted
    stream, in the source walk's order when `walk` is given."""
    nb = bsrc.shape[0]
    if stream == "dense":
        if walk is None:
            return blocks, bsrc, bdst, jnp.arange(nb, dtype=jnp.int32), nb
        order = walk[0]
        return (blocks, jnp.take(bsrc, order), jnp.take(bdst, order),
                order, nb)
    ident = jnp.full((1, T, T), sr.zero, jnp.float32)
    bsel, bsrc_c, bdst_c, n_active = compact_block_stream(
        tile_activity(sv, sr), bsrc, bdst, None, walk)
    return (jnp.concatenate([blocks, ident]), bsrc_c, bdst_c, bsel,
            int(n_active))


def _grouped(sv, carry, sr, *stream_args):
    """The grouped grid at GROUP slots a step, on the TPU interpreter."""
    grouped = jax.jit(functools.partial(
        F._relax_grouped, semiring=sr, interpret=pltpu.InterpretParams(),
        group=GROUP))
    return np.asarray(grouped(sv, carry, *stream_args))


@pytest.mark.parametrize("stream", sorted(DEAD))
@pytest.mark.parametrize("batch", [1, 3, 8, 16])
@pytest.mark.parametrize("sr", SCALAR, ids=lambda s: s.name)
def test_grouped_matches_slab_and_jnp(sr, batch, stream):
    """The grouped grid over the stream in the order its calls walk it
    (source-major at B = 8 and 16 under an idempotent ⊕) against the
    slab grid over the destination-major stream and the jnp fallback."""
    sv, carry, blocks, bsrc, bdst = _case(sr, batch, stream)
    nb = bsrc.shape[0]
    want = np.asarray(_relax_jnp(sv, carry, blocks, bsrc, bdst,
                                 semiring=sr))
    dst_args = _stream(sr, sv, blocks, bsrc, bdst, stream)
    n_active = dst_args[-1]
    if stream != "dense":
        expect = {"compact_all": nb, "compact_none": 0}.get(stream)
        if expect is None:
            assert 0 < n_active < nb and n_active % GROUP
        else:
            assert n_active == expect
    walk = F.relax_walk("grouped", batch, sr)
    assert walk == ("src" if batch % 8 == 0 and sr.idempotent else "dst")
    walk_args = _stream(sr, sv, blocks, bsrc, bdst, stream,
                        _walk(bsrc) if walk == "src" else None)
    assert walk_args[-1] == n_active
    got = _grouped(sv, carry, sr, *walk_args)
    slab = np.asarray(F._relax_slab(sv, carry, *dst_args[:-1], semiring=sr,
                                    interpret=True, feature_dim=1))
    np.testing.assert_array_equal(got, slab)
    np.testing.assert_array_equal(got, want)
    # the destination no block writes keeps its carry, bit for bit
    np.testing.assert_array_equal(got[:, 3], np.asarray(carry)[:, 3])


@pytest.mark.parametrize("sr", [MIN_PLUS, MAX_MIN, OR_AND],
                         ids=lambda s: s.name)
def test_source_runs_bit_exact_with_real_floats(sr):
    """U[0,1) f32 weights and state, which round in every ⊗: the source
    walk at B = 8 gives the slab grid's and the jnp fallback's bits,
    because each candidate is one ⊗ of the same two floats and ⊕ is
    exact in any order."""
    rng = np.random.default_rng(5)
    nt, batch = 9, 8
    pairs = sorted({(d, d) for d in range(nt)}
                   | {(int(d), int(s)) for d, s in
                      rng.integers(0, nt, (30, 2))})
    bdst = jnp.asarray([d for d, _ in pairs], jnp.int32)
    bsrc = jnp.asarray([s for _, s in pairs], jnp.int32)
    blocks = rng.random((len(pairs), T, T), np.float32)
    blocks[rng.random(blocks.shape) < 0.5] = sr.zero
    sv = rng.random((batch, nt, T), np.float32)
    sv[rng.random(sv.shape) < 0.3] = sr.zero
    sv[:, [2, 6]] = sr.zero                     # dead for every row
    sv[3, 4] = sr.zero                          # dead for one row
    carry = rng.random((batch, nt, T), np.float32)
    sv, carry, blocks = map(jnp.asarray, (sv, carry, blocks))
    walk_args = _stream(sr, sv, blocks, bsrc, bdst, "compact", _walk(bsrc))
    dst_args = _stream(sr, sv, blocks, bsrc, bdst, "compact")
    assert 0 < walk_args[-1] < len(pairs)
    got = _grouped(sv, carry, sr, *walk_args)
    slab = np.asarray(F._relax_slab(sv, carry, *dst_args[:-1], semiring=sr,
                                    interpret=True, feature_dim=1))
    want = np.asarray(_relax_jnp(sv, carry, blocks, bsrc, bdst,
                                 semiring=sr))
    np.testing.assert_array_equal(got, slab)
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got, np.asarray(carry))   # it relaxed


@pytest.mark.parametrize("stream", sorted(DEAD))
def test_source_walk_compaction(stream):
    """Through the source walk the compaction names the same blocks as
    the destination walk, in (bsrc, bdst) order, with the same active
    count and the sentinel tail; the dense stream walks `src_order`
    itself."""
    sv, _, _, bsrc, bdst = _case(MIN_PLUS, 3, stream)
    nb = bsrc.shape[0]
    order, tiles = _src_walk(np.asarray(bsrc))
    assert sorted(order) == list(range(nb))
    np.testing.assert_array_equal(tiles, np.asarray(bsrc)[order])
    if stream == "dense":
        act = jnp.ones(NTILES, bool)
    else:
        act = tile_activity(sv, MIN_PLUS)
    dst = compact_block_stream(act, bsrc, bdst)
    src = compact_block_stream(act, bsrc, bdst, None, _walk(bsrc))
    n = int(src[3])
    assert n == int(dst[3])
    sel, sel_d = np.asarray(src[0]), np.asarray(dst[0])
    assert sorted(sel[:n]) == sorted(sel_d[:n])
    assert (sel[n:] == nb).all() and (sel_d[n:] == nb).all()
    s, d = np.asarray(src[1]), np.asarray(src[2])
    np.testing.assert_array_equal(s[:n], np.asarray(bsrc)[sel[:n]])
    np.testing.assert_array_equal(d[:n], np.asarray(bdst)[sel[:n]])
    keys = s[:n].astype(np.int64) * NTILES + d[:n]
    assert (np.diff(keys) > 0).all()                 # (bsrc, bdst) order
    # the tail repeats the last active block's pair (block nb-1's if none)
    last = sel[n - 1] if n else nb - 1
    assert (s[n:] == np.asarray(bsrc)[last]).all()
    assert (d[n:] == np.asarray(bdst)[last]).all()
    if stream == "dense":
        np.testing.assert_array_equal(sel, order)


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


@pytest.mark.parametrize("relax_mode, batch, algo, path, walk", [
    ("interpret", 1, "sssp", "grouped", "dst"),
    ("interpret", 4, "sssp", "grouped", "dst"),
    ("interpret", 8, "sssp", "grouped", "src"),
    ("interpret", 8, "pagerank", "grouped", "dst"),   # (+, ×)
    ("jnp", 4, "sssp", "jnp", ""),
])
def test_telemetry_reports_relax_grid(relax_mode, batch, algo, path, walk):
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, algo, flip.ExecutionPlan(relax_mode=relax_mode,
                                                  tile=64))
    srcs = SRCS8[:batch] if batch > 1 else int(SRCS8[0])
    r = cq.query(srcs)
    rt = cq.query(srcs, trace=True)
    np.testing.assert_array_equal(np.asarray(r.attrs), np.asarray(rt.attrs))
    np.testing.assert_array_equal(np.asarray(r.steps), np.asarray(rt.steps))
    d = rt.telemetry.dispatches[0]
    nslots = d.n_blocks
    want_steps = 0 if path == "jnp" else -(-nslots // F.GROUP)
    want = (path, want_steps, walk)
    assert (d.relax_path, d.relax_grid_steps, d.relax_walk) == want
    s = d.summary()
    assert (s["relax_path"], s["relax_grid_steps"], s["relax_walk"]) == want
    j = d.to_json()
    assert (j["relax_path"], j["relax_grid_steps"], j["relax_walk"]) == want
    tr = d.trace
    if walk != "src":
        assert tr.src_runs is None and s["slots_per_src_run"] is None
        return
    # every tile owns its diagonal block, so each active source tile is
    # one run of the compacted stream
    assert (tr.src_runs > 0).all()
    np.testing.assert_array_equal(tr.src_runs, tr.active_tiles)
    assert s["slots_per_src_run"] == (tr.blocks_fetched.sum()
                                      / tr.src_runs.sum())
    assert j["trace"]["src_runs"] == tr.src_runs.tolist()


def test_host_fixpoint_reports_source_runs():
    """A deadline runs the fixpoint program one step per call from the
    host; its trace counts the same source runs as one whole-loop
    call's, and the answer is the same."""
    g = make_road_network(160, seed=0)
    eng = flip.compile(g, "sssp", flip.ExecutionPlan(
        relax_mode="interpret", tile=64)).engine
    out, steps, host = eng.execute(SRCS8[:8], trace=True, deadline_s=1e6)
    want, want_steps, dev = eng.execute(SRCS8[:8], trace=True)
    assert host.trace.step_wall_s is not None     # one step per call
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(steps, want_steps)
    assert host.relax_walk == dev.relax_walk == "src"
    np.testing.assert_array_equal(host.trace.src_runs, dev.trace.src_runs)


def test_microbench_rehearses_on_the_interpreter(tmp_path):
    """`tools/relax_microbench.py` runs its bodies on the TPU interpreter
    at a tiny size, one line per case, and leaves the kernel's body
    selection as it found it."""
    import importlib.util
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "relax_microbench.py")
    spec = importlib.util.spec_from_file_location("relax_microbench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = tmp_path / "mb.jsonl"
    assert bench.main(["--interpret", "--slots", "16", "--blocks", "4",
                       "--tiles", "8", "--runs", "1,4", "--batches", "1,8",
                       "--reps", "1", "--repeat", "1", "--json",
                       str(out)]) == 0
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [(x["batch"], x["body"], x["run"]) for x in lines] == [
        (1, "row", 1), (1, "row", 4), (8, "rows8", 1), (8, "rows8", 4),
        (8, "runs", 1), (8, "runs", 4)]
    assert all(x["us_per_slot"] is None for x in lines)
    assert F.source_runs(8, MIN_PLUS) and F.CHAINS == 8


def test_relax_grid_slab_for_vector_programs():
    """A d > 1 program's dispatch reports the slab grid: nslots * B grid
    steps per relax step."""
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, "sssp", flip.ExecutionPlan(relax_mode="interpret",
                                                    tile=64))
    bg = cq.engine.bg
    nslots = int(bg.bsrc.shape[0])
    assert relax_grid(bg, 4, "interpret", feature_dim=8) == \
        ("slab", nslots * 4, "dst")
    assert relax_grid(bg, 4, "interpret") == ("grouped",
                                              -(-nslots // F.GROUP), "dst")
