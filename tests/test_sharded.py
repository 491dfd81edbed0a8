"""The sharded block layout and the sharded fixpoint on 4 host devices.

Everything that needs more than one device runs once, in one subprocess
with ``xla_force_host_platform_device_count=4`` (the test process keeps
its one CPU device), and prints what it saw as JSON; the parametrised
tests below read their case from it. The graph is a scale-10 Graph500
Kronecker graph from `bench/graphs` at T=96: 11 tiles, padded to 12 (3 a
device), so the last device owns a tile with no vertex and its slab ends
in padding; the layout is checked at T=32 too (32 tiles, 8 a device).
"""
import json
import os
import subprocess
import sys

import pytest

from repro.api.plan import layout_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALGOS = ("sssp", "bfs", "widest", "wcc", "pagerank")
BATCHES = (1, 8)
INTERPRET = ("sssp", "pagerank")
TILES = (32, 96)
FIX_TILE = 96

SCRIPT = r"""
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from bench import graphs
import repro.api.plan as plan_mod
from repro import api as flip
from repro.graphs.csr import Graph
from repro.kernels.frontier.ops import (block_keys, build_blocks,
                                        compact_block_stream)

csr = graphs.generate({"generator": "kronecker", "dataset_seed": 0,
                       "scale": 10, "edgefactor": 16,
                       "initiator": [0.57, 0.19, 0.19, 0.05]}, 0)
g = Graph(indptr=csr.indptr, indices=csr.indices, weights=csr.weights,
          directed=False)
roots = np.flatnonzero(np.diff(csr.indptr) > 0)[::37][:8]
mesh = Mesh(np.array(jax.devices()), ("x",))
out = {"layout": {}, "walk": {}, "fixpoint": {}, "interpret": {},
       "updates": {}}


def same(a, b):
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


# ---- the sharded layout against the whole one, slab by slab ---------- #
for tile in TILES:
    whole = build_blocks(g, "sssp", tile)
    sh = block_keys(g, "sssp", tile).build(mesh=mesh, axis="x")
    lay = sh.shards
    wb, wsrc, wdst = (np.asarray(whole.blocks), np.asarray(whole.bsrc),
                      np.asarray(whole.bdst))
    shards = sorted(sh.blocks.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    checks = {
        "held_once": sh.blocks is sh.blocks_ext,
        "one_slab_per_device": (
            len(shards) == 4 and len({s.device for s in shards}) == 4
            and all(s.data.shape == (lay.slots + 1, tile, tile)
                    for s in shards)),
        "n_blocks": sh.n_blocks == whole.n_blocks,
        "padded_tiles": lay.ntiles_p % 4 == 0
        and lay.ntiles_p >= whole.ntiles,
    }
    bsrc = np.asarray(sh.bsrc).reshape(4, lay.slots)
    bdst = np.asarray(sh.bdst).reshape(4, lay.slots)
    live = np.asarray(sh.live).reshape(4, lay.slots)
    ranges, slabs, padding = True, True, True
    for k, s in enumerate(shards):
        lo, hi = k * lay.tiles_per_dev, (k + 1) * lay.tiles_per_dev
        a, e = int(lay.starts[k]), int(lay.starts[k + 1])
        c = e - a
        # the device's blocks are exactly those writing its tiles
        ranges &= bool(np.all((wdst[a:e] >= lo) & (wdst[a:e] < hi))
                       and a == np.searchsorted(wdst, lo)
                       and e == np.searchsorted(wdst, hi))
        slab = np.asarray(s.data)
        slabs &= (same(slab[:c], wb[a:e]) and same(bsrc[k, :c], wsrc[a:e])
                  and same(bdst[k, :c], wdst[a:e] - lo)
                  and bool(live[k, :c].all()))
        # padding and the sentinel are the ⊕-identity, never live
        padding &= bool(np.all(slab[c:] == np.float32(np.inf))
                        and not live[k, c:].any())
    checks.update(ranges=ranges, slabs=slabs, padding=padding)
    out["layout"][str(tile)] = checks

    # each slab's source walk: a permutation of its own slots in
    # (bsrc, bdst) order with their source tiles (-1 on padding), through
    # which compaction names the slab's live blocks of active source
    # tiles -- never a padding slot -- and the sentinel tail
    order = np.asarray(sh.src_order).reshape(4, lay.slots)
    tiles = np.asarray(sh.src_tiles).reshape(4, lay.slots)
    rng = np.random.default_rng(tile)
    act = jnp.asarray(rng.random(lay.ntiles_p) < 0.5)
    walk = {"permutation": True, "sorted": True, "tiles": True,
            "same_blocks": True, "no_padding": True, "tail": True,
            "padded_slab": False}
    for k in range(4):
        o = order[k]
        walk["permutation"] &= sorted(o.tolist()) == list(range(lay.slots))
        key = bsrc[k, o].astype(np.int64) * lay.ntiles_p + bdst[k, o]
        walk["sorted"] &= bool((np.diff(key) >= 0).all())
        walk["tiles"] &= same(tiles[k], np.where(live[k, o], bsrc[k, o], -1))
        walk["padded_slab"] |= not live[k].all()
        args = (act, jnp.asarray(bsrc[k]), jnp.asarray(bdst[k]),
                jnp.asarray(live[k]))
        sel, bs, bd, n = map(np.asarray, compact_block_stream(
            *args, (jnp.asarray(o), jnp.asarray(tiles[k]))))
        sel_d, _, _, n_d = map(np.asarray, compact_block_stream(*args))
        n = int(n)
        walk["same_blocks"] &= (n == int(n_d) and sorted(sel[:n].tolist())
                                == sorted(sel_d[:n].tolist()))
        walk["no_padding"] &= bool(live[k][sel[:n]].all())
        walk["tail"] &= bool((sel[n:] == lay.slots).all())
        walk["sorted"] &= bool((np.diff(bs[:n].astype(np.int64)
                                        * lay.ntiles_p + bd[:n]) > 0).all())
    out["walk"][str(tile)] = walk


# ---- the fixpoint: local, default plan over a small budget, mesh ----- #
def compiles_during(fn):
    seen = []

    def on_span(event, start, end, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
    return len(seen)


def compare(algo, relax_mode, batches, table):
    plan = flip.ExecutionPlan(tile=FIX_TILE, relax_mode=relax_mode)
    local = flip.compile(g, algo, plan)
    keys = block_keys(g, algo, FIX_TILE)
    # a device that holds the sharded slab but not the one-device layout
    plan_mod.device_bytes_limit = lambda: keys.shard_bytes(4)
    try:
        auto = flip.compile(g, algo, plan)
    finally:
        plan_mod.device_bytes_limit = real_limit
    explicit = flip.compile(g, algo, flip.ExecutionPlan(
        tile=FIX_TILE, relax_mode=relax_mode, mesh=mesh, mesh_axis="x"))
    for b in batches:
        srcs = int(roots[0]) if b == 1 else roots[:b]
        rl = local.query(srcs, trace=True)
        ra = auto.query(srcs)
        rt = auto.query(srcs, trace=True)
        re = explicit.query(srcs)
        again = (int(roots[1]) if b == 1
                 else np.roll(roots[:b], 1))
        n_compiles = compiles_during(lambda: auto.query(again))
        tl = rl.telemetry.dispatches[0]
        tt = rt.telemetry.dispatches[0]
        tr = tt.trace
        table[f"{algo}-{b}"] = {
            "auto_sharded": bool(auto.plan.distributed
                                 and auto.engine.bg.shards is not None),
            "local_stays_local": not local.plan.distributed,
            "auto_equal": same(ra.attrs, rl.attrs)
            and same(ra.steps, rl.steps),
            "explicit_equal": same(re.attrs, rl.attrs)
            and same(re.steps, rl.steps),
            "oracle": bool(ra.check()),
            "traced_equal": same(rt.attrs, ra.attrs)
            and same(rt.steps, ra.steps),
            "telemetry_equal": same(tr.blocks_fetched,
                                    tl.trace.blocks_fetched)
            and same(tr.active_tiles, tl.trace.active_tiles)
            and same(tr.active_vertices, tl.trace.active_vertices)
            and same(tr.converged, tl.trace.converged)
            and tt.n_blocks == tl.n_blocks,
            "weight_bytes_summed": (
                tt.summary()["hbm_weight_bytes_est"]
                == tl.summary()["hbm_weight_bytes_est"]
                and same(tr.shard_live_mean * 4, tr.blocks_fetched)
                and bool(np.all(tr.shard_live_max * 4
                                >= tr.blocks_fetched))),
            "gather_bytes": tt.meta.get("gather_bytes")
            == b * auto.engine.bg.shards.ntiles_p * FIX_TILE * 4,
            # the walk each device takes is the local one; a source tile
            # is a run on every device that streams a block of it
            "walk": tt.relax_walk == tl.relax_walk == (
                "" if relax_mode == "jnp" else
                "src" if b % 8 == 0 and algo != "pagerank" else "dst"),
            "src_runs": (tr.src_runs is None) == (tl.trace.src_runs is None)
            and (tr.src_runs is None
                 or bool((tr.src_runs >= tl.trace.src_runs).all()
                         and (tr.src_runs <= tr.blocks_fetched).all())),
            "second_call_compiles": n_compiles,
        }


# ---- streaming updates on a sharded session -------------------------- #
def updates(table):
    def plan(**kw):
        return flip.ExecutionPlan(tile=16, relax_mode="jnp", **kw)
    cq = flip.compile(g, "sssp", plan(mesh=mesh, mesh_axis="x"))
    bg = cq.engine.bg
    keys = set(bg.shards.keys.tolist())
    # two isolated vertices whose tile pair holds no block yet: an edge
    # between them grows the layout
    iso = np.flatnonzero(np.diff(csr.indptr) == 0)
    tile_of = bg.perm // 16
    u, v = next((int(a), int(b)) for a in iso for b in iso
                if tile_of[b] * bg.ntiles + tile_of[a] not in keys
                and tile_of[a] * bg.ntiles + tile_of[b] not in keys)
    eu = g.edge_sources()
    halve = [(int(eu[i]), int(g.indices[i]), float(g.weights[i]) * 0.5)
             for i in range(0, 400, 100)]
    srcs = roots[:8]
    prev = cq.query(srcs)
    for name, batch in (("value", halve), ("grow", halve + [(u, v, 0.25)])):
        cq2, delta = cq.update(batch)
        want = flip.compile(cq2.graph, "sssp", plan()).query(srcs)
        table[name] = {
            "shape_changed": delta.shape_changed == (name == "grow"),
            "still_sharded": cq2.engine.bg.shards is not None,
            "stream_kept": (cq2.engine.bg.bsrc is bg.bsrc)
            == (name == "value"),
            "scratch_equal": same(cq2.query(srcs).attrs, want.attrs),
            "warm_equal": same(cq2.query(srcs, warm=prev).attrs,
                               want.attrs),
        }


real_limit = plan_mod.device_bytes_limit
updates(out["updates"])
for algo in ALGOS:
    compare(algo, "jnp", BATCHES, out["fixpoint"])
for algo in INTERPRET:
    compare(algo, "interpret", (8,), out["interpret"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def seen():
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n"
            f"ALGOS, BATCHES, INTERPRET, TILES, FIX_TILE = {ALGOS!r}, "
            f"{BATCHES!r}, {INTERPRET!r}, {TILES!r}, {FIX_TILE}\n"
            + SCRIPT)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=900, cwd=REPO,
        env={"PYTHONPATH": os.path.join(REPO, "src"), "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", REPO), "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("tile", TILES)
def test_sharded_layout_is_the_whole_layout_split_by_destination(seen,
                                                                 tile):
    checks = seen["layout"][str(tile)]
    assert all(checks.values()), checks


@pytest.mark.parametrize("tile", TILES)
def test_sharded_source_walk_per_slab(seen, tile):
    # at T=96 the last device's slab ends in padding slots
    checks = seen["walk"][str(tile)]
    if tile == FIX_TILE:
        assert checks["padded_slab"]
    checks.pop("padded_slab")
    assert all(checks.values()), checks


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("batch", BATCHES)
def test_sharded_fixpoint_is_the_local_one_bit_for_bit(seen, algo, batch):
    case = seen["fixpoint"][f"{algo}-{batch}"]
    assert case.pop("second_call_compiles") == 0
    assert all(case.values()), case


@pytest.mark.parametrize("algo", INTERPRET)
def test_sharded_pallas_relax_is_the_local_one_bit_for_bit(seen, algo):
    # the compacted dispatch of the chip: compaction over the device's
    # live slots, then the grouped Pallas grid (interpreted)
    case = seen["interpret"][f"{algo}-8"]
    assert case.pop("second_call_compiles") == 0
    assert all(case.values()), case


@pytest.mark.parametrize("kind", ["value", "grow"])
def test_sharded_session_takes_updates(seen, kind):
    # a value-only batch patches the slabs in place (the stream, and so
    # the compiled fixpoint, kept); one that adds a tile pair re-lays
    # them; either way the answer is the scratch one, warm or not
    case = seen["updates"][kind]
    assert all(case.values()), case


GB = 10**9


@pytest.mark.parametrize("local,shard,limit,ndev,want", [
    (8.58 * GB, 4.3 * GB, 15.7 * GB, 4, 1),     # fits one device
    (15.7 * GB, 7.9 * GB, 15.7 * GB, 4, 1),     # fits exactly
    (33.7 * GB, 4.22 * GB, 15.7 * GB, 4, 4),    # shards over all four
    (33.7 * GB, 15.7 * GB, 15.7 * GB, 2, 2),    # the shard fits exactly
    (33.7 * GB, 4.22 * GB, None, 4, 1),         # no limit known (CPU)
])
def test_layout_choice(local, shard, limit, ndev, want):
    assert layout_devices(local, shard, limit, ndev) == want


@pytest.mark.parametrize("local,shard,limit,ndev", [
    (123.7 * GB, 31 * GB, 15.7 * GB, 4),        # not even sharded
    (33.7 * GB, 16.9 * GB, 15.7 * GB, 1),       # one device only
])
def test_layout_choice_refuses_what_cannot_fit(local, shard, limit, ndev):
    with pytest.raises(ValueError, match="does not fit"):
        layout_devices(local, shard, limit, ndev)
