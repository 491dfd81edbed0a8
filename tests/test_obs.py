"""Telemetry subsystem tests: tracing exactness, oracle frontier replay,
server stats, exporters, and the metrics registry.

The two load-bearing contracts:

  * tracing is EXACT -- `query(trace=True)` returns bit-identical attrs
    and step counts to the untraced run for every algebra, relax mode,
    and batching shape (the stat buffers are write-only extra outputs);
  * the recorded per-step stats are TRUE -- on a 1k power-law graph the
    traced BFS active-vertex counts equal a numpy frontier replay of
    the algorithm exactly, per step, on both local fixpoints.
"""
import json
import types

import numpy as np
import pytest

from conftest import ALGOS, SRCS8
from repro import api as flip
from repro.graphs import make_power_law, make_road_network
from repro.obs import (Counter, Histogram, MetricsRegistry, QueryTelemetry,
                       chrome_trace_from_result, from_sim,
                       write_chrome_trace)


def _plan(relax_mode="jnp", **kw):
    kw.setdefault("tile", 64)
    return flip.ExecutionPlan(relax_mode=relax_mode, **kw)


# ---------------------------------------------------------------- #
# tracing exactness across the whole execution matrix
# ---------------------------------------------------------------- #
@pytest.mark.parametrize("relax_mode", ["jnp", "interpret"])
@pytest.mark.parametrize("algo", ALGOS)
def test_trace_bit_exact_solo(algo, relax_mode):
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, algo, _plan(relax_mode))
    r = cq.query(3)
    rt = cq.query(3, trace=True)
    np.testing.assert_array_equal(np.asarray(r.attrs), np.asarray(rt.attrs))
    assert r.steps == rt.steps
    assert rt.telemetry is not None and r.telemetry is None
    d = rt.telemetry.dispatches[0]
    assert len(d.trace) == r.steps
    assert not d.truncated


@pytest.mark.parametrize("relax_mode", ["jnp", "interpret"])
@pytest.mark.parametrize("algo", ALGOS)
def test_trace_bit_exact_batched(algo, relax_mode):
    g = make_road_network(160, seed=0)
    srcs = SRCS8[:4]
    cq = flip.compile(g, algo, _plan(relax_mode))
    r = cq.query(srcs)
    rt = cq.query(srcs, trace=True)
    np.testing.assert_array_equal(np.asarray(r.attrs), np.asarray(rt.attrs))
    np.testing.assert_array_equal(np.asarray(r.steps),
                                  np.asarray(rt.steps))
    d = rt.telemetry.dispatches[0]
    assert len(d.trace) == int(np.asarray(r.steps).max())
    assert d.trace.active_vertices.shape == (len(d.trace), 4)


# ---------------------------------------------------------------- #
# per-step stats vs a numpy oracle frontier replay (BFS, 1k graph)
# ---------------------------------------------------------------- #
def _bfs_frontier_replay(g, src):
    """Replay BFS as the engine executes it: per step, relax every
    out-edge of the frontier; the improved destinations are the next
    frontier. Returns the per-step active-vertex counts (frontier size
    ENTERING each step) and the per-step frontier sets."""
    dist = np.full(g.n, np.inf)
    dist[src] = 0.0
    frontier = {src}
    counts, fronts = [], []
    while frontier:
        counts.append(len(frontier))
        fronts.append(set(frontier))
        nxt = set()
        for u in frontier:
            for v in g.indices[g.indptr[u]:g.indptr[u + 1]]:
                if dist[u] + 1.0 < dist[v]:
                    dist[v] = dist[u] + 1.0
                    nxt.add(int(v))
        frontier = nxt
    return counts, fronts, dist


@pytest.mark.parametrize("compact", [True, False])
def test_bfs_trace_matches_frontier_replay_1k(compact):
    g = make_power_law(1024, 4096, seed=0)
    src = 0
    counts, fronts, dist = _bfs_frontier_replay(g, src)

    cq = flip.compile(g, "bfs", flip.ExecutionPlan(compact=compact))
    r = cq.query(src)
    rt = cq.query(src, trace=True)
    # exactness first: tracing changes nothing
    np.testing.assert_array_equal(np.asarray(r.attrs), np.asarray(rt.attrs))
    assert r.steps == rt.steps == len(counts)

    tr = rt.telemetry.dispatches[0].trace
    np.testing.assert_array_equal(tr.active_vertices[:, 0],
                                  np.asarray(counts, np.int32))
    np.testing.assert_array_equal((~tr.converged[:, 0]),
                                  np.ones(len(counts), bool))

    # active tiles and fetched blocks follow from the frontier sets via
    # the engine's own placement (perm -> tile) and block list (bsrc)
    bg = cq.engine.bg
    perm = np.asarray(bg.perm)
    bsrc = np.asarray(bg.bsrc)
    nb = bsrc.shape[0]
    for t, front in enumerate(fronts):
        tiles = {int(perm[v]) // bg.tile for v in front}
        assert int(tr.active_tiles[t]) == len(tiles), t
        if compact:
            fetched = int(sum(int(b) in tiles for b in bsrc))
        else:
            fetched = nb
        assert int(tr.blocks_fetched[t]) == fetched, t
        assert int(tr.blocks_skipped[t]) == nb - fetched, t


def test_trace_identical_across_fixpoints():
    """The deadline-driven loop (one program call per step) and the one
    whole-loop call must record the same stats row for row (only
    step_wall_s is the step loop's own)."""
    g = make_power_law(512, 1536, seed=1)
    srcs = [0, 7]
    cq = flip.compile(g, "bfs", flip.ExecutionPlan())
    host = cq.query(srcs, trace=True, deadline_s=1e6)
    whole = cq.query(srcs, trace=True)
    np.testing.assert_array_equal(host.attrs, whole.attrs)
    np.testing.assert_array_equal(host.steps, whole.steps)
    th = host.telemetry.dispatches[0].trace
    tw = whole.telemetry.dispatches[0].trace
    np.testing.assert_array_equal(th.active_vertices, tw.active_vertices)
    np.testing.assert_array_equal(th.active_tiles, tw.active_tiles)
    np.testing.assert_array_equal(th.converged, tw.converged)
    assert th.step_wall_s is not None and len(th.step_wall_s) == len(th)
    assert (th.step_wall_s > 0).all()
    assert tw.step_wall_s is None        # while_loop has no per-step clock


def test_converged_mask_two_depths():
    """Batch of two sources with different convergence depths: the
    converged mask records exactly when each query froze, and its
    frontier stays empty afterwards."""
    g = make_power_law(512, 1536, seed=1)
    cq = flip.compile(g, "bfs", flip.ExecutionPlan())
    rt = cq.query([0, 5], trace=True)
    steps = np.asarray(rt.steps)
    tr = rt.telemetry.dispatches[0].trace
    assert len(tr) == steps.max()
    for t in range(len(tr)):
        for b in range(2):
            assert bool(tr.converged[t, b]) == (t >= steps[b]), (t, b)
            if t >= steps[b]:
                assert tr.active_vertices[t, b] == 0


def test_truncation_flag():
    g = make_power_law(512, 1536, seed=1)
    for compact in (True, False):
        cq = flip.compile(g, "bfs", flip.ExecutionPlan(compact=compact))
        r = cq.query(0)
        rt = cq.query(0, trace=2)
        assert r.steps > 2
        d = rt.telemetry.dispatches[0]
        assert d.truncated and len(d.trace) == 2
        assert r.steps == rt.steps       # execution itself is not cut


def test_trace_distributed_raises():
    # a distributed plan traces like a local one (bit-identical, with
    # the shards' per-step counts); only a deadline, which needs host
    # step boundaries, is still refused there
    g = make_road_network(96, seed=0)
    cq = flip.compile(g, "bfs", flip.ExecutionPlan(distributed=True))
    r, rt = cq.query(0), cq.query(0, trace=True)
    np.testing.assert_array_equal(r.attrs, rt.attrs)
    assert r.steps == rt.steps
    d = rt.telemetry.dispatches[0]
    assert len(d.trace) == r.steps and d.meta["devices"] == 1
    np.testing.assert_array_equal(d.trace.shard_live_max,
                                  d.trace.blocks_fetched)
    with pytest.raises(ValueError, match="distributed"):
        cq.query(0, deadline_s=5.0)


# ---------------------------------------------------------------- #
# compile-time attribution
# ---------------------------------------------------------------- #
def test_compile_s_first_dispatch_only():
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, "bfs", _plan())
    r1 = cq.query(3)
    r2 = cq.query(5)
    assert 0.0 < r1.compile_s <= r1.wall_s
    assert r1.compile_s == pytest.approx(r1.wall_s, rel=0.05)
    assert r2.compile_s == 0.0 and r2.wall_s > 0.0
    # tracing compiles its own executable (extended carry) -> first
    # traced dispatch is compile-attributed again; the second is not
    t1 = cq.query(3, trace=True)
    t2 = cq.query(3, trace=True)
    assert t1.compile_s > 0.0 and t2.compile_s == 0.0


def test_compile_s_bucketed():
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, "bfs", _plan(batch=4))
    srcs = list(range(10))
    r1 = cq.query(srcs)
    r2 = cq.query(srcs)
    assert r1.dispatches == 3 and r2.dispatches == 3
    assert r1.compile_s > 0.0 and r2.compile_s == 0.0


def test_bucketed_trace_collects_all_dispatches():
    g = make_road_network(160, seed=0)
    cq = flip.compile(g, "bfs", _plan(batch=4))
    rt = cq.query(list(range(10)), trace=True)
    assert len(rt.telemetry.dispatches) == rt.dispatches == 3
    # per-query step counts across dispatches match the solo runs
    solo = flip.compile(g, "bfs", _plan())
    for s in (0, 4, 9):
        assert int(np.asarray(rt.steps)[s]) == solo.query(s).steps
    hist = rt.telemetry.steps_histogram()
    assert sum(hist.values()) == 12      # 3 padded buckets of B=4


# ---------------------------------------------------------------- #
# server stats
# ---------------------------------------------------------------- #
def test_server_stats_shape_and_monotonicity():
    from repro.launch.serve_graph import GraphServer
    g = make_power_law(256, 768, seed=0)
    srv = GraphServer(g, batch=4, tile=64)
    rng = np.random.default_rng(0)
    stream = [(a, int(rng.integers(g.n)))
              for a in ["bfs", "sssp"] * 6]
    srv.serve(stream)
    s1 = srv.stats()
    json.dumps(s1)                       # JSON-ready all the way down
    assert s1["queue_depth"] == 0
    assert s1["completed"] == 12
    assert s1["sessions_cached"] == 2
    assert s1["session_cache"]["misses"] == 2
    assert s1["session_cache"]["hits"] >= 2
    h = s1["metrics"]["histograms"]
    for algo in ("bfs", "sssp"):
        for kind in ("latency_s", "queue_wait_s", "service_s", "steps"):
            hh = h[f"{kind}.{algo}"]
            assert hh["count"] == 6, (kind, algo)
        assert h[f"latency_s.{algo}"]["sum"] > 0.0
        assert h[f"latency_s.{algo}"]["p95"] >= h[f"latency_s.{algo}"]["p50"]
    assert h["compile_s"]["count"] >= 2   # one first dispatch per algebra

    # more traffic plus an update: counters only move up
    srv.serve([("bfs", 1), ("bfs", 2), ("update", [(0, 1, 0.5)]),
               ("sssp", 3)])
    s2 = srv.stats()
    assert s2["completed"] == 15
    assert s2["updates_applied"] == 1
    assert s2["metrics"]["counters"]["requests.completed"] == 15
    assert s2["session_cache"]["hits"] > s1["session_cache"]["hits"]
    assert s2["metrics"]["histograms"]["update_s"]["count"] == 1
    assert s2["metrics"]["histograms"]["rebuild_s"]["count"] == 2
    for k, v in s1["metrics"]["counters"].items():
        assert s2["metrics"]["counters"][k] >= v, k


# ---------------------------------------------------------------- #
# exporters
# ---------------------------------------------------------------- #
def test_chrome_trace_roundtrip(tmp_path):
    g = make_power_law(256, 768, seed=0)
    cq = flip.compile(g, "bfs", flip.ExecutionPlan())
    rt = cq.query([0, 5], trace=True)
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, rt)
    with open(path) as f:
        doc = json.load(f)
    assert doc == chrome_trace_from_result(rt)
    evs = doc["traceEvents"]
    steps = [e for e in evs if e["ph"] == "X"
             and e["name"].startswith("step ")]
    assert len(steps) == int(np.asarray(rt.steps).max())
    assert all(e["dur"] >= 0 and "args" in e for e in steps)
    assert {"active_vertices", "active_tiles", "blocks_fetched",
            "blocks_skipped", "live_queries"} <= set(steps[0]["args"])
    assert any(e["ph"] == "C" and e["name"] == "frontier" for e in evs)

    with pytest.raises(ValueError, match="trace=True"):
        chrome_trace_from_result(cq.query(0))


def test_telemetry_to_json_roundtrip():
    g = make_road_network(160, seed=0)
    rt = flip.compile(g, "bfs", _plan()).query(SRCS8[:4], trace=True)
    doc = json.loads(json.dumps(rt.telemetry.to_json()))
    assert doc["summary"]["traced_steps"] == \
        len(rt.telemetry.dispatches[0].trace)
    assert len(doc["dispatches"]) == 1
    tr = doc["dispatches"][0]["trace"]
    assert len(tr["active_vertices"]) == doc["summary"]["traced_steps"]


def test_from_sim_schema():
    sim = types.SimpleNamespace(
        parallelism_trace=[1, 3, 2, 0], cycles=4,
        attrs=np.zeros(16, np.float32), packets_delivered=9,
        edges_relaxed=6, avg_parallelism=1.5, max_parallelism=3, swaps=1)
    tele = from_sim(sim, freq_mhz=100.0)
    assert isinstance(tele, QueryTelemetry)
    d = tele.dispatches[0]
    assert d.backend == "sim" and d.batch == 1
    assert len(d.trace) == 4
    np.testing.assert_array_equal(d.trace.active_vertices[:, 0],
                                  [1, 3, 2, 0])
    assert d.trace.step_wall_s is not None
    assert tele.wall_s == pytest.approx(4 * 1e-6 / 100.0)
    assert d.meta["packets_delivered"] == 9
    json.dumps(tele.to_json())           # whole schema is JSON-clean


# ---------------------------------------------------------------- #
# metrics registry
# ---------------------------------------------------------------- #
def test_counter_monotone():
    c = Counter("x")
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_quantiles_exact_below_capacity():
    h = Histogram("lat", capacity=256)
    for v in range(100):                 # 0..99, exact (under capacity)
        h.observe(float(v))
    s = h.snapshot()
    assert s["count"] == 100 and s["min"] == 0.0 and s["max"] == 99.0
    assert s["mean"] == pytest.approx(49.5)
    assert abs(s["p50"] - 49.5) <= 1.0
    assert s["p95"] >= 93.0 and s["p99"] >= 97.0


def test_histogram_reservoir_bounded():
    h = Histogram("lat", capacity=64)
    for v in range(10_000):
        h.observe(float(v % 100))
    assert len(h._reservoir) == 64
    assert h.count == 10_000
    assert 0.0 <= h.quantile(0.5) <= 99.0


def test_registry_snapshot_and_exports(tmp_path):
    m = MetricsRegistry()
    m.counter("req").inc(3)
    m.gauge("depth").set(7)
    m.histogram("lat").observe(0.25)
    m.emit("dispatch", algo="bfs", batch=4)
    snap = m.snapshot()
    assert snap["counters"]["req"] == 3
    assert snap["gauges"]["depth"] == 7.0
    assert snap["histograms"]["lat"]["count"] == 1
    p = m.write_snapshot_json(str(tmp_path / "snap.json"))
    with open(p) as f:
        assert json.load(f) == snap
    p = m.write_events_jsonl(str(tmp_path / "events.jsonl"))
    with open(p) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == 1
    assert lines[0]["kind"] == "dispatch" and lines[0]["algo"] == "bfs"
    assert m.counter("req") is m.counter("req")   # get-or-create
