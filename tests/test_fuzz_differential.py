"""Seeded differential fuzz harness.

Each seed generates one random graph -- alternating between an
adversarial uniform family (self-loops, parallel edges that the CSR
builder ⊕-dedupes, isolated vertices, n never tile-aligned) and
`make_power_law` hubs -- and pushes **every registered algebra** through
the execution layers against the numpy reference oracles:

  * FlipEngine data mode, jnp relax path (frontier-compacted fixpoint)
  * FlipEngine op mode, jnp relax path (full-sweep classic-CGRA)
  * Pallas kernel body in interpret mode (rotated: one algebra per seed,
    so the slow path still covers every algebra across the seed corpus)
  * the asynchronous cycle simulator (rotated over the expressible
    algebras, on the self-loop-free power-law family)

then drives a random mutation sequence (inserts / deletes / reweights,
including self-loop and parallel-edge upserts) through the incremental
engines: after every batch the delta-driven warm `execute` result must be
bit-for-bit the from-scratch run on the mutated graph and match the
oracle, and the incrementally rebuilt block layout must equal a full
rebuild.

Failures print a minimal repro: the seed, the generated graph's
parameters, and the exact pytest command that replays the case.

Seed count: 50 by default (~ISSUE spec); `FUZZ_SEEDS=5` is the CI smoke
setting, and any larger value soaks further.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ALGOS, SIM_ALGOS, VEC_ALGOS, np_contract, oracle

from repro.algebra import ALGEBRAS
from repro.core import PROGRAMS, compile_mapping, simulate
from repro.core.engine import FlipEngine
from repro.graphs import Graph, make_power_law, reference
from repro.kernels.frontier import build_blocks, frontier_relax

SEEDS = range(int(os.environ.get("FUZZ_SEEDS", "50")))
TILE = 16
# vertex counts are drawn from a small fixed set (never tile-aligned) so
# the jit cache sees a bounded family of shapes across the whole corpus
NS_UNIFORM = (17, 23, 33, 41)
NS_POWER = (19, 27, 35, 45)


def _random_uniform_graph(rng):
    """Adversarial uniform-random graph: endpoints drawn with
    replacement, so self-loops and parallel edges (⊕-deduped by
    `Graph.from_edges`) occur, and nothing guarantees connectivity --
    isolated vertices and unreachable components stay in."""
    n = int(rng.choice(NS_UNIFORM))
    m = int(rng.integers(n, 4 * n))
    u = rng.integers(0, n, size=m)
    v = rng.integers(0, n, size=m)
    w = rng.integers(1, 9, size=m).astype(float)
    directed = bool(rng.integers(2))
    return Graph.from_edges(n, list(zip(u, v)), list(w),
                            directed=directed)


def _random_batch(g, rng, k=4):
    """Random mutation batch: inserts (self-loops allowed), deletes of
    existing edges, reweights of existing edges -- all dyadic weights so
    bit-exact warm-vs-scratch comparison is meaningful."""
    eu = g.edge_sources()
    batch = []
    for _ in range(k):
        kind = int(rng.integers(3)) if g.m else 0
        if kind == 0:
            batch.append((int(rng.integers(g.n)), int(rng.integers(g.n)),
                          float(rng.integers(1, 9))))
        else:
            i = int(rng.integers(g.m))
            u, v = int(eu[i]), int(g.indices[i])
            batch.append((u, v, None) if kind == 1
                         else (u, v, float(rng.integers(1, 9))))
    return batch


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_differential(seed):
    rng = np.random.default_rng(seed)
    if seed % 2:
        g = _random_uniform_graph(rng)
    else:
        n = int(rng.choice(NS_POWER))
        g = make_power_law(n, int(rng.integers(2 * n, 4 * n)), seed=seed)
    src = int(rng.integers(g.n))
    repro = (f"repro: FUZZ_SEEDS={seed + 1} python -m pytest "
             f"'tests/test_fuzz_differential.py::test_fuzz_differential"
             f"[{seed}]' | graph: n={g.n} m={g.m} "
             f"directed={g.directed} family="
             f"{'uniform' if seed % 2 else 'power_law'} src={src}")

    interp_algo = ALGOS[seed % len(ALGOS)]
    engines, results = {}, {}
    for algo in ALGOS:
        ref = oracle(algo, g, src)
        for mode in ("data", "op"):
            eng = FlipEngine.build(g, algo, tile=TILE, mode=mode,
                                   relax_mode="jnp")
            got, steps = eng.execute(src)
            assert ALGEBRAS[algo].results_match(got, ref), \
                f"{algo} {mode}/jnp diverged from oracle; {repro}"
            if mode == "data":
                engines[algo], results[algo] = eng, got
        if algo == interp_algo:
            got, _ = FlipEngine.build(g, algo, tile=8, mode="data",
                                      relax_mode="interpret").execute(src)
            assert ALGEBRAS[algo].results_match(got, ref), \
                f"{algo} data/interpret diverged from oracle; {repro}"

    # cycle simulator: self-loop-free family only (the packet model, like
    # the paper's fabric, assumes simple edges)
    if seed % 2 == 0 and SIM_ALGOS:
        algo = SIM_ALGOS[seed % len(SIM_ALGOS)]
        m = compile_mapping(g, effort=0, seed=0)
        r = simulate(m, PROGRAMS[algo], src=src)
        assert ALGEBRAS[algo].results_match(r.attrs, oracle(algo, g, src)), \
            f"{algo} sim diverged from oracle; {repro}"

    # random mutation sequence through the incremental engines
    g_cur = g
    for step in range(2):
        batch = _random_batch(g_cur, rng)
        g_next = g_cur.apply_updates(batch)
        for algo in ALGOS:
            eng2, delta = engines[algo].apply_updates(g_next, batch)
            inc, _ = eng2.execute(
                src, warm=eng2.resolve_warm(results[algo], delta))
            scr, _ = eng2.execute(src)
            np.testing.assert_array_equal(
                inc, scr,
                err_msg=f"{algo} incremental != scratch after mutation "
                        f"batch {step} {batch}; {repro}")
            assert ALGEBRAS[algo].results_match(
                inc, oracle(algo, g_next, src)), \
                f"{algo} diverged from oracle after mutation batch " \
                f"{step} {batch}; {repro}"
            engines[algo], results[algo] = eng2, inc
        # structural spot-check (rotated algebra): incremental layout ==
        # full rebuild, covering delete/reinsert/shape-change paths
        full = build_blocks(g_next, interp_algo, tile=TILE)
        np.testing.assert_array_equal(
            np.asarray(engines[interp_algo].bg.blocks),
            np.asarray(full.blocks),
            err_msg=f"{interp_algo} incremental layout != rebuild after "
                    f"batch {step} {batch}; {repro}")
        g_cur = g_next


# ------------------------------------------------------------------ #
# vector-state fuzz: random (T, d) feature blocks through every algebra
# ------------------------------------------------------------------ #
def _random_features(rng, sr, shape, family):
    """Random feature state inside the semiring's domain: a bounded
    uniform family and a heavy-tailed power-law family (Pareto), the
    latter stressing the ⊕-reduce with magnitudes spanning decades."""
    if sr.name == "or_and":
        return (rng.random(shape) < 0.5).astype(np.float32)
    if family == "uniform":
        vals = rng.uniform(0.25, 8.0, shape)
    else:
        vals = 0.25 + rng.pareto(1.5, shape)
    return vals.astype(np.float32)


@pytest.mark.parametrize("seed", SEEDS)
def test_fuzz_features(seed):
    """d > 1 differential: one frontier_relax step on random feature
    state vs the per-tile numpy contraction oracle for **every**
    algebra, plus the vector programs' full fixpoints vs their (n, d)
    oracles, on the same alternating graph families as the scalar
    fuzz."""
    rng = np.random.default_rng(10_000 + seed)
    family = "uniform" if seed % 2 else "power_law"
    if seed % 2:
        g = _random_uniform_graph(rng)
    else:
        n = int(rng.choice(NS_POWER))
        g = make_power_law(n, int(rng.integers(2 * n, 4 * n)), seed=seed)
    src = int(rng.integers(g.n))
    d = int(rng.choice((2, 4, 8)))
    repro = (f"repro: FUZZ_SEEDS={seed + 1} python -m pytest "
             f"'tests/test_fuzz_differential.py::test_fuzz_features"
             f"[{seed}]' | graph: n={g.n} m={g.m} "
             f"directed={g.directed} family={family} src={src} d={d}")

    interp_algo = ALGOS[seed % len(ALGOS)]
    for algo in ALGOS:
        sr = ALGEBRAS[algo].semiring
        bg = build_blocks(g, algo=algo, tile=TILE)
        shape = (bg.ntiles, bg.tile, d)
        sv = _random_features(rng, sr, shape, family)
        carry = _random_features(rng, sr, shape, family)
        blocks = np.asarray(bg.blocks)
        bsrc, bdst = np.asarray(bg.bsrc), np.asarray(bg.bdst)
        want = carry.copy()
        for i in range(len(bsrc)):
            c = np_contract(sr, sv[bsrc[i]], blocks[i])
            want[bdst[i]] = sr.add_np(want[bdst[i]], c)
        modes = ("jnp", "interpret") if algo == interp_algo else ("jnp",)
        for mode in modes:
            got = np.asarray(frontier_relax(
                jnp.asarray(sv), jnp.asarray(carry), bg, mode=mode,
                feature_dim=d))
            if sr.idempotent:
                np.testing.assert_array_equal(
                    got, want,
                    err_msg=f"{algo} {mode} d={d} feature relax diverged "
                            f"from numpy oracle; {repro}")
            else:
                np.testing.assert_allclose(
                    got, want, rtol=1e-4, atol=1e-4,
                    err_msg=f"{algo} {mode} d={d} feature relax diverged "
                            f"from numpy oracle; {repro}")

    # vector programs: full engine fixpoint vs the (n, d) oracle,
    # rotated so each seed runs one of them (labelprop fixpoints are
    # long) and the corpus covers both
    algo = VEC_ALGOS[seed % len(VEC_ALGOS)]
    eng = FlipEngine.build(g, algo, tile=TILE, relax_mode="jnp")
    got, _ = eng.execute(src)
    assert ALGEBRAS[algo].results_match(got, oracle(algo, g, src)), \
        f"{algo} engine diverged from (n, d) oracle; {repro}"


# ------------------------------------------------------------------ #
# fault-injection fuzz: seeded chaos schedules against the server
# ------------------------------------------------------------------ #
CHAOS_SEEDS = range(int(os.environ.get("FUZZ_CHAOS_SEEDS", "8")))


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_fuzz_chaos_serving(seed):
    """Seeded fault schedules (backend raise + NaN poison at random
    (dispatch, rung) ordinals) against a serving stream with
    interleaved updates: zero lost requests, typed errors on every
    failure, oracle-exact results on every success. Each seed draws its
    own graph, request stream, and fault schedule; `FUZZ_CHAOS_SEEDS`
    scales the corpus (CI smoke uses a smaller value)."""
    from repro.launch.serve_graph import GraphServer
    from repro.resilience import FaultInjector, FlipError

    rng = np.random.default_rng(20_000 + seed)
    n = int(rng.choice(NS_POWER))
    g = make_power_law(n, int(rng.integers(2 * n, 4 * n)), seed=seed)
    algos = ["bfs", "sssp"]
    n_req = 16
    repro = (f"repro: FUZZ_CHAOS_SEEDS={seed + 1} python -m pytest "
             f"'tests/test_fuzz_differential.py::test_fuzz_chaos_serving"
             f"[{seed}]' | graph: n={g.n} m={g.m}")

    inj = FaultInjector.random(seed=30_000 + seed, dispatches=12,
                               rate=0.4)
    srv = GraphServer(g, batch=4, tile=TILE, fault_injector=inj)
    g_cur, reqs, snaps = g, [], []
    for i in range(n_req):
        if i == n_req // 2 and g_cur.m:       # one mid-stream mutation
            eu = g_cur.edge_sources()
            j = int(rng.integers(g_cur.m))
            batch = [(int(eu[j]), int(g_cur.indices[j]),
                      float(g_cur.weights[j]) * 0.5)]
            srv.update(batch)
            g_cur = g_cur.apply_updates(batch)
        reqs.append(srv.submit(algos[int(rng.integers(len(algos)))],
                               int(rng.integers(g.n))))
        snaps.append(g_cur)
    srv.drain()

    assert all(r.done for r in reqs), f"server lost requests; {repro}"
    for r, g_snap in zip(reqs, snaps):
        if r.error is not None:
            assert isinstance(r.error, FlipError), \
                f"untyped failure {r.error!r}; {repro}"
        if r.ok:
            assert ALGEBRAS[r.algo].results_match(
                r.result, oracle(r.algo, g_snap, r.src)), \
                f"{r.algo} src={r.src} rung={r.rung} diverged; {repro}"


# ------------------------------------------------------------------ #
# continuous-batching traffic fuzz: Zipf sources, mixed algebras,
# interleaved mutations, deterministic replay
# ------------------------------------------------------------------ #
TRAFFIC_SEEDS = range(int(os.environ.get("FUZZ_TRAFFIC_SEEDS", "8")))


def _zipf_src(rng, n):
    """Zipf-distributed source id (clipped to the vertex set): the
    serving-traffic shape -- a few hot sources dominate, exercising
    the result cache and warm-start reuse."""
    return int(min(rng.zipf(1.4) - 1, n - 1))


@pytest.mark.parametrize("seed", TRAFFIC_SEEDS)
def test_fuzz_traffic(seed):
    """Seeded Zipf traffic through the continuous-batching scheduler:
    mixed algebras (scalar + vector state), hot repeated sources, and
    interleaved monotone mutation batches, all on a virtual clock.
    Every served result -- cold, cache hit, or warm-started -- must
    match the numpy oracle for the graph version current at its
    submission, zero requests may be lost, and the full transcript
    must replay bit-for-bit on a second identically-seeded server.
    `FUZZ_TRAFFIC_SEEDS` scales the corpus (CI smoke uses fewer)."""
    from repro.serving import AsyncGraphServer, VirtualClock

    rng0 = np.random.default_rng(40_000 + seed)
    n = int(rng0.choice(NS_POWER))
    g = make_power_law(n, int(rng0.integers(2 * n, 4 * n)), seed=seed)
    algos = ["bfs", "sssp", "wcc", "pagerank", "multi_bfs"]
    n_req = 20
    repro = (f"repro: FUZZ_TRAFFIC_SEEDS={seed + 1} python -m pytest "
             f"'tests/test_fuzz_differential.py::test_fuzz_traffic"
             f"[{seed}]' | graph: n={g.n} m={g.m}")

    def run():
        rng = np.random.default_rng(50_000 + seed)
        srv = AsyncGraphServer(
            g, batch=3, tile=TILE, relax_mode="jnp",
            segment_steps=int(rng.integers(1, 5)), cache_capacity=16,
            clock=VirtualClock())
        g_cur, reqs, snaps = g, [], []
        for i in range(n_req):
            if i and i % 7 == 0 and g_cur.m:
                # ⊕-improving reweights + one insert: monotone, so
                # warm-start reuse stays in play across versions
                eu = g_cur.edge_sources()
                idx = rng.choice(g_cur.m, size=min(3, g_cur.m),
                                 replace=False)
                batch = [(int(eu[j]), int(g_cur.indices[j]),
                          float(g_cur.weights[j]) * 0.5) for j in idx]
                batch.append((int(rng.integers(n)),
                              int(rng.integers(n)), 1.0))
                srv.update(batch)
                g_cur = g_cur.apply_updates(batch)
            reqs.append(srv.submit(
                algos[int(rng.integers(len(algos)))], _zipf_src(rng, n)))
            snaps.append(g_cur)
            if rng.random() < 0.3:    # partial progress between submits
                srv.pump()
        srv.drain()
        return srv, reqs, snaps

    srv, reqs, snaps = run()
    assert all(r.done for r in reqs), f"scheduler lost requests; {repro}"
    for r, g_snap in zip(reqs, snaps):
        assert r.ok, f"{r.algo} src={r.src} failed: {r.error!r}; {repro}"
        if ALGEBRAS[r.algo].feature_dim == 1:
            ref = oracle(r.algo, g_snap, r.src)
        else:
            ref, _ = reference.run(r.algo, g_snap, r.src)
        assert ALGEBRAS[r.algo].results_match(r.result, ref), \
            (f"{r.algo} src={r.src} hit={r.cache_hit} "
             f"warm={r.warm_started} diverged; {repro}")

    # deterministic replay: a second identically-seeded run produces
    # the exact same transcript, scheduling decisions included
    _, reqs2, _ = run()
    t1 = [(r.req_id, r.algo, r.src, r.slot, r.admit_window,
           r.queue_wait_s, r.service_s, r.steps, r.cache_hit,
           r.warm_started,
           None if r.result is None else r.result.tobytes())
          for r in reqs]
    t2 = [(r.req_id, r.algo, r.src, r.slot, r.admit_window,
           r.queue_wait_s, r.service_s, r.steps, r.cache_hit,
           r.warm_started,
           None if r.result is None else r.result.tobytes())
          for r in reqs2]
    assert t1 == t2, f"transcript replay diverged; {repro}"
