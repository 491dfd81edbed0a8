"""The unified query API (`repro.api` / `import flip`).

The redesign's contract, proven here:

  * `flip.compile(graph, program, plan).query(srcs)` is bit-exact vs
    the engine's own `FlipEngine.execute` -- solo, batched,
    distributed, and incremental-recompute -- across all registered
    algebras x {jnp, interpret} relax modes;
  * `ExecutionPlan` validation rejects inconsistent knob combinations
    at compile time;
  * a `Program`-defined custom algorithm (algebra + oracle registered
    atomically in one call) round-trips through the engine, the
    `reference.run` dispatch, and `QueryResult.check`.
"""
import warnings

import numpy as np
import pytest
from conftest import ALGOS, assert_close, oracle

import flip
from repro.algebra import ALGEBRAS, Semiring, VertexAlgebra
from repro.core.engine import FlipEngine, WarmStart
from repro.graphs import make_power_law, make_synthetic, reference


def _monotone_batch(g):
    """⊕-improving reweights: halve the first three edge weights."""
    eu = g.edge_sources()
    return [(int(eu[i]), int(g.indices[i]), float(g.weights[i]) * 0.5)
            for i in range(3)]


# --------------------------------------------------------------------- #
# bit-exact parity: the session surface vs the engine's execute
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("relax", ["jnp", "interpret"])
def test_query_parity_solo_and_batch(algo, relax):
    """query(src) == execute(src) and query(srcs) == execute(srcs),
    bit-for-bit, for every algebra on both CPU kernel paths."""
    g = make_synthetic(40, 110, seed=3)
    cq = flip.compile(g, algo,
                      flip.ExecutionPlan(tile=16, relax_mode=relax))
    r = cq.query(2)
    out, steps = cq.engine.execute(2)
    np.testing.assert_array_equal(r.attrs, out)
    assert r.steps == steps
    assert_close(r.attrs, oracle(algo, g, 2), algo, "solo")

    srcs = np.array([2, 7, 19])
    rb = cq.query(srcs)
    outs, steps = cq.engine.execute(srcs)
    np.testing.assert_array_equal(rb.attrs, outs)
    np.testing.assert_array_equal(rb.steps, steps)
    assert rb.check()


@pytest.mark.parametrize("algo", ["sssp", "pagerank"])
def test_query_parity_distributed(algo):
    """A distributed plan routes through the shard_map fixpoint and is
    bit-exact vs the engine's distributed execute and vs the local
    path."""
    g = make_synthetic(48, 140, seed=5)
    plan = flip.ExecutionPlan(tile=16, relax_mode="jnp",
                              distributed=True)
    cq = flip.compile(g, algo, plan)
    assert cq.plan.distributed
    r = cq.query(3)
    out, steps = cq.engine.execute(3, distributed=True)
    np.testing.assert_array_equal(r.attrs, out)
    assert r.steps == steps
    local = flip.compile(
        g, algo, flip.ExecutionPlan(tile=16, relax_mode="jnp")).query(3)
    np.testing.assert_array_equal(r.attrs, local.attrs)


@pytest.mark.parametrize("algo", ["sssp", "bfs", "widest"])
def test_query_parity_incremental(algo):
    """session.update + query(warm=prev) == warm execute == scratch,
    bit-for-bit (the incremental-recompute leg of the old surface)."""
    g = make_power_law(48, 140, seed=7)
    cq = flip.compile(g, algo,
                      flip.ExecutionPlan(tile=16, relax_mode="jnp"))
    prev = cq.query(3)
    batch = _monotone_batch(g)
    cq2, delta = cq.update(batch)
    warm = cq2.query(3, warm=prev)
    eng_out, eng_steps = cq2.engine.execute(
        3, warm=cq2.engine.resolve_warm(prev.attrs, delta))
    np.testing.assert_array_equal(warm.attrs, eng_out)
    assert warm.steps == eng_steps
    scratch = cq2.query(3)
    np.testing.assert_array_equal(warm.attrs, scratch.attrs)
    if delta.monotone and ALGEBRAS[algo].kind == "monotone":
        assert warm.steps <= scratch.steps
    assert_close(warm.attrs, oracle(algo, cq2.graph, 3), algo, "incr")


def test_query_nonmonotone_update_falls_back_to_scratch():
    """warm='auto' on a delete (non-monotone delta): query(warm=...)
    silently recomputes from scratch, as `resolve_warm` does."""
    g = make_power_law(48, 140, seed=2)
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(tile=16, relax_mode="jnp"))
    prev = cq.query(1)
    eu = g.edge_sources()
    cq2, delta = cq.update([(int(eu[0]), int(g.indices[0]), None)])
    assert not delta.monotone
    warm = cq2.query(1, warm=prev)
    scratch = cq2.query(1)
    np.testing.assert_array_equal(warm.attrs, scratch.attrs)
    assert warm.steps == scratch.steps          # no resume happened


def test_bucketed_dispatch_is_bitexact():
    """plan.batch > 0: padded fixed-size buckets return exactly the
    solo-run rows (the serving policy, now a plan knob) -- including a
    short sequence, which pads to one full bucket rather than tracing a
    tail-sized executable."""
    g = make_synthetic(40, 110, seed=9)
    cq = flip.compile(g, "bfs",
                      flip.ExecutionPlan(tile=16, relax_mode="jnp",
                                         batch=4))
    srcs = np.array([3, 11, 0, 27, 5, 19])     # 6 queries -> 2 dispatches
    r = cq.query(srcs)
    assert r.dispatches == 2
    assert r.attrs.shape == (6, g.n)
    solo = flip.compile(g, "bfs",
                        flip.ExecutionPlan(tile=16, relax_mode="jnp"))
    for b, s in enumerate(srcs):
        np.testing.assert_array_equal(r.attrs[b],
                                      solo.query(int(s)).attrs)
    short = cq.query(np.array([3, 11]))        # < B: one padded bucket
    assert short.dispatches == 1
    assert short.attrs.shape == (2, g.n)
    np.testing.assert_array_equal(short.attrs, r.attrs[:2])
    empty = cq.query(np.array([], dtype=np.int64))   # degenerate batch
    assert empty.attrs.shape == (0, g.n)
    assert empty.steps.shape == (0,)


# --------------------------------------------------------------------- #
# the engine's warm start
# --------------------------------------------------------------------- #
def test_engine_warm_execute_matches_scratch():
    """After a monotone update, `execute(src, warm=resolve_warm(prev,
    delta))` is bit-for-bit a from-scratch `execute` on the updated
    graph, and takes no more steps."""
    g = make_synthetic(40, 110, seed=0)
    eng = FlipEngine.build(g, "sssp", tile=16, relax_mode="jnp")
    prev, _ = eng.execute(0)
    batch = _monotone_batch(g)
    g2 = g.apply_updates(batch)
    eng2, delta = eng.apply_updates(g2, batch)
    warm = eng2.resolve_warm(prev, delta)
    assert delta.monotone and warm is not None
    out, steps = eng2.execute(0, warm=warm)
    scratch, scratch_steps = FlipEngine.build(g2, "sssp", tile=16,
                                              relax_mode="jnp").execute(0)
    np.testing.assert_array_equal(out, scratch)
    assert steps <= scratch_steps


# --------------------------------------------------------------------- #
# ExecutionPlan validation
# --------------------------------------------------------------------- #
def test_plan_rejects_bad_combos():
    with pytest.raises(ValueError, match="compact=True is inconsistent"):
        flip.ExecutionPlan(mode="op", compact=True).resolve()
    with pytest.raises(ValueError, match="plan.mode"):
        flip.ExecutionPlan(mode="dataa").resolve()
    with pytest.raises(ValueError, match="plan.relax_mode"):
        flip.ExecutionPlan(relax_mode="cuda").resolve()
    with pytest.raises(ValueError, match="plan.batch"):
        flip.ExecutionPlan(batch=-1).resolve()
    with pytest.raises(ValueError, match="plan.tile"):
        flip.ExecutionPlan(tile=0).resolve()
    with pytest.raises(ValueError, match="plan.warm"):
        flip.ExecutionPlan(warm="maybe").resolve()
    with pytest.raises(ValueError, match="plan.max_steps"):
        flip.ExecutionPlan(max_steps=0).resolve()
    # warm='always' is unsound for residual algebras
    with pytest.raises(ValueError, match="monotone algebra"):
        flip.ExecutionPlan(warm="always").resolve(ALGEBRAS["pagerank"])


def test_plan_resolution_collapses_auto():
    plan = flip.ExecutionPlan().resolve()
    assert plan.relax_mode in ("jnp", "pallas")     # backend-concrete
    assert plan.compact is True                     # data mode default
    assert flip.ExecutionPlan(mode="op").resolve().compact is False
    # a mesh implies distributed execution
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("data",))
    assert flip.ExecutionPlan(mesh=mesh).resolve().distributed
    # resolution is idempotent
    assert plan.resolve() == plan


def test_plan_warm_never_forbids_warm_queries():
    g = make_synthetic(40, 110, seed=1)
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(tile=16, relax_mode="jnp",
                                         warm="never"))
    prev = cq.query(0)
    cq2, _ = cq.update(_monotone_batch(g))
    with pytest.raises(ValueError, match="warm='never'"):
        cq2.query(0, warm=prev)


def test_plan_warm_always_rejects_unsound_resume():
    g = make_synthetic(40, 110, seed=1)
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(tile=16, relax_mode="jnp",
                                         warm="always"))
    prev = cq.query(0)
    eu = g.edge_sources()
    cq2, delta = cq.update([(int(eu[0]), int(g.indices[0]), None)])
    assert not delta.monotone
    with pytest.raises(ValueError, match="unsound"):
        cq2.query(0, warm=prev)


def test_warm_from_stale_graph_version_rejected():
    """A warm result older than the session's last update carries
    improvements the delta's seeds cannot re-derive: resuming from it
    must error, not silently return wrong attrs."""
    g = make_power_law(48, 140, seed=7)
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(tile=16, relax_mode="jnp"))
    prev = cq.query(3)
    eu = g.edge_sources()
    b1 = _monotone_batch(g)
    b2 = [(int(eu[9]), int(g.indices[9]), float(g.weights[9]) * 0.5)]
    cq2, _ = cq.update(b1)
    cq3, _ = cq2.update(b2)
    with pytest.raises(ValueError, match="pre-update graph version"):
        cq3.query(3, warm=prev)                # two updates stale
    mid = cq2.query(3, warm=prev)              # one update: fine
    fresh = cq3.query(3, warm=mid)             # stepwise: fine
    np.testing.assert_array_equal(fresh.attrs, cq3.query(3).attrs)
    # a warm result resumes only its own sources
    with pytest.raises(ValueError, match="same sources"):
        cq3.query(7, warm=mid)
    fan = cq3.query([3, 3], warm=mid)          # scalar fan-out: fine
    np.testing.assert_array_equal(fan.attrs[0], fresh.attrs)
    # (1, n) batched results fan out exactly like scalar ones
    mid_b = cq2.query([3], warm=None)
    fan_b = cq3.query([3, 3], warm=mid_b)
    np.testing.assert_array_equal(fan_b.attrs, fan.attrs)


def test_warm_without_update_delta_rejected():
    g = make_synthetic(40, 110, seed=1)
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(tile=16, relax_mode="jnp"))
    prev = cq.query(0)
    with pytest.raises(ValueError, match="no update delta"):
        cq.query(0, warm=prev)
    # ... but an explicit WarmStart resumes from arbitrary state
    r = cq.query(0, warm=WarmStart(prev.attrs, np.array([], np.int64)))
    np.testing.assert_array_equal(r.attrs, prev.attrs)
    assert r.steps == 0


def test_cli_alias_resolution():
    """--engine op folds into --engine jax --mode op with one warning;
    canonical spellings pass through silently."""
    with pytest.warns(DeprecationWarning, match="--engine op"):
        assert flip.resolve_cli_engine("op", "data") == ("jax", "op")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert flip.resolve_cli_engine("jax", "op") == ("jax", "op")
        assert flip.resolve_cli_engine("dist", "data") == ("dist", "data")
    plan = flip.plan_from_cli("dist", "data")
    assert plan.distributed
    with pytest.raises(ValueError, match="no ExecutionPlan"):
        flip.plan_from_cli("sim", "data")


# --------------------------------------------------------------------- #
# Program: one-call algorithm registration
# --------------------------------------------------------------------- #
def test_program_round_trip_engine_and_oracle():
    """One Program.define call registers algebra + oracle atomically:
    the engine runs it, reference.run dispatches to the user's oracle,
    and QueryResult.check closes the loop."""
    import heapq

    import jax
    import jax.numpy as jnp

    min_max = Semiring(
        name="min_max_api", zero=float("inf"), one=float("-inf"),
        add_np=np.minimum, mul_np=np.maximum,
        add_jnp=jnp.minimum, mul_jnp=jnp.maximum,
        add_reduce_jnp=jnp.min,
        segment_reduce_jnp=lambda x, s, n: jax.ops.segment_min(
            x, s, num_segments=n),
        idempotent=True,
    )

    @flip.Program.define("minimax_api", min_max, weight_rule="graph")
    def minimax_oracle(g, src):
        best = np.full(g.n, np.inf, dtype=np.float32)
        best[src] = -np.inf
        heap = [(-np.inf, src)]
        while heap:
            d, u = heapq.heappop(heap)
            if d > best[u]:
                continue
            for k in range(g.indptr[u], g.indptr[u + 1]):
                v = int(g.indices[k])
                cand = max(d, float(g.weights[k]))
                if cand < best[v]:
                    best[v] = np.float32(cand)
                    heapq.heappush(heap, (cand, v))
        return best

    prog = minimax_oracle                  # the decorator returns Program
    assert isinstance(prog, flip.Program)
    try:
        assert "minimax_api" in ALGEBRAS               # engine registry
        g = make_synthetic(40, 120, seed=9)
        ref, stats = reference.run("minimax_api", g, 2)  # oracle registry
        assert stats == {}
        # compile by name, by algebra, and by Program: all equivalent
        for spec in ("minimax_api", prog.algebra, prog):
            r = flip.compile(
                g, spec,
                flip.ExecutionPlan(tile=16, relax_mode="jnp")).query(2)
            assert_close(r.attrs, ref, "minimax_api", "round-trip")
            assert r.check()
    finally:
        prog.unregister()
    assert "minimax_api" not in ALGEBRAS
    assert reference.get_oracle("minimax_api") is None
    with pytest.raises(ValueError, match="unknown algorithm"):
        reference.run("minimax_api", g, 2)


def test_program_define_without_register():
    """register=False compiles locally without touching the registries."""
    alg = VertexAlgebra("local_bfs", ALGEBRAS["bfs"].semiring,
                        weight_rule="hop")
    prog = flip.Program.define(algebra=alg,
                               oracle=lambda g, src: reference.bfs(g, src),
                               register=False)
    assert "local_bfs" not in ALGEBRAS
    g = make_synthetic(40, 110, seed=4)
    r = flip.compile(g, prog,
                     flip.ExecutionPlan(tile=16, relax_mode="jnp")).query(3)
    assert r.check()
    assert "local_bfs" not in ALGEBRAS


def test_program_get_wraps_builtins():
    prog = flip.Program.get("sssp")
    assert prog.name == "sssp" and prog.oracle is not None
    g = make_synthetic(30, 80, seed=0)
    np.testing.assert_array_equal(prog.reference(g, 1),
                                  oracle("sssp", g, 1))
    with pytest.raises(ValueError, match="unknown algorithm"):
        flip.Program.get("nope")
    with pytest.raises(TypeError, match="program must be"):
        flip.Program.of(42)


# --------------------------------------------------------------------- #
# serving: sessions cached by fingerprint + plan
# --------------------------------------------------------------------- #
def test_server_caches_sessions_by_fingerprint_and_plan():
    from repro.launch.serve_graph import GraphServer
    g = make_synthetic(40, 110, seed=5)
    srv = GraphServer(g, batch=2, tile=16, relax_mode="jnp")
    s1 = srv.session("sssp")
    assert srv.session("sssp") is s1               # cache hit
    srv.update(_monotone_batch(srv.graph))
    s2 = srv.session("sssp")
    assert s2 is not s1                            # new graph version
    assert s2.graph.fingerprint() == srv.graph.fingerprint()
    r = srv.serve([("sssp", 3)])[0]
    assert ALGEBRAS["sssp"].results_match(
        r.result, oracle("sssp", srv.graph, 3))
    # wholesale graph swaps supersede, not accumulate: one session per
    # algebra survives no matter how many versions were served
    for seed in (11, 12, 13):
        srv.graph = make_synthetic(40, 110, seed=seed)
        srv.serve([("sssp", 1)])
    assert len([k for k in srv._sessions if k[0] == "sssp"]) == 1
