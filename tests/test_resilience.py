"""Serving-layer resilience: taxonomy, budgets, ladder, shedding, chaos.

The contract under test (docs/RESILIENCE.md):

  * every failure is TYPED (`FlipError` subclass) and attached to the
    request that caused it -- a failing request can never take down its
    bucket, its stream, or the server;
  * budget-stopped fixpoints (`max_steps` / `deadline_s`) come back as
    FLAGGED partials (`converged=False` + typed error), never silent
    truncations -- whether the fixpoint program runs one step per call
    (a deadline) or as one whole loop;
  * the degradation ladder (pallas->jnp, compact->dense) is EXACT:
    a degraded response is bit-for-bit the primary response;
  * admission control sheds the newest request with `CapacityExceeded`;
  * the chaos replay: a seeded fault schedule (backend raise, NaN
    poison, step stall, plus deadline/budget pressure) over a mixed
    query+update stream loses zero requests and keeps every success
    oracle-exact.
"""
import time

import numpy as np
import pytest
from conftest import oracle

import flip
from repro.algebra import ALGEBRAS
from repro.distributed.health import HeartbeatMonitor
from repro.graphs import make_power_law
from repro.launch.serve_graph import GraphServer
from repro.resilience import (BackendFailure, CapacityExceeded,
                              ConvergenceFailure, DeadlineExceeded,
                              FaultInjector, FaultSpec, FlipError,
                              InjectedFault, InvalidRequest, classify,
                              fallback_chain, finite_guard)

TILE = 16


@pytest.fixture(scope="module")
def g():
    return make_power_law(60, 180, seed=3)


# ------------------------------------------------------------------ #
# taxonomy
# ------------------------------------------------------------------ #
def test_error_taxonomy_shape():
    cases = [
        (InvalidRequest("bad", value=-1), "invalid_request", False),
        (CapacityExceeded("full", depth=3, limit=3),
         "capacity_exceeded", False),
        (DeadlineExceeded("late", deadline_s=1.0, elapsed_s=2.0),
         "deadline_exceeded", False),
        (ConvergenceFailure("partial", steps=5, max_steps=5),
         "convergence_failure", False),
        (BackendFailure("boom", rung=0), "backend_failure", True),
    ]
    codes = set()
    for err, code, retryable in cases:
        assert isinstance(err, FlipError)
        assert err.code == code
        assert err.retryable is retryable
        d = err.describe()
        assert d["code"] == code and d["type"] == type(err).__name__
        codes.add(code)
    assert len(codes) == 5          # codes are distinct machine ids
    # pre-taxonomy `except ValueError` call sites keep working
    assert isinstance(InvalidRequest("x"), ValueError)
    assert not isinstance(CapacityExceeded("x"), ValueError)


def test_classify_passthrough_and_wrap():
    e = InvalidRequest("bad")
    assert classify(e, rung=1) is e
    wrapped = classify(RuntimeError("xla died"), rung=2)
    assert isinstance(wrapped, BackendFailure) and wrapped.retryable
    assert wrapped.rung == 2
    assert isinstance(wrapped.cause, RuntimeError)


def test_finite_guard():
    finite_guard(np.array([1.0, np.inf, -np.inf]))   # ±inf legitimate
    with pytest.raises(BackendFailure):
        finite_guard(np.array([[1.0, np.nan], [2.0, 3.0]]))


# ------------------------------------------------------------------ #
# degradation ladder
# ------------------------------------------------------------------ #
def test_fallback_chain_is_validated_and_terminates():
    alg = ALGEBRAS["sssp"]
    chain = fallback_chain(flip.ExecutionPlan(mode="data", tile=TILE), alg)
    assert len(chain) >= 1
    keys = [p.key() for p in chain]
    assert len(keys) == len(set(keys))          # no duplicate rungs
    assert chain[-1].relax_mode == "jnp" and chain[-1].compact is False
    # a plan already at the bottom gets a one-rung chain
    bottom = flip.ExecutionPlan(mode="op", relax_mode="jnp",
                                compact=False, tile=TILE)
    assert len(fallback_chain(bottom, alg)) == 1


def test_degraded_rungs_bit_exact(g):
    """Every ladder rung returns bit-for-bit the primary result."""
    plan = flip.ExecutionPlan(mode="data", tile=TILE)
    chain = fallback_chain(plan, ALGEBRAS["sssp"])
    assert len(chain) >= 2
    srcs = [0, 7, 13, 21]
    ref = flip.compile(g, "sssp", chain[0]).query(srcs)
    for rung in chain[1:]:
        got = flip.compile(g, "sssp", rung).query(srcs)
        np.testing.assert_array_equal(got.attrs, ref.attrs)
        np.testing.assert_array_equal(got.steps, ref.steps)


def test_server_ladder_result_bit_exact_with_primary(g):
    """A fault-degraded server response equals the no-fault response."""
    srcs = list(range(8))
    clean = GraphServer(g, batch=4, tile=TILE)
    ok = [clean.submit("sssp", s) for s in srcs]
    clean.drain()
    inj = FaultInjector(specs=[FaultSpec(kind="raise", dispatch=d, rung=0)
                               for d in range(2)])
    srv = GraphServer(g, batch=4, tile=TILE, fault_injector=inj)
    degraded = [srv.submit("sssp", s) for s in srcs]
    srv.drain()
    assert all(r.ok and r.rung == 1 for r in degraded)
    assert len(inj.fired) == 2
    for a, b in zip(ok, degraded):
        np.testing.assert_array_equal(a.result, b.result)
        assert a.steps == b.steps
    assert srv.metrics.sum_counters("fallback.") == 2
    assert srv.stats()["resilience"]["fallbacks"] == 2


# ------------------------------------------------------------------ #
# truncated fixpoints: deadline-driven AND one whole-loop call
# ------------------------------------------------------------------ #
TRUNC_PLANS = [
    # a plan deadline runs the fixpoint program one step per call
    pytest.param(dict(mode="data", compact=True, relax_mode="jnp",
                      deadline_s=1e6),
                 id="deadline-compact"),
    # dense data mode runs the jitted while_loop fixpoint
    pytest.param(dict(mode="data", compact=False, relax_mode="jnp"),
                 id="jit-dense"),
    # op mode: full-sweep jitted while_loop
    pytest.param(dict(mode="op", relax_mode="jnp"), id="jit-op"),
]


@pytest.mark.parametrize("knobs", TRUNC_PLANS)
def test_truncated_fixpoint_flagged_not_silent(g, knobs):
    cq = flip.compile(g, "sssp", flip.ExecutionPlan(tile=TILE, **knobs))
    srcs = [0, 7, 13, 21]
    base = cq.query(srcs)
    assert base.all_converged
    base.check()                       # oracle-exact when converged
    steps = np.atleast_1d(base.steps)
    cap = int(steps.max()) - 1
    assert cap >= 1, "fixture graph must need >= 2 steps"
    part = cq.query(srcs, max_steps=cap)
    want_conv = steps <= cap
    np.testing.assert_array_equal(np.atleast_1d(part.converged),
                                  want_conv)
    assert not part.all_converged
    # converged rows are bit-exact; the partial is flagged, and check()
    # refuses to certify it
    for b, conv in enumerate(want_conv):
        if conv:
            np.testing.assert_array_equal(part.attrs[b], base.attrs[b])
    with pytest.raises(ConvergenceFailure) as ei:
        part.check()
    assert "converge" in str(ei.value)
    # a budget >= the true step count changes nothing, bit-for-bit
    full = cq.query(srcs, max_steps=int(steps.max()))
    assert full.all_converged
    np.testing.assert_array_equal(full.attrs, base.attrs)


def test_per_query_budget_vector(g):
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(mode="data", tile=TILE))
    srcs = [0, 7, 13, 21]
    steps = np.atleast_1d(cq.query(srcs).steps)
    cap = int(steps.max()) - 1
    mixed = cq.query(srcs, max_steps=[cap, 10_000, cap, 10_000])
    conv = np.atleast_1d(mixed.converged)
    assert conv[1] and conv[3]
    np.testing.assert_array_equal(
        conv, [steps[0] <= cap, True, steps[2] <= cap, True])
    # None entries in a budget vector mean "plan default", same as the
    # scalar form -- never a cast error
    part = cq.query(srcs, max_steps=[cap, None, cap, None])
    np.testing.assert_array_equal(np.atleast_1d(part.converged), conv)
    full = cq.query(srcs)
    assert np.array_equal(part.attrs[1], full.attrs[1])
    assert np.array_equal(part.attrs[3], full.attrs[3])


def test_deadline_expiry_flagged(g):
    cq = flip.compile(g, "sssp",
                      flip.ExecutionPlan(mode="data", tile=TILE))
    srcs = [0, 7, 13, 21]
    base = cq.query(srcs)
    tight = cq.query(srcs, deadline_s=1e-9)
    assert np.any(np.atleast_1d(tight.deadline_expired))
    assert not tight.all_converged
    with pytest.raises(ConvergenceFailure):
        tight.check()
    generous = cq.query(srcs, deadline_s=120.0)
    assert generous.all_converged
    assert not np.any(np.atleast_1d(generous.deadline_expired))
    np.testing.assert_array_equal(generous.attrs, base.attrs)


def test_plan_deadline_default_and_validation(g):
    plan = flip.ExecutionPlan(mode="data", tile=TILE, deadline_s=120.0)
    r = flip.compile(g, "bfs", plan).query([0, 5])
    assert r.all_converged
    assert plan.key() != flip.ExecutionPlan(mode="data", tile=TILE).key()
    with pytest.raises(ValueError):
        flip.ExecutionPlan(deadline_s=0.0).validate()
    with pytest.raises(ValueError):
        flip.ExecutionPlan(deadline_s=5.0, distributed=True).validate()


# ------------------------------------------------------------------ #
# request validation
# ------------------------------------------------------------------ #
def test_session_rejects_bad_sources(g):
    cq = flip.compile(g, "bfs", flip.ExecutionPlan(tile=TILE))
    for bad in (-1, g.n, [2, g.n + 7], [0, -3]):
        with pytest.raises(InvalidRequest) as ei:
            cq.query(bad)
        msg = str(ei.value)
        assert str(g.n) in msg          # names the valid range
    with pytest.raises(InvalidRequest):
        cq.query([0.5, 1])
    with pytest.raises(InvalidRequest):
        cq.query([0, 1], max_steps=0)
    with pytest.raises(InvalidRequest):
        cq.query([0, 1], deadline_s=-1.0)


def test_server_rejects_bad_requests_synchronously(g):
    srv = GraphServer(g, batch=4, tile=TILE)
    for bad in (-1, g.n, "seven"):
        with pytest.raises(InvalidRequest):
            srv.submit("bfs", bad)
    with pytest.raises(InvalidRequest):
        srv.submit("not_an_algo", 0)
    with pytest.raises(InvalidRequest):
        srv.submit("bfs", 0, max_steps=-5)
    with pytest.raises(InvalidRequest):
        srv.submit("bfs", 0, deadline_s=0.0)
    # nothing was enqueued by the rejected submissions
    assert srv.stats()["queue_depth"] == 0


# ------------------------------------------------------------------ #
# per-request failure isolation (the request-loss fix)
# ------------------------------------------------------------------ #
def test_no_request_loss_when_every_rung_fails(g):
    """All rungs poisoned: the bucket's requests each carry the typed
    error (never vanish), and the server keeps serving afterwards."""
    inj = FaultInjector(specs=[FaultSpec(kind="nan", dispatch=0, rung=r)
                               for r in range(4)])
    srv = GraphServer(g, batch=4, tile=TILE, fault_injector=inj)
    reqs = [srv.submit("bfs", i) for i in range(4)]
    assert all(r.done for r in reqs)
    assert all(isinstance(r.error, BackendFailure) for r in reqs)
    assert all(r.result is None for r in reqs)
    assert srv.failed == 4 and srv.stats()["failed"] == 4
    assert srv.stats()["queue_depth"] == 0       # bucket not stuck
    after = [srv.submit("bfs", i) for i in range(4)]
    assert all(r.ok for r in after)
    for r in after:
        assert ALGEBRAS["bfs"].results_match(r.result,
                                             oracle("bfs", g, r.src))


def test_failed_bucket_does_not_poison_other_algebras(g):
    inj = FaultInjector(specs=[FaultSpec(kind="nan", dispatch=0, rung=r,
                                         algo="bfs") for r in range(4)])
    srv = GraphServer(g, batch=2, tile=TILE, fault_injector=inj)
    bfs = [srv.submit("bfs", i) for i in range(2)]        # dispatch 0
    sssp = [srv.submit("sssp", i) for i in range(2)]      # dispatch 1
    assert all(isinstance(r.error, BackendFailure) for r in bfs)
    assert all(r.ok for r in sssp)


# ------------------------------------------------------------------ #
# admission control
# ------------------------------------------------------------------ #
def test_admission_sheds_newest_with_typed_error(g):
    srv = GraphServer(g, batch=8, tile=TILE, max_queue_depth=2)
    a = srv.submit("bfs", 0)
    b = srv.submit("bfs", 1)
    c = srv.submit("bfs", 2)             # newest -> shed
    assert isinstance(c.error, CapacityExceeded)
    assert c.error.depth == 2 and c.error.limit == 2
    assert c.done and not c.ok and c.result is None
    assert a.error is None and b.error is None
    srv.drain()
    assert a.ok and b.ok                 # accepted requests unharmed
    st = srv.stats()
    assert st["shed"] == 1 and st["resilience"]["shed"] == 1
    assert st["completed"] == 2


def test_admission_quota_is_per_algo(g):
    srv = GraphServer(g, batch=8, tile=TILE, quotas={"bfs": 1})
    srv.submit("bfs", 0)
    shed = srv.submit("bfs", 1)
    assert isinstance(shed.error, CapacityExceeded)
    other = srv.submit("sssp", 1)        # no quota -> accepted
    assert other.error is None
    srv.drain()


def test_resilience_off_disables_admission_and_ladder(g):
    srv = GraphServer(g, batch=4, tile=TILE, resilience=False,
                      max_queue_depth=1)
    reqs = [srv.submit("bfs", i) for i in range(4)]   # depth cap ignored
    assert all(r.ok for r in reqs)
    assert srv.shed == 0


# ------------------------------------------------------------------ #
# heartbeat monitor
# ------------------------------------------------------------------ #
def test_heartbeat_rearms_after_each_stall():
    hits = []
    hb = HeartbeatMonitor(timeout_s=0.08, poll_s=0.02,
                          on_stall=lambda: hits.append(1)).start()
    try:
        deadline = time.monotonic() + 5.0
        while hb.stall_count < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert hb.stalled and hb.stall_count == 1 and len(hits) == 1
        hb.beat()                         # re-arm
        assert not hb.stalled
        while hb.stall_count < 2 and time.monotonic() < deadline:
            time.sleep(0.01)              # second stall episode
        assert hb.stall_count == 2 and len(hits) == 2
    finally:
        hb.stop()


def test_heartbeat_stop_joins_and_silences_callback():
    hits = []
    hb = HeartbeatMonitor(timeout_s=0.05, poll_s=0.01,
                          on_stall=lambda: hits.append(1)).start()
    deadline = time.monotonic() + 5.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.01)
    hb.stop()                             # synchronous: joins the thread
    assert hb._thread is None
    n = len(hits)
    time.sleep(0.1)                       # several poll intervals
    assert len(hits) == n                 # no callback after stop()
    hb.stop()                             # idempotent


def test_stall_fault_trips_wired_heartbeat(g):
    hits = []
    hb = HeartbeatMonitor(timeout_s=0.1, poll_s=0.02,
                          on_stall=lambda: hits.append(1)).start()
    inj = FaultInjector(specs=[FaultSpec(kind="stall", dispatch=0,
                                         rung=0, stall_s=0.3)])
    srv = GraphServer(g, batch=2, tile=TILE, fault_injector=inj,
                      heartbeat=hb)
    try:
        reqs = [srv.submit("bfs", i) for i in range(2)]
        assert all(r.ok for r in reqs)    # the stall only delays
        assert hb.stall_count >= 1 and hits
        assert not hb.stalled             # post-dispatch beat re-armed
        assert srv.stats()["resilience"]["heartbeat_stalls"] >= 1
    finally:
        hb.stop()


# ------------------------------------------------------------------ #
# budgets through the server
# ------------------------------------------------------------------ #
def test_server_step_budget_partial_with_typed_error(g):
    srv = GraphServer(g, batch=4, tile=TILE)
    base = [srv.submit("sssp", i) for i in range(4)]
    srv.drain()
    cap = max(r.steps for r in base) - 1
    assert cap >= 1
    part = [srv.submit("sssp", i, max_steps=cap) for i in range(4)]
    srv.drain()
    hit = [r for r in part if not r.converged]
    assert hit
    for r in hit:
        assert isinstance(r.error, ConvergenceFailure)
        assert r.result is not None       # flagged partial attached
    for r in part:
        if r.converged:
            assert r.error is None
            np.testing.assert_array_equal(r.result, base[r.src].result)


def test_server_deadline_counts_queue_wait(g):
    srv = GraphServer(g, batch=4, tile=TILE)
    reqs = [srv.submit("sssp", i, deadline_s=1e-6) for i in range(4)]
    srv.drain()
    for r in reqs:
        assert r.deadline_expired
        assert isinstance(r.error, DeadlineExceeded)
        assert r.error.code == "deadline_exceeded"


# ------------------------------------------------------------------ #
# the chaos replay
# ------------------------------------------------------------------ #
def _chaos_stream(g0, algos, n_requests, n_updates, seed):
    """Deterministic mixed stream + the graph snapshot each query will
    be served against (submission order is graph-version order)."""
    rng = np.random.default_rng(seed)
    update_at = set(np.linspace(1, n_requests - 1, n_updates,
                                dtype=int).tolist())
    stream, snaps, g_cur = [], [], g0
    for i in range(n_requests):
        if i in update_at:
            eu = g_cur.edge_sources()
            k = int(rng.integers(1, 4))
            idx = rng.choice(g_cur.m, size=min(k, g_cur.m), replace=False)
            batch = [(int(eu[j]), int(g_cur.indices[j]),
                      float(g_cur.weights[j]) * 0.5) for j in idx]
            batch.append((int(rng.integers(g_cur.n)),
                          int(rng.integers(g_cur.n)),
                          float(rng.integers(1, 9))))
            stream.append(("update", batch))
            g_cur = g_cur.apply_updates(batch)
        stream.append((algos[int(rng.integers(len(algos)))],
                       int(rng.integers(g0.n))))
        snaps.append(g_cur)
    return stream, snaps


def test_chaos_replay_zero_loss_typed_errors_exact_successes(g):
    """>= 64 requests over 3 algebras with interleaved updates, under a
    seeded schedule spanning >= 4 failure modes: injected backend
    raises, NaN-poisoned results, a step stall (tripping the wired
    heartbeat), plus deadline and step-budget pressure. Invariants:
    zero lost requests, a typed error on every failure, and bit-exact
    oracle agreement on every success."""
    algos = ["bfs", "sssp", "pagerank"]
    n_req = 72
    stream, snaps = _chaos_stream(g, algos, n_req, n_updates=3, seed=11)

    specs = FaultInjector.random(seed=13, dispatches=40, algos=None,
                                 rate=0.3).specs
    # a nan fault pinned to every rung of one dispatch: guaranteed
    # ladder exhaustion -> per-request typed BackendFailure
    specs += [FaultSpec(kind="nan", dispatch=5, rung=r)
              for r in range(4)]
    # one stall long enough to trip the heartbeat
    specs += [FaultSpec(kind="stall", dispatch=8, rung=0, stall_s=0.3)]
    inj = FaultInjector(specs=specs, seed=13)
    hits = []
    hb = HeartbeatMonitor(timeout_s=0.1, poll_s=0.02,
                          on_stall=lambda: hits.append(1)).start()
    srv = GraphServer(g, batch=4, tile=TILE, fault_injector=inj,
                      heartbeat=hb)
    rng = np.random.default_rng(17)
    reqs = []
    try:
        qi = 0
        for algo, arg in stream:
            if algo == "update":
                srv.update(arg)
                continue
            kw = {}
            roll = rng.random()
            if roll < 0.08:
                kw["max_steps"] = 1          # step-budget pressure
            elif roll < 0.16:
                kw["deadline_s"] = 1e-6      # deadline pressure
            reqs.append(srv.submit(algo, arg, **kw))
            qi += 1
        srv.drain()
    finally:
        hb.stop()

    assert len(reqs) == n_req
    # --- zero lost requests: every submission reached an outcome ---
    assert all(r.done for r in reqs)
    # --- every failure is typed, every success oracle-exact ---
    n_ok = n_failed = 0
    kinds = {f["kind"] for f in inj.fired}
    for r, g_snap in zip(reqs, snaps):
        if r.error is not None:
            n_failed += 1
            assert isinstance(r.error, FlipError), r.error
            assert r.error.code in {
                "backend_failure", "deadline_exceeded",
                "convergence_failure", "capacity_exceeded"}
            if isinstance(r.error, (DeadlineExceeded,
                                    ConvergenceFailure)):
                assert r.result is not None    # flagged partial
        if r.ok:
            n_ok += 1
            assert ALGEBRAS[r.algo].results_match(
                r.result, oracle(r.algo, g_snap, r.src)), \
                (r.req_id, r.algo, r.src, r.rung)
    assert n_ok + n_failed == n_req
    assert n_ok > 0 and n_failed > 0
    # --- the schedule really exercised >= 4 failure modes ---
    error_codes = {r.error.code for r in reqs if r.error is not None}
    assert kinds >= {"raise", "nan", "stall"}, kinds
    assert len(error_codes) + len(kinds) >= 4
    assert hb.stall_count >= 1 and hits
    # --- counters line up: nothing double-counted, nothing dropped ---
    st = srv.stats()
    assert st["completed"] + srv.failed - sum(
        1 for r in reqs if r.error is not None and r.result is not None
    ) == n_req - st["shed"]
    assert st["resilience"]["faults_fired"] == len(inj.fired) > 0
    assert st["queue_depth"] == 0


def test_chaos_replay_is_deterministic(g):
    """Same seeds -> same fault schedule -> identical outcome vector."""
    def run():
        stream, _ = _chaos_stream(g, ["bfs", "sssp"], 16, 1, seed=23)
        inj = FaultInjector.random(seed=29, dispatches=10, rate=0.5)
        srv = GraphServer(g, batch=4, tile=TILE, fault_injector=inj)
        out = []
        for algo, arg in stream:
            if algo == "update":
                srv.update(arg)
            else:
                out.append(srv.submit(algo, arg))
        srv.drain()
        return ([None if r.error is None else r.error.code
                 for r in out],
                [r.rung for r in out], inj.fired)
    a, b = run(), run()
    assert a == b


def test_injected_fault_is_not_a_flip_error():
    """The injector's exception must look foreign to the taxonomy, so
    classify() exercises the real wrap path."""
    assert not isinstance(InjectedFault("x"), FlipError)
    wrapped = classify(InjectedFault("x"), rung=0)
    assert isinstance(wrapped, BackendFailure)
