"""The program's spans in `jax.profiler`'s trace (`repro.obs.span`).

A query under the profiler leaves, on the calling thread, one
``flip.query`` span holding one ``flip.dispatch`` per engine dispatch,
each holding the dispatch's phases in the order they run; and the dense
fixpoint is one program with a stable name, ``jit_flip_fixpoint``.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api as flip
from repro import obs
from repro.core.engine import _layout_args
from repro.graphs import make_road_network

PHASES = ["flip.prepare", "flip.launch", "flip.wait", "flip.finalize"]


def _plan(**kw):
    kw.setdefault("tile", 32)
    kw.setdefault("relax_mode", "jnp")
    return flip.ExecutionPlan(**kw)


@pytest.fixture(scope="module")
def g():
    return make_road_network(160, seed=0)


def _profile(tmp_path, fn):
    """The ``flip.*`` host events recorded while `fn` runs, as dicts
    (name, start, end, attrs), by start time."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    return sorted(({"name": ev.name, "start": ev.start_ns,
                    "end": ev.start_ns + ev.duration_ns,
                    "attrs": dict(ev.stats)}
                   for p in pd.planes if p.name.startswith("/host:")
                   for line in p.lines for ev in line.events
                   if ev.name.startswith("flip.")),
                  key=lambda e: (e["start"], -e["end"]))


def _inside(outer, events, name=None):
    return [e for e in events if e is not outer
            and outer["start"] <= e["start"] and e["end"] <= outer["end"]
            and (name is None or e["name"] == name)]


def _warm(cq, *calls):
    for srcs, kw in calls:
        cq.query(srcs, **kw)


def test_solo_query_nests_dispatch_and_phases_in_order(g, tmp_path):
    cq = flip.compile(g, "sssp", _plan(compact=False))
    _warm(cq, (3, {}))
    ev = _profile(tmp_path, lambda: cq.query(3))
    query, = [e for e in ev if e["name"] == "flip.query"]
    assert query["attrs"] == {"qid": 1, "batch": 1}
    dispatch, = _inside(query, ev, "flip.dispatch")
    assert dispatch["attrs"] == {"qid": 1, "bucket": 0, "first": 0}
    phases = [e["name"] for e in _inside(dispatch, ev)]
    assert phases == PHASES
    # each phase ends before the next starts
    spans = _inside(dispatch, ev)
    assert all(a["end"] <= b["start"] for a, b in zip(spans, spans[1:]))


def test_sharded_compile_and_query_spans(g, tmp_path):
    # the slab build and placement is one set-up span; a sharded query
    # has the local dispatch's phases, traced ones the readback too
    ev = _profile(tmp_path, lambda: flip.compile(g, "sssp",
                                                 _plan(distributed=True)))
    shard, = [e for e in ev if e["name"] == "flip.shard"]
    assert shard["attrs"]["devices"] == 1
    cq = flip.compile(g, "sssp", _plan(distributed=True))
    _warm(cq, ([0, 5], {}), ([0, 5], {"trace": True}))
    ev = _profile(tmp_path / "query", lambda: (cq.query([0, 5]),
                                               cq.query([0, 5], trace=True)))
    plain, traced = [e for e in ev if e["name"] == "flip.dispatch"]
    assert [e["name"] for e in _inside(plain, ev)] == PHASES
    assert [e["name"] for e in _inside(traced, ev)] == PHASES + [
        "flip.telemetry"]


def test_bucketed_query_has_one_dispatch_per_bucket(g, tmp_path):
    cq = flip.compile(g, "sssp", _plan(compact=False, batch=2))
    srcs = [0, 5, 9, 17, 40]
    ev = _profile(tmp_path, lambda: cq.query(srcs))
    query, = [e for e in ev if e["name"] == "flip.query"]
    assert query["attrs"] == {"qid": 0, "batch": 5}
    dispatches = _inside(query, ev, "flip.dispatch")
    assert [d["attrs"] for d in dispatches] == [
        {"qid": 0, "bucket": b, "first": int(b == 0)} for b in range(3)]
    for d in dispatches:
        assert [e["name"] for e in _inside(d, ev)] == PHASES


def test_telemetry_span_only_with_trace(g, tmp_path):
    cq = flip.compile(g, "sssp", _plan(compact=False))
    _warm(cq, ([0, 5], {}), ([0, 5], {"trace": True}))
    results = {}

    def run():
        results["plain"] = cq.query([0, 5])
        results["traced"] = cq.query([0, 5], trace=True)

    ev = _profile(tmp_path, run)
    plain, traced = [e for e in ev if e["name"] == "flip.query"]
    assert not _inside(plain, ev, "flip.telemetry")
    tele, = _inside(traced, ev, "flip.telemetry")
    steps = int(np.max(results["traced"].steps))
    assert tele["attrs"] == {"rows": steps}
    dispatch, = _inside(traced, ev, "flip.dispatch")
    assert [e["name"] for e in _inside(dispatch, ev)] == (
        PHASES + ["flip.telemetry"])
    assert plain["attrs"]["qid"] + 1 == traced["attrs"]["qid"]


def test_host_driven_fixpoint_has_a_span_per_step(g, tmp_path):
    # a deadline runs the fixpoint program one step per call, from the
    # host
    cq = flip.compile(g, "bfs", _plan(compact=True))
    _warm(cq, (3, {"deadline_s": 1e6}))
    out = {}
    ev = _profile(tmp_path, lambda: out.setdefault(
        "r", cq.query(3, deadline_s=1e6)))
    dispatch, = [e for e in ev if e["name"] == "flip.dispatch"]
    steps = _inside(dispatch, ev, "flip.step")
    # one span per relax step, and the last one the read that finds the
    # frontier empty
    assert [s["attrs"]["step"] for s in steps] == list(
        range(out["r"].steps + 1))
    assert [e["name"] for e in _inside(dispatch, ev)
            if e["name"] != "flip.step"] == ["flip.prepare",
                                             "flip.finalize"]


def test_spans_leave_results_alone(g, tmp_path):
    cq = flip.compile(g, "sssp", _plan(compact=False))
    want = cq.query([0, 5, 9])
    got = {}
    _profile(tmp_path, lambda: got.setdefault("r", cq.query([0, 5, 9])))
    np.testing.assert_array_equal(got["r"].attrs, want.attrs)
    np.testing.assert_array_equal(got["r"].steps, want.steps)


def test_dense_fixpoint_program_is_named_flip_fixpoint(g):
    eng = flip.compile(g, "sssp", _plan(compact=False)).engine
    attrs, aux, frontier = eng.initial_state([0, 5])
    bg = eng.bg
    for cap in (0, 16):
        text = eng._dense_fixpoint_jit(cap).lower(
            _layout_args(bg), attrs, aux, frontier,
            eng._device_budgets(None, 2)).as_text()
        assert "module @jit_flip_fixpoint" in text


def test_sharded_fixpoint_program_is_named_flip_fixpoint_sharded(g):
    eng = flip.compile(g, "sssp", _plan(distributed=True)).engine
    attrs, aux, frontier = eng.initial_state([0, 5])
    bg = eng.bg
    assert bg.shards is not None
    for cap in (0, 16):
        text = eng._dense_fixpoint_jit(cap).lower(
            _layout_args(bg), attrs, aux, frontier,
            eng._device_budgets(None, 2)).as_text()
        assert "module @jit_flip_fixpoint_sharded" in text


def test_span_records_name_and_attributes(tmp_path):
    def run():
        with obs.span("flip.test", qid=7) as sp:
            sp.set_metadata(rows=3)

    ev = _profile(tmp_path, run)
    assert [(e["name"], e["attrs"]) for e in ev] == [
        ("flip.test", {"qid": 7, "rows": 3})]
