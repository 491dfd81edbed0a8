"""The one fixpoint loop: every call runs the compiled `flip_fixpoint`
program, shared by every engine whose trace is the same.

  * engines that agree on everything the trace reads share one program,
    and each still gets its own graph's answer;
  * engines that differ in any field of the program's key get different
    programs;
  * a program holds none of a layout's arrays: a dropped session frees
    its blocks;
  * a deadline that has passed by the first step boundary stops every
    query before its first step (a stubbed clock, no sleep).
"""
import gc
import itertools
import time
import types
import weakref

import jax
import numpy as np
import pytest
from conftest import oracle

from repro import api as flip
from repro.algebra import ALGEBRAS
from repro.core import engine as engine_mod
from repro.core.engine import FlipEngine
from repro.graphs import make_power_law, make_synthetic

BASE = dict(algo="sssp", tile=16, mode="data", relax_mode="jnp",
            compact="auto", feature_dim=None)


def _engine(g, **kw):
    kw = {**BASE, **kw}
    return FlipEngine.build(g, kw.pop("algo"), **kw)


def test_engines_with_the_same_trace_share_one_program():
    g1 = make_power_law(48, 140, seed=1)
    g2 = make_power_law(48, 160, seed=2)
    e1, e2 = _engine(g1), _engine(g2)
    assert e1._dense_fixpoint_jit(0) is e2._dense_fixpoint_jit(0)
    for g, eng in ((g1, e1), (g2, e2)):
        out, _ = eng.execute([0, 5])
        for b, s in enumerate((0, 5)):
            assert ALGEBRAS["sssp"].results_match(out[b],
                                                  oracle("sssp", g, s))


# one field of the key changed at a time: (engine kwargs, graph size,
# trace_cap)
KEY_FIELDS = [
    pytest.param(dict(algo="bfs"), 48, 0, id="algebra"),
    pytest.param(dict(mode="op", compact=True), 48, 0, id="mode"),
    pytest.param(dict(relax_mode="interpret"), 48, 0, id="relax_mode"),
    pytest.param(dict(compact=False), 48, 0, id="compact"),
    pytest.param(dict(feature_dim=4), 48, 0, id="feature_dim"),
    pytest.param({}, 48, 16, id="trace_cap"),
    pytest.param({}, 47, 0, id="n"),
    pytest.param(dict(tile=20), 48, 0, id="tile"),   # still 3 tiles
]


@pytest.mark.parametrize("kw,n,cap", KEY_FIELDS)
def test_engines_differing_in_a_key_field_get_different_programs(kw, n,
                                                                 cap):
    base = _engine(make_synthetic(48, 140, seed=4))
    other = _engine(make_synthetic(n, 140, seed=4), **kw)
    assert base._dense_fixpoint_jit(0) is not other._dense_fixpoint_jit(cap)


def test_sharded_layout_gets_its_own_program():
    eng = _engine(make_synthetic(48, 140, seed=4))
    sharded = eng._layout(True, None, "data")
    assert sharded.shards is not None
    assert (eng._dense_fixpoint_jit(0, sharded)
            is not eng._dense_fixpoint_jit(0))


def test_dropped_session_frees_its_blocks():
    g = make_power_law(61, 170, seed=5)
    cq = flip.compile(g, "sssp", flip.ExecutionPlan(tile=16,
                                                    relax_mode="jnp"))
    cq.query([0, 3])
    cq.query([0, 3], trace=True)
    cq.query(3, deadline_s=1e6)
    bg = cq.engine.bg
    held = [weakref.ref(x) for x in (bg.blocks, bg.blocks_ext, bg.bsrc,
                                     bg.bdst, bg.src_order, bg.src_tiles)]
    n_live = len(jax.live_arrays())
    del cq, bg
    gc.collect()
    assert all(w() is None for w in held)
    assert len(jax.live_arrays()) <= n_live - len(held)


def test_deadline_past_at_the_first_boundary_returns_the_initial_state(
        monkeypatch):
    """Each clock read is a minute after the one before, so a 1 s
    deadline has passed by the first step boundary: every query comes
    back as its initial state, flagged, with 0 steps."""
    ticks = itertools.count(0.0, 60.0)
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        monotonic=lambda: next(ticks), perf_counter=time.perf_counter))
    eng = _engine(make_power_law(48, 140, seed=1))
    srcs = [0, 7, 13]
    attrs0, aux0, _ = eng.initial_state(srcs)
    want = eng.finalize_state(attrs0, aux0)
    for trace in (False, True):
        d = eng.execute(srcs, deadline_s=1.0, detail=True, trace=trace)
        np.testing.assert_array_equal(d.attrs, want)
        np.testing.assert_array_equal(d.steps, [0, 0, 0])
        assert d.deadline_expired.all() and not d.converged.any()
    assert len(d.telemetry.trace) == 0
    assert len(d.telemetry.trace.step_wall_s) == 0
