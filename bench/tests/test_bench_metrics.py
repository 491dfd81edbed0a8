"""The arithmetic of the metric readers, on hand-made windows."""
import numpy as np
import pytest

from bench import graphs, spec
from bench.run import Call, Components, Window, least_bytes
from bench.trace import Summary

PEAKS = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.metric_reader(name)


def window(calls, trace=None, **kw):
    args = dict(window_s=2.0, setup_s=7.5, memory_peak_bytes=8.6e9,
                peaks=PEAKS, trace=trace)
    args.update(kw)
    return Window(calls=calls, **args)


def call(steps, edges, wall=0.5, ok=True, weight_bytes=None, least=0):
    steps = np.atleast_1d(steps)
    return Call(srcs=0 if steps.size == 1 else np.arange(steps.size),
                rows=steps.size, wall_s=wall, ok=ok, steps=steps,
                edges=edges, least_bytes=least, weight_bytes=weight_bytes)


def test_teps_counts_completed_traversals_over_the_window():
    calls = [call(19, 441_422), call(20, 441_422),
             call(5, 999, ok=False), call([19, 21], 2 * 441_422)]
    assert reader("teps")(window(calls)) == pytest.approx(
        4 * 441_422 / 2.0)


def test_peak_and_setup_are_read_as_measured():
    w = window([call(1, 1)])
    assert reader("peak_hbm_gb")(w) == pytest.approx(8.6)
    assert reader("setup_s")(w) == 7.5
    assert reader("peak_hbm_gb")(window([], memory_peak_bytes=None)) \
        is None


def test_engine_readers():
    calls = [call(10, 5, wall=0.2), call([10, 30], 10, wall=0.6)]
    w = window(calls)
    assert reader("engine.steps_per_query")(w) == pytest.approx(50 / 3)
    # a batch's fixpoint runs as many steps as its longest row
    assert reader("engine.step_ms")(w) == pytest.approx(1e3 * 0.8 / 40)


def test_weight_bytes_per_edge_needs_telemetry():
    w = window([call(3, 100, weight_bytes=3 * 65536)])
    assert reader("relax.weight_bytes_per_edge")(w) == pytest.approx(
        3 * 65536 / 100)
    assert reader("relax.weight_bytes_per_edge")(window([call(3, 100)])) \
        is None


def summary(busy=1.5, relax=1.2):
    return Summary(window_s=2.0, busy_s=busy,
                   op_s={"_relax_kernel": relax, "fusion.3": 0.1},
                   gap_s={"bench.query": 0.5})


def test_kernel_time_and_roofline_share():
    calls = [call(10, 441_422, least=4 * 882_860 + 4 * 24_190)] * 2
    w = window(calls, trace=summary())
    assert reader("kernel.relax_ms_per_step")(w) == pytest.approx(
        1e3 * 1.2 / 20)
    least = 2 * (4 * 882_860 + 4 * 24_190)
    assert reader("kernel.relax_roofline")(w) == pytest.approx(
        100 * least / 819e9 / 1.2)


def test_device_readers_are_silent_without_a_device_trace():
    w = window([call(10, 5)])
    for name in ("kernel.relax_ms_per_step", "kernel.relax_roofline",
                 "device.idle_share"):
        assert reader(name)(w) is None
    w = window([call(10, 5)], trace=summary(busy=0.0, relax=0.0))
    for name in ("kernel.relax_ms_per_step", "kernel.relax_roofline",
                 "device.idle_share"):
        assert reader(name)(w) is None


def test_idle_share():
    w = window([call(10, 5)], trace=summary(busy=1.5))
    assert reader("device.idle_share")(w) == pytest.approx(25.0)


def test_least_bytes_reads_each_component_once_per_batch():
    # two components: {0, 1, 2} with 2 edges, {3, 4} with 1 edge; 5 alone
    g = graphs.from_undirected(6, np.array([0, 1, 3]), np.array([1, 2, 4]),
                               np.ones(3, np.float32))
    comp = Components(g)
    assert comp.of(0, False) == (4, 3)
    assert comp.of(5, False) == (0, 1)
    assert comp.of(5, True) == (6, 6)
    assert least_bytes(comp, 2, False) == 4 * 4 + 4 * 3
    # a batch reads the union of its components' weights once, and
    # writes every row's state
    assert least_bytes(comp, np.array([0, 2, 3]), False) == \
        4 * (4 + 2) + 4 * (3 + 3 + 2)
    assert least_bytes(comp, np.array([0, 0]), True) == 4 * 6 + 4 * 12
