"""The reduction of the program's spans and the device's programs
(`bench.spans`) and the three metrics that read it: on a trace written
by hand, and on a small trace recorded on the chip."""
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import spans as sp
from bench import trace
from bench.spans import FIXPOINT_MODULE, QUERY_SPAN
from bench.spec import metric_reader

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data")


def _plane(pid, name, lines):
    """A text-proto XPlane; `lines` maps a line name to events
    ``(name, start_us, end_us, stats)``."""
    names, stats, out = {}, {}, []
    for lid, (line, events) in enumerate(lines.items(), 1):
        evs = []
        for ev, s, e, st in events:
            mid = names.setdefault(ev, len(names) + 1)
            body = "".join(
                f" stats {{ metadata_id: {stats.setdefault(k, len(stats) + 1)}"
                f" int64_value: {v} }}" for k, v in st.items())
            evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{int(s * 1e6)} duration_ps: {int((e - s) * 1e6)}"
                       f"{body} }}")
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                   + " ".join(evs) + " }")
    out += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in names.items()]
    out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in stats.items()]
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(out) + " }"


def _query(t, qid):
    """One query call at `t` us on the harness thread, and what the
    device ran for it: 18 us busy, 20 us idle inside `flip.query`, of
    which 4 us in `flip.telemetry`; three programs, the fixpoint's 17 us
    long with 12 us of relax kernel."""
    host = [("bench.query", t, t + 40, {}),
            ("flip.query", t + 1, t + 39, {"qid": qid, "batch": 1}),
            ("flip.dispatch", t + 2, t + 38,
             {"qid": qid, "bucket": 0, "first": 0}),
            ("flip.prepare", t + 2, t + 10, {}),
            ("DevicePutWithSharding", t + 4, t + 6, {}),
            ("flip.launch", t + 10, t + 12, {}),
            ("PjitFunction(flip_fixpoint)", t + 10, t + 12, {}),
            ("flip.wait", t + 12, t + 30, {}),
            ("np.asarray(jax.Array)", t + 12, t + 30, {}),
            ("flip.finalize", t + 30, t + 34, {}),
            ("np.asarray(jax.Array)", t + 31, t + 33, {}),
            ("flip.telemetry", t + 34, t + 38, {"rows": 2}),
            ("np.asarray(jax.Array)", t + 35, t + 37, {})]
    ops = [("convert_element_type.1", t + 7, t + 8, {}),
           ("%while.8 = (s32[]) while(...)", t + 13, t + 29, {}),
           ("%frontier_relax_pallas.8 = f32[] custom-call(...)", t + 14,
            t + 20, {}),
           ("%frontier_relax_pallas.8 = f32[] custom-call(...)", t + 21,
            t + 27, {}),
           ("fusion.28", t + 27, t + 28, {}),
           ("reduce_or.1", t + 30, t + 31, {})]
    modules = [("jit_convert_element_type(11)", t + 7, t + 8, {}),
               ("jit_flip_fixpoint(22)", t + 12, t + 29, {}),
               ("jit__reduce_any(33)", t + 30, t + 31, {})]
    return host, ops, modules


def hand_trace():
    """Window 0..100 us; queries at 10 and 55; one program between
    them (96..97) that starts inside no query."""
    host, ops, mods = [("bench.window", 0, 100, {})], [], []
    for t, qid in ((10, 0), (55, 1)):
        h, o, m = _query(t, qid)
        host, ops, mods = host + h, ops + o, mods + m
    host.append(("bench.record", 95, 98, {}))
    ops.append(("copy.3", 96, 97, {}))
    mods.append(("jit_copy(44)", 96, 97, {}))
    text = (_plane(1, "/host:CPU", {"python3": host})
            + _plane(2, "/device:TPU:0", {"XLA Ops": ops,
                                          "XLA Modules": mods}))
    return ProfileData.from_text_proto(text)


def window(pd, steps_per_call=2, calls=2):
    """What the metric readers see, with the spans on the summary."""
    summary = trace.reduce_profile(pd)
    summary.spans = sp.reduce_profile(pd)
    call = types.SimpleNamespace(iterations=steps_per_call)
    return types.SimpleNamespace(trace=summary, done=[call] * calls)


def test_program_spans_and_modules_of_a_hand_written_trace():
    s = sp.reduce_profile(hand_trace())
    queries = s.named(QUERY_SPAN)
    assert [q.attrs for q in queries] == [{"qid": 0, "batch": 1},
                                          {"qid": 1, "batch": 1}]
    assert [q.idle_s for q in queries] == pytest.approx([20e-6, 20e-6])
    assert s.idle_s("flip.telemetry") == pytest.approx(8e-6)
    assert s.idle_s("flip.wait") == pytest.approx(4e-6)
    assert s.named("flip.telemetry")[0].attrs == {"rows": 2}
    assert [m for m, _, _ in s.modules[0]] == [
        "jit_convert_element_type", "jit_flip_fixpoint", "jit__reduce_any",
        "jit_convert_element_type", "jit_flip_fixpoint", "jit__reduce_any",
        "jit_copy"]
    assert s.programs_in(QUERY_SPAN) == 6
    assert s.module_s(FIXPOINT_MODULE) == pytest.approx(34e-6)


def test_gaps_are_put_down_to_the_program_span_the_host_was_in():
    s = sp.reduce_profile(hand_trace())
    # each query: 22 us idle inside `bench.query`, cut at every span edge
    per_query = {
        "bench.query": 2,
        "bench.query > flip.query": 2,
        "bench.query > flip.prepare": 5,
        "bench.query > flip.prepare > DevicePutWithSharding": 2,
        "bench.query > flip.launch > PjitFunction(flip_fixpoint)": 2,
        "bench.query > flip.wait > np.asarray(jax.Array)": 2,
        "bench.query > flip.finalize": 1,
        "bench.query > flip.finalize > np.asarray(jax.Array)": 2,
        "bench.query > flip.telemetry": 2,
        "bench.query > flip.telemetry > np.asarray(jax.Array)": 2,
    }
    want = {k: 2 * v * 1e-6 for k, v in per_query.items()}
    want["bench.window"] = 17e-6          # 0..10, 50..55, 98..100
    want["bench.record"] = 2e-6           # 95..96, 97..98
    assert s.gap_s == pytest.approx(want)
    # every idle second of the window is put down once, as in bench.trace
    summary = trace.reduce_profile(hand_trace())
    assert sum(s.gap_s.values()) == pytest.approx(sum(
        summary.gap_s.values()))
    assert sum(s.gap_s.values()) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_readers_of_the_hand_written_trace():
    win = window(hand_trace())
    read = {m: metric_reader(m)(win) for m in (
        "session.idle_ms_per_query", "session.programs_per_query",
        "engine.outside_relax_ms_per_step")}
    # (40 - 8) us of idle over 2 calls; 6 programs over 2 calls;
    # (34 - 24) us outside the kernel over 4 steps
    assert read == pytest.approx({
        "session.idle_ms_per_query": 16e-3,
        "session.programs_per_query": 3.0,
        "engine.outside_relax_ms_per_step": 2.5e-3})


def test_readers_report_nothing_without_program_spans():
    pd = hand_trace()
    win = window(pd)
    win.trace.spans = sp.Spans(spans=[], modules=[[]], gap_s={},
                               window=win.trace.spans.window)
    bare = types.SimpleNamespace(trace=trace.reduce_profile(pd),
                                 done=win.done)
    for m in ("session.idle_ms_per_query", "session.programs_per_query",
              "engine.outside_relax_ms_per_step"):
        assert metric_reader(m)(win) is None
        assert metric_reader(m)(bare) is None
        assert metric_reader(m)(types.SimpleNamespace(
            trace=None, done=win.done)) is None


def test_a_trace_without_the_window_is_refused():
    pd = ProfileData.from_text_proto(_plane(
        1, "/host:CPU", {"python3": [("flip.query", 0, 1, {})]}))
    with pytest.raises(ValueError, match="bench.window"):
        sp.reduce_profile(pd)


def test_reduction_of_a_trace_recorded_on_the_chip_with_program_spans():
    # two traced solo SSSP calls (15 and 14 steps) on the Kronecker graph
    # of bench.graphs at scale 12, recorded on one TPU v5e ("TPU v5
    # lite") inside a `bench.window` span, as the harness's traced run
    # makes them
    pd = ProfileData.from_file(os.path.join(DATA,
                                            "kron12-sssp-spans.xplane.pb"))
    s = sp.reduce_profile(pd)
    queries = s.named(QUERY_SPAN)
    calls = [h for h in _harness(pd) if h[2] == "bench.query"]
    assert len(queries) == len(calls) == 2
    # host spans of the program and of the harness share one clock
    for q in queries:
        assert any(c0 <= q.start_ns and q.end_ns <= c1
                   for c0, c1, _ in calls)
        inside = [x.name for x in s.spans
                  if q.start_ns < x.start_ns and x.end_ns <= q.end_ns]
        assert inside == ["flip.dispatch", "flip.prepare", "flip.launch",
                          "flip.wait", "flip.finalize", "flip.telemetry"]
    programs = s.programs_in(QUERY_SPAN) / len(queries)
    assert programs == int(programs) == 4
    assert [m for m, _, _ in s.modules[0]][:4] == [
        "jit_convert_element_type", "jit_convert_element_type",
        FIXPOINT_MODULE, "jit__reduce_any"]
    # the accepted readers still find the relax kernel: one event per
    # relax step, as many as the stat rows the host read back
    kernel = [e for p in pd.planes if p.name == "/device:TPU:0"
              for line in p.lines if line.name == trace.OPS_LINE
              for e in line.events
              if e.name.split(" = ")[0].startswith("%frontier_relax_pallas")]
    rows = [x.attrs["rows"] for x in s.named("flip.telemetry")]
    assert len(kernel) == sum(rows) == 15 + 14
    summary = trace.reduce_profile(pd)
    assert 0 < summary.kernel_s(trace.RELAX_KERNEL) < s.module_s(
        FIXPOINT_MODULE)
    assert sum(s.gap_s.values()) == pytest.approx(sum(
        summary.gap_s.values()))


def _harness(pd):
    return [(ev.start_ns, ev.end_ns, ev.name) for p in pd.planes
            if p.name == trace.HOST_PLANE for line in p.lines
            for ev in line.events if ev.name.startswith("bench.")]
