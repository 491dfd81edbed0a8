"""The reduction from the profiler's trace to the metrics: on a trace
written by hand, and on a small trace recorded on the chip."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace

# times in us: window 0..100 on the host's harness thread; queries at
# 11..40 and 50..90; device ops 20..25 (kernel), 24..30 (overlaps),
# 60..80 (kernel) and one op after the window that must not count
HAND = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 29000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 4000000 }
  }
  lines { id: 2 name: "other thread" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.query" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(run)" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 24000000 duration_ps: 6000000 }
    events { metadata_id: 1 offset_ps: 60000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 120000000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "_relax_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run" } }
}
'''


def test_reduction_of_a_hand_written_trace():
    s = trace.reduce_profile(ProfileData.from_text_proto(HAND))
    assert s.window_s == pytest.approx(100e-6)
    # union of [20, 30] and [60, 80]: the module line does not count
    assert s.busy_s == pytest.approx(30e-6)
    assert s.kernel_s(trace.RELAX_KERNEL) == pytest.approx(25e-6)
    assert s.op_s["fusion.1"] == pytest.approx(6e-6)
    # idle: 0..20 (mid 10: the window, between queries), 30..60 (mid 45:
    # between queries), 80..100 (mid 90: the end of query 2)
    assert s.gap_s == pytest.approx({"bench.window": 50e-6,
                                     "bench.query": 20e-6})
    b = s.breakdown()
    assert b["device_ops"][0] == ["_relax_kernel", pytest.approx(25e-6)]
    assert [k for k, _ in b["idle_gaps"]] == ["bench.window",
                                              "bench.query"]


def test_gap_names_the_runtime_event_inside_a_harness_span():
    hand = HAND.replace("offset_ps: 12000000 duration_ps: 4000000",
                        "offset_ps: 0 duration_ps: 20000000")
    s = trace.reduce_profile(ProfileData.from_text_proto(hand))
    assert "bench.window > PjitFunction(run)" in s.gap_s


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_profile(ProfileData.from_text_proto(
            HAND.replace('"bench.window"', '"other"')))


def test_reduction_of_a_trace_recorded_on_the_chip():
    # two solo SSSP queries of 128 steps on a 4096-vertex road graph,
    # traced on one TPU v5e ("TPU v5 lite") inside a `bench.window` span
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data",
                        "road4096-sssp.xplane.pb")
    pd = ProfileData.from_file(path)
    s = trace.reduce_profile(pd)
    kernel = [e for p in pd.planes if p.name == "/device:TPU:0"
              for line in p.lines if line.name == trace.OPS_LINE
              for e in line.events
              if e.name.split(" = ")[0].startswith("%frontier_relax_pallas")]
    assert len(kernel) == 2 * 128                 # one call per step
    assert s.kernel_s(trace.RELAX_KERNEL) == pytest.approx(
        sum(e.duration_ns for e in kernel) * 1e-9)
    assert s.window_s == pytest.approx(0.022024768)
    # the fixpoint's while op spans each query's loop on the device
    assert 0 < s.kernel_s(trace.RELAX_KERNEL) < s.busy_s < s.window_s
    assert s.breakdown()["device_ops"][0][0].startswith("%while")
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s)
