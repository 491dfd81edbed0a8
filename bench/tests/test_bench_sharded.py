"""What the four-chip cell adds: its configuration's sizes at seed 0,
and the reader of the exchange between chips."""
import os

import numpy as np
import pytest

from bench import graphs, spec
from bench.run import Call, Window
from bench.trace import Summary


def test_graph500_s16_sizes():
    # the four-chip cell's configuration, as its file states it
    config = spec.load_json(os.path.join(spec.ROOT, "bench", "configs",
                                         "graph500-s16.json"))
    g = graphs.generate(config, 0)
    assert (g.n, g.m) == (65536, 1_818_572)
    # 257,200 of the 262,144 tile pairs (16.86 GB of dense blocks), split
    # by quarters of the destination tiles to within 0.1%
    tile, nt = 128, 512
    key = (g.indices // tile).astype(np.int64) * nt + g.sources() // tile
    keys = np.unique(np.concatenate([key, np.arange(nt) * (nt + 1)]))
    assert keys.size == 257_200
    quarters = np.diff(np.searchsorted(keys, np.arange(5) * (nt // 4) * nt))
    assert quarters.tolist() == [64_321, 64_353, 64_250, 64_276]


def window(trace):
    calls = [Call(srcs=np.arange(2), rows=2, wall_s=0.5, ok=True,
                  steps=np.array([12, 14]), edges=10)] * 2
    return Window(calls=calls, window_s=2.0, setup_s=7.5,
                  memory_peak_bytes=None, peaks={}, trace=trace)


def summary(op_s):
    return Summary(window_s=2.0, busy_s=1.5, op_s=op_s, gap_s={})


@pytest.mark.parametrize("trace", [
    None,                                           # an untraced run
    summary({"_relax_kernel": 1.2, "fusion.3": 0.1}),    # one chip
])
def test_collective_reader_is_silent_without_collectives(trace):
    read = spec.metric_reader("dist.collective_ms_per_step")
    assert read(window(trace)) is None


def test_collective_ms_per_step():
    read = spec.metric_reader("dist.collective_ms_per_step")
    # the instruction's name decides, not the operands its text names
    trace = summary({"_relax_kernel": 1.2,
                     "%all-gather-start.2 = f32[8,512,128] all-gather(x)":
                     0.02,
                     "%all-gather-done.2 = f32[8,512,128] y": 0.03,
                     "%all-reduce.7 = s32[] all-reduce(z)": 0.001,
                     "%fusion.9 = f32[8] fusion(%all-gather.2)": 0.5})
    # two calls of 14 steps (a batch runs as long as its longest row)
    assert read(window(trace)) == pytest.approx(1e3 * 0.051 / 28)
