"""The profiler's trace of a window, reduced to what the metrics read.

`start`/`stop` wrap `jax.profiler` (Python tracer off, host spans on);
`reduce` reads the `.xplane.pb` it wrote with `jax.profiler.ProfileData`
and returns a `Summary`:

  * the window: the host span ``bench.window`` that the harness opens
    around the measured loop;
  * device busy time: the union of the intervals of the operations on
    each device plane's op line, clipped to the window, averaged over
    the chips used;
  * per-op device time, summed by name, for the kernel metrics and the
    breakdown;
  * idle gaps: the stretches of the window in which the device ran
    nothing, each put down to what the host's harness thread was doing
    at its middle -- the innermost harness span (``bench.*``) and,
    inside it, the innermost host event of the runtime.
"""
from __future__ import annotations

import dataclasses
import glob
import os

# device planes and the line that holds one event per executed operation
# (named by the HLO instruction's text; the fixpoint's `while` is one of
# them and spans its whole loop)
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# the relax kernel's device events: the Pallas call is the custom call
# named after the jitted function that makes it (`%frontier_relax_pallas.N
# = ... custom-call(...)` on a v5e); its body is `_relax_kernel`
RELAX_KERNEL = ("frontier_relax_pallas", "_relax_kernel")


def start(logdir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                   # per chip, averaged
    op_s: dict                      # op name -> device seconds per chip
    gap_s: dict                     # host activity -> idle seconds

    def kernel_s(self, names: tuple) -> float:
        """Device seconds per chip of the ops whose instruction name (the
        event's text before " = ": its operands name other ops) holds
        one of `names`."""
        return sum(s for op, s in self.op_s.items()
                   if any(n in op.split(" = ")[0] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        """Top device ops (by the HLO instruction's name: the events
        carry its whole text) and the longest idle stretches by what the
        host was doing, in seconds."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k.split(" = ")[0], v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _innermost(spans, times):
    """For ascending `times`, the name of the innermost span of `spans`
    holding each (None where none does). Spans of one host thread nest,
    so one sweep with a stack of open spans finds them all."""
    spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def reduce_profile(pd, chips: int = 1) -> Summary:
    """Reduce a `jax.profiler.ProfileData` to a `Summary`."""
    host = [p for p in pd.planes if p.name == HOST_PLANE]
    window, thread = None, None
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window, thread = (ev.start_ns, ev.end_ns), line
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    harness, runtime = [], []
    for ev in thread.events:
        if ev.end_ns < w0 or ev.start_ns > w1 or ev.name == WINDOW_SPAN:
            continue
        span = (ev.start_ns, ev.end_ns, ev.name)
        (harness if ev.name.startswith("bench.") else runtime).append(span)

    devices = sorted((p for p in pd.planes
                      if p.name.startswith(DEVICE_PLANE)),
                     key=lambda p: p.name)[:chips]
    busy_ns, op_ns, gap_ns = 0.0, {}, {}
    for plane in devices:
        ivals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                ivals.append((s, e))
                op_ns[ev.name] = op_ns.get(ev.name, 0.0) + (e - s)
        merged = _union(ivals)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        mids = [0.5 * (s + e) for s, e in gaps]
        for (s, e), what, inner in zip(gaps, _innermost(harness, mids),
                                       _innermost(runtime, mids)):
            what = what or WINDOW_SPAN
            label = what if inner is None else f"{what} > {inner}"
            gap_ns[label] = gap_ns.get(label, 0.0) + (e - s)
    n = max(len(devices), 1)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_ns * 1e-9 / n,
                   op_s={k: v * 1e-9 / n for k, v in op_ns.items()},
                   gap_s={k: v * 1e-9 / n for k, v in gap_ns.items()})


def reduce(logdir: str, chips: int = 1) -> Summary:
    """Reduce the newest trace the profiler wrote under `logdir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise ValueError(f"the profiler wrote no trace under {logdir}")
    return reduce_profile(ProfileData.from_file(paths[-1]), chips)
