"""The program's own spans and the device's programs in the profiler's trace.

`bench.trace` reduces a traced window to device busy time, time per op
and idle gaps by harness span. This module reads two more things out of
the same `jax.profiler.ProfileData`, for the session and fixpoint
metrics:

  * the program's spans (``flip.*``, written by `repro.obs.span`) on the
    harness thread, inside the window, with their attributes and the
    device-idle time inside each;
  * the events of each device plane's ``XLA Modules`` line: one per run
    of a program, named ``jit_<function>(<hash>)``; the hash is dropped.

Its idle gaps are cut at every host span's edge, and each piece is put
down to ``<harness span> > <innermost flip.* span> > <innermost runtime
event>``, leaving out a part that is absent.

The harness hands the result to the metric readers as
``window.trace.spans``; a trace without program spans (a program that
writes none) reduces to empty lists, and the readers then report
nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools

from bench import trace as tr

PROGRAM_PREFIX = "flip."
MODULES_LINE = "XLA Modules"
QUERY_SPAN = "flip.query"
TELEMETRY_SPAN = "flip.telemetry"
# the dense fixpoint's program: the jitted function `flip_fixpoint` of
# `FlipEngine._dense_fixpoint_jit`
FIXPOINT_MODULE = "jit_flip_fixpoint"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    attrs: dict
    idle_s: float               # device idle inside it, per chip


@dataclasses.dataclass
class Spans:
    spans: list                 # Span, by start
    modules: list               # per device plane: (name, start_ns,
                                # end_ns); empty without one
    gap_s: dict                 # label -> idle seconds per chip
    window: tuple               # (start_ns, end_ns)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def idle_s(self, name: str) -> float:
        """Device-idle seconds per chip inside the spans `name`."""
        return sum(s.idle_s for s in self.named(name))

    def programs_in(self, name: str) -> float:
        """Program runs per chip that start inside a span `name`."""
        inside = self.named(name)
        starts = [s.start_ns for s in inside]
        n = 0
        for chip in self.modules:
            for _, m0, _ in chip:
                i = bisect.bisect_right(starts, m0) - 1
                n += i >= 0 and m0 <= inside[i].end_ns
        return n / max(len(self.modules), 1)

    def module_s(self, name: str) -> float:
        """Device seconds per chip of the runs of program `name`, inside
        the window."""
        w0, w1 = self.window
        ns = sum(max(0.0, min(e, w1) - max(s, w0))
                 for chip in self.modules for m, s, e in chip if m == name)
        return ns * 1e-9 / max(len(self.modules), 1)


def module_name(event_name: str) -> str:
    """``jit_run(123)`` -> ``jit_run``."""
    return event_name.split("(")[0]


def reduce_profile(pd, chips: int = 1) -> Spans:
    """Reduce a `jax.profiler.ProfileData` to the program's `Spans`."""
    window, thread = None, None
    for plane in pd.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tr.WINDOW_SPAN:
                    window, thread = (ev.start_ns, ev.end_ns), line
    if window is None:
        raise ValueError(f"no {tr.WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    harness, program, runtime = [], [], []
    for ev in thread.events:
        if ev.end_ns < w0 or ev.start_ns > w1 or ev.name == tr.WINDOW_SPAN:
            continue
        span = (max(ev.start_ns, w0), min(ev.end_ns, w1), ev.name)
        if ev.name.startswith("bench."):
            harness.append(span)
        elif ev.name.startswith(PROGRAM_PREFIX):
            program.append(span + (dict(ev.stats),))
        else:
            runtime.append(span)
    program.sort(key=lambda sp: (sp[0], -sp[1]))

    devices = sorted((p for p in pd.planes
                      if p.name.startswith(tr.DEVICE_PLANE)),
                     key=lambda p: p.name)[:chips]
    n = max(len(devices), 1)
    # every host span's edge cuts the window into pieces in which the
    # host did one thing; busy edges make each piece busy or idle whole
    cuts = {w0, w1}
    for sp in itertools.chain(harness, program, runtime):
        cuts.update(sp[:2])
    modules, gap_ns = [], {}
    span_idle = [0.0] * len(program)
    for plane in devices:
        ivals = []
        chip = []
        for line in plane.lines:
            for ev in line.events:
                if line.name == tr.OPS_LINE:
                    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if e > s:
                        ivals.append((s, e))
                elif line.name == MODULES_LINE:
                    chip.append((module_name(ev.name), ev.start_ns,
                                 ev.end_ns))
        modules.append(sorted(chip, key=lambda m: m[1]))
        busy = tr._union(ivals)
        points = sorted(cuts.union(x for iv in busy for x in iv))
        starts = [s for s, _ in busy]
        pieces = []
        for a, b in zip(points, points[1:]):
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or busy[i][1] <= a:
                pieces.append((a, b))
        mids = [0.5 * (a + b) for a, b in pieces]
        for (a, b), h, p, r in zip(pieces, tr._innermost(harness, mids),
                                   tr._innermost(program, mids),
                                   tr._innermost(runtime, mids)):
            label = " > ".join(x for x in (h or tr.WINDOW_SPAN, p, r) if x)
            gap_ns[label] = gap_ns.get(label, 0.0) + (b - a)
        # idle inside each program span, from a running sum over pieces
        ends = [b for _, b in pieces]
        total = list(itertools.accumulate((b - a for a, b in pieces),
                                          initial=0.0))
        for k, sp in enumerate(program):
            lo = bisect.bisect_right(ends, sp[0])
            hi = bisect.bisect_right(ends, sp[1])
            span_idle[k] += total[hi] - total[lo]
    spans = [Span(name=nm, start_ns=s, end_ns=e, attrs=a,
                  idle_s=idle * 1e-9 / n)
             for (s, e, nm, a), idle in zip(program, span_idle)]
    return Spans(spans=spans, modules=modules,
                 gap_s={k: v * 1e-9 / n for k, v in gap_ns.items()},
                 window=(w0, w1))
