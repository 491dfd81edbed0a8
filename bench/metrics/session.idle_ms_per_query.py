"""Device-idle milliseconds per query call inside the program: the time
in which no operation ran on the device inside the program's
`flip.query` spans, less that inside its `flip.telemetry` spans (the
stat readback that only traced runs pay), summed over the window, over
its completed calls. Read from the program's spans on the profiler's
clock (`bench.spans`); nothing where the trace holds none."""

from bench.spans import QUERY_SPAN, TELEMETRY_SPAN


def read(win):
    spans = getattr(win.trace, "spans", None)
    if (spans is None or not spans.modules or not spans.named(QUERY_SPAN)
            or not win.done):
        return None
    idle = spans.idle_s(QUERY_SPAN) - spans.idle_s(TELEMETRY_SPAN)
    return 1e3 * idle / len(win.done)
