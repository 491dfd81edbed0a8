"""Device programs run per query call: the events of the device's
`XLA Modules` line that start inside the program's `flip.query` spans,
over the window's completed calls (`bench.spans`); nothing where the
trace holds no such span."""

from bench.spans import QUERY_SPAN


def read(win):
    spans = getattr(win.trace, "spans", None)
    if (spans is None or not spans.modules or not spans.named(QUERY_SPAN)
            or not win.done):
        return None
    return spans.programs_in(QUERY_SPAN) / len(win.done)
