"""Device milliseconds of the exchange between chips per fixpoint step:
per chip, the durations of the collective operations in the profiler's
trace (those whose instruction name holds one of `COLLECTIVES`), over
the window's fixpoint steps. Nothing where no collective ran (one chip,
or the CPU)."""

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all")


def read(win):
    if win.trace is None:
        return None
    collective_s = win.trace.kernel_s(COLLECTIVES)
    steps = sum(c.iterations for c in win.done)
    if not collective_s or not steps:
        return None
    return 1e3 * collective_s / steps
