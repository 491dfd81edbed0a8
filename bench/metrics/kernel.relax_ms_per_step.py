"""Device milliseconds of the Pallas relax kernel per fixpoint step: the
durations of the kernel's events in the profiler's trace, over the
window's fixpoint steps."""

from bench.trace import RELAX_KERNEL


def read(win):
    if win.trace is None:
        return None
    kernel_s = win.trace.kernel_s(RELAX_KERNEL)
    steps = sum(c.iterations for c in win.done)
    if not kernel_s or not steps:
        return None
    return 1e3 * kernel_s / steps
