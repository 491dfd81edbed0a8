"""Share of the HBM roofline the relax kernel reaches, in %: the least
time the chip could take for the window's traversals -- the bytes no
implementation can avoid (`bench.run.least_bytes`: each half-edge of a
reached component read once at its 4 B weight, each reached vertex's
4 B state written once) over the peak HBM rate -- divided by the
kernel's device time from the trace. Bytes bound it: the 2 operations
per edge are nothing against the peak FLOP/s."""

from bench.trace import RELAX_KERNEL


def read(win):
    if win.trace is None:
        return None
    kernel_s = win.trace.kernel_s(RELAX_KERNEL)
    if not kernel_s:
        return None
    least = sum(c.least_bytes for c in win.done)
    return 100.0 * least / win.peaks["hbm_bytes_per_s"] / kernel_s
