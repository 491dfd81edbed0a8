"""Device milliseconds per fixpoint step spent in the fixpoint's program
outside the relax kernel: the device time of the `jit_flip_fixpoint`
module's runs less the relax kernel's (`RELAX_KERNEL`), over the
window's fixpoint steps. That is the compaction and the step's
elementwise fusions. Nothing where no program of that name ran."""

from bench.spans import FIXPOINT_MODULE
from bench.trace import RELAX_KERNEL


def read(win):
    spans = getattr(win.trace, "spans", None)
    if spans is None:
        return None
    module_s = spans.module_s(FIXPOINT_MODULE)
    steps = sum(c.iterations for c in win.done)
    if not module_s or not steps:
        return None
    return 1e3 * (module_s - win.trace.kernel_s(RELAX_KERNEL)) / steps
