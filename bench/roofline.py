"""A kernel's share of its roofline over the traced window, for the
``<kernel>_roofline`` metric readers.

For every traced step of the kind that runs the kernel (the model module's
``KERNELS[kernel].step``), the least time its calls need at the chip's
peaks (``calls`` per step, each needing ``call(dims, work)`` operations and
bytes), summed over the steps in which the kernel ran, over the time the
kernel ran in those steps' programs, in percent.
"""
from typing import Optional

from bench import flops, match


def share(run, kernel: str) -> Optional[float]:
    k = run.model.KERNELS.get(kernel) if run.model is not None else None
    if k is None:
        return None
    got = match.pairs(run, k.step)
    if not got or run.peak is None:
        return None
    need = ran = 0.0
    for step, ex in got:
        t = sum(run.trace.kernel_time(e, kernel) for e in ex) / 1e9
        if t <= 0:
            continue
        f, b = k.call(run.dims, match.work(run, step))
        need += k.calls(run.dims) * flops.least_time(f, b, run.peak)[0]
        ran += t
    return 100.0 * need / ran if ran > 0 else None
