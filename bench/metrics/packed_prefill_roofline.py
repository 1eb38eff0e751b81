"""Kernel ``kernels/packed_prefill.py`` (the custom call
``packed_flash_attention``): the least time its calls in the traced window
need (each packed segment's causal span, unpadded; ``bench/flops.py``) at
the chip's peaks, over the time the kernel ran, in percent.  Calls are one
per layer; an admission whose program ran no kernel (a pack under the
program's short-prefill rule) is left out.  Which bound binds is printed
on the traced run's ``roofline_bounds`` line."""
from bench import flops, match

KERNEL = "packed_flash_attention"


def read(run):
    got = match.pairs(run, "admit", match.PACKED_PROGRAM)
    if not got or run.peak is None:
        return None
    need = ran = 0.0
    for step, ex in got:
        t = run.trace.kernel_time(ex, KERNEL) / 1e9
        if t <= 0:
            continue
        f, b = flops.packed_prefill_call(run.dims, match.segments(run, step))
        need += run.dims.n_layers * flops.least_time(f, b, run.peak)[0]
        ran += t
    return 100.0 * need / ran if ran > 0 else None
