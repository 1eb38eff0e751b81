"""Kernel ``kernels/decode_attention.py`` (the custom call
``decode_attention``): the least time its calls in the traced window need
(each active slot's query against the K/V of its live positions;
``bench/flops.py``) at the chip's peaks, over the time the kernel ran, in
percent.  Calls are one per layer.  Which bound binds is printed on the
traced run's ``roofline_bounds`` line."""
from bench import flops, match

KERNEL = "decode_attention"


def read(run):
    got = match.pairs(run, "decode", match.DECODE_PROGRAM)
    if not got or run.peak is None:
        return None
    need = ran = 0.0
    for step, ex in got:
        t = run.trace.kernel_time(ex, KERNEL) / 1e9
        if t <= 0:
            continue
        f, b = flops.decode_attention_call(run.dims, match.lives(run, step))
        need += run.dims.n_layers * flops.least_time(f, b, run.peak)[0]
        ran += t
    return 100.0 * need / ran if ran > 0 else None
