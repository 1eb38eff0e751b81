"""Model step, prefill (``_jit_packed`` -> ``models/lm.prefill_packed``):
useful model FLOPs of every packed admission in the traced window (each
request's unpadded new tokens behind its reused prefix, one row of logits;
``bench/flops.py``) over the device time of those executions of the packed
prefill program times the chip's bf16 peak, in percent."""
from bench import flops, match


def read(run):
    got = match.pairs(run, "admit", match.PACKED_PROGRAM)
    if not got or run.peak is None:
        return None
    work = sum(flops.prefill_segment_flops(run.dims, m, n)
               for step, _ in got for m, n in match.segments(run, step))
    t = sum(e.end - e.start for _, e in got) / 1e9
    return 100.0 * work / (t * run.peak["bf16_flops_per_s"])
