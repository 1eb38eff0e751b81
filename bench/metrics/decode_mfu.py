"""Model step, decode (``_jit_decode`` -> ``models/lm.decode``): useful model
FLOPs of every decode step in the traced window (one token per active slot,
attending its live positions; ``bench/flops.py``) over the device time of
those executions of the decode program times the chip's bf16 peak, in
percent."""
from bench import flops, match


def read(run):
    got = match.pairs(run, "decode", match.DECODE_PROGRAM)
    if not got or run.peak is None:
        return None
    work = sum(flops.decode_token_flops(run.dims, n)
               for step, _ in got for n in match.lives(run, step))
    t = sum(e.end - e.start for _, e in got) / 1e9
    return 100.0 * work / (t * run.peak["bf16_flops_per_s"])
