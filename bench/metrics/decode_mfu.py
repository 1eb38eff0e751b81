"""Model step, decode (``_jit_decode`` -> ``models/lm.decode``): useful model
FLOPs of every decode step in the traced window (one token per active slot,
attending its live positions; the model module's ``decode_token_flops``)
over the device time of those executions of the decode program times the
chip's bf16 peak, in percent."""
from bench import match


def read(run):
    got = match.pairs(run, "decode")
    if not got or run.peak is None:
        return None
    work = sum(run.model.decode_token_flops(run.dims, n)
               for step, _ in got for n in match.lives(run, step))
    t = sum(e.end - e.start for _, ex in got for e in ex) / 1e9
    return 100.0 * work / (t * run.peak["bf16_flops_per_s"])
