"""Model step, prefill (the engine's admission program: ``_jit_packed`` ->
``models/lm.prefill_packed`` where it packs its admissions, else its
per-request ``_jit_prefill``): useful model FLOPs of every admission in the
traced window (each request's unpadded new tokens behind its reused prefix,
one row of logits; the model module's ``prefill_segment_flops``) over the
device time of those executions of the admission program times the chip's
bf16 peak, in percent."""
from bench import match


def read(run):
    got = match.pairs(run, "admit")
    if not got or run.peak is None:
        return None
    work = sum(run.model.prefill_segment_flops(run.dims, m, n)
               for step, _ in got for m, n in match.segments(run, step))
    t = sum(e.end - e.start for _, ex in got for e in ex) / 1e9
    return 100.0 * work / (t * run.peak["bf16_flops_per_s"])
