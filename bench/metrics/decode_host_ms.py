"""Engine decode step, host side (``ServingEngine.step`` -> ``_decode_step``):
for each decode step of the traced window, the time inside its
``engine.step`` host span that the device's busy union does not cover
(batch set-up, dispatch, the argmax sync, the per-token bookkeeping); the
median over those steps, in milliseconds.  Read from the trace's host plane
(``bench/host_spans.py``); a program without engine spans reads nothing."""
from bench import host_spans


def read(run):
    events = host_spans.events_for(run.trace)
    return host_spans.decode_host_ms(events, run.trace) if events else None
