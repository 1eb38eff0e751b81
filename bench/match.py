"""Pairs the harness's steps with the device's program executions.

The engine's programs are named in ``RunView.programs``
(``harness.programs``).  Each decode step runs the decode program once, and
each admission step runs the packed prefill program once where the engine
packs its admissions: the k-th traced step of such a kind is paired with
the k-th execution of its program in the traced window.  Where the engine
admits one request at a time, an admission step runs its per-request
prefill program once or more (twice where the context is written back
between its two launches); each execution is then given to the traced
step whose host span it starts in, the profiler's clocks agreeing to
within ``trace.SKEW_NS``.  Where the counts differ, or an execution falls
in no step of its kind, the harness cannot say which work a program's time
was spent on (a program renamed, or run outside the steps), and the run
fails.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from bench.trace import SKEW_NS


class Unpaired(RuntimeError):
    """The traced steps of a kind and the executions of their program differ
    in number, or an execution lies outside every step of its kind."""


def pairs(run, kind: str) -> Optional[List[Tuple[object, list]]]:
    """[(harness.Step, [trace.Event])] for the traced steps of ``kind``:
    each step with the executions of its program; None without a trace or
    without such steps."""
    if run.trace is None:
        return None
    program = run.programs[kind]
    steps = [s for s in run.window.steps if s.in_window and s.kind == kind]
    execs = run.trace.executions(program.name)
    if not steps:
        return None
    if not program.per_step:
        return _by_span(run, kind, program.name, execs)
    if len(steps) != len(execs):
        raise Unpaired(f"{len(steps)} traced {kind} steps, {len(execs)} executions of "
                       f"{program.name!r} in the trace")
    return [(s, [e]) for s, e in zip(steps, execs)]


def _by_span(run, kind: str, name: str, execs) -> List[Tuple[object, list]]:
    """Each execution to the traced step whose host span (``bench.step``)
    it starts in."""
    traced = [s for s in run.window.steps if s.in_window]
    spans = [h for h in run.trace.host if h.name == "bench.step"]
    if len(spans) != len(traced):
        raise Unpaired(f"{len(traced)} traced steps, {len(spans)} bench.step spans")
    starts = [h.start - SKEW_NS for h in spans]
    got = {id(s): (s, []) for s in traced if s.kind == kind}
    for e in execs:
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or traced[i].kind != kind or e.start > spans[i].end + SKEW_NS:
            raise Unpaired(f"an execution of {name!r} lies outside every traced {kind} step")
        got[id(traced[i])][1].append(e)
    empty = sum(not ex for _, ex in got.values())
    if empty:
        raise Unpaired(f"{empty} traced {kind} steps ran no execution of {name!r}")
    return list(got.values())


def segments(run, step) -> List[Tuple[int, int]]:
    """(matched, n_new) of each request an admission step admitted."""
    reqs = run.window.by_rid()
    out = []
    for rid in step.batch:
        r = reqs[rid]
        out.append((r.matched, r.ctx_len - r.matched + r.q_len))
    return out


def lives(run, step) -> List[int]:
    """Positions each token of a decode step attends: the request's prompt
    and the tokens before it, itself included."""
    reqs = run.window.by_rid()
    return [reqs[rid].ctx_len + reqs[rid].q_len + idx for rid, idx in step.decoded]


def work(run, step):
    """What a step's kernels work on: an admission's segments, a decode
    step's live lengths."""
    return segments(run, step) if step.kind == "admit" else lives(run, step)
