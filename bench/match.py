"""Pairs the harness's steps with the device's program executions.

Each admission step runs the packed prefill program once, each decode step
the decode program once, in the order the steps ran.  The k-th traced step
of a kind is paired with the k-th execution of its program in the traced
window.  Where the counts differ the harness cannot say which work a
program's time was spent on (a program renamed, or run outside the steps),
and the run fails.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

PACKED_PROGRAM = "jit__packed_prefill_impl"
DECODE_PROGRAM = "jit__decode_impl"


class Unpaired(RuntimeError):
    """The traced steps of a kind and the executions of their program differ
    in number."""


def pairs(run, kind: str, program: str) -> Optional[List[Tuple[object, object]]]:
    """[(harness.Step, trace.Event)] for the traced steps of ``kind``; None
    without a trace or without such steps."""
    if run.trace is None:
        return None
    steps = [s for s in run.window.steps if s.in_window and s.kind == kind]
    execs = run.trace.executions(program)
    if not steps:
        return None
    if len(steps) != len(execs):
        raise Unpaired(f"{len(steps)} traced {kind} steps, {len(execs)} executions of "
                       f"{program!r} in the trace")
    return list(zip(steps, execs))


def segments(run, step) -> List[Tuple[int, int]]:
    """(matched, n_new) of each request an admission step packed."""
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
