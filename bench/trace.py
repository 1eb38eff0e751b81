"""The reduction from a profiler trace to device time, per program and per
kernel, idle gaps and the breakdown.

``jax.profiler`` writes ``<dir>/plugins/profile/<stamp>/*.xplane.pb``; it is
read with ``jax.profiler.ProfileData``.  On a TPU the device plane
``/device:TPU:<n>`` has a line ``XLA Modules`` (one event per execution of
a compiled program, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO op, named by its HLO text ``%<op>.<n> = ...``;
a Pallas kernel is a custom call named after its kernel function, and a
``while`` op spans the ops of its body).  The host plane ``/host:CPU``
holds the harness's ``bench.*`` annotations on the same clock (ns).

The traced window is from the first ``bench.*`` annotation's start to the
last one's end.  Busy time is the union of the device's module and op
intervals inside it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # (start_ns, end_ns)

# the profiler aligns the device plane to the host's only to about a
# millisecond: a program can show up to that much before the host call that
# launched it, or after the host saw it end
SKEW_NS = 1_000_000

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?: =|$)")


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    end: int  # ns


def op_name(hlo_text: str) -> str:
    """``%decode_attention.4 = bf16[...] custom-call(...)`` -> ``decode_attention``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def union(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi] between the disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Per op name, the time no op nested inside it ran (a ``while`` op's
    own time excludes its body's ops)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [event, child time]
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            done, child = stack.pop()
            out[op_name(done.name)] = out.get(op_name(done.name), 0) + done.end - done.start - child
        if stack:
            stack[-1][1] += ev.end - ev.start
        stack.append([ev, 0])
    while stack:
        done, child = stack.pop()
        out[op_name(done.name)] = out.get(op_name(done.name), 0) + done.end - done.start - child
    return out


@dataclasses.dataclass
class Reduction:
    """A traced window, reduced."""

    lo: int
    hi: int
    modules: List[Event]  # device program executions, by start
    ops: List[Event]  # device ops, by start
    busy: List[Interval]  # union of device activity inside the window
    host: List[Event]  # the harness's bench.* annotations, by start
    step_kinds: List[str]  # the kind of each traced bench.step, in order

    def __post_init__(self):
        self._op_starts = [o.start for o in self.ops]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) / 1e9

    def executions(self, prefix: str) -> List[Event]:
        """Executions of the program whose module name starts with ``prefix``,
        inside the window (to within ``SKEW_NS`` at its edges: the steps at
        the window's ends launch and sync their programs inside it)."""
        return [m for m in self.modules
                if m.name.startswith(prefix) and self.lo - SKEW_NS <= m.start
                and m.end <= self.hi + SKEW_NS]

    def kernel_time(self, run: Event, kernel: str) -> int:
        """ns the ops named ``kernel`` ran inside one program execution."""
        i = bisect.bisect_left(self._op_starts, run.start)
        j = bisect.bisect_right(self._op_starts, run.end)
        return sum(o.end - o.start for o in self.ops[i:j]
                   if o.end <= run.end and op_name(o.name) == kernel)

    def host_activity(self, t: int) -> str:
        """What the harness was doing at ``t``: the innermost annotation."""
        label = "host.other"
        steps = iter(self.step_kinds)
        for h in self.host:
            kind = next(steps, "other") if h.name == "bench.step" else None
            if h.start <= t < h.end:
                label = f"step.{kind}" if kind else h.name.replace("bench.", "")
            if h.start > t:
                break
        return label

    def breakdown(self, top: int = 10) -> dict:
        st = self_times([o for o in self.ops if self.lo <= o.start < self.hi])
        ops = sorted(st.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(gaps(self.busy, self.lo, self.hi), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[self.host_activity((s + e) // 2), (e - s) / 1e9] for s, e in idle],
        }


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def reduce_file(path: str, step_kinds: Sequence[str], device: int = 0) -> Optional[Reduction]:
    """Reduce one ``.xplane.pb`` (or ``.xplane.pb.gz``); None when it holds
    no TPU device plane or no harness annotation."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(path)
    modules: List[Event] = []
    ops: List[Event] = []
    host: List[Event] = []
    dev_plane = f"/device:TPU:{device}"
    for plane in pd.planes:
        if plane.name == dev_plane:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = _events(line)
                elif line.name == "XLA Ops":
                    ops = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [e for e in _events(line) if e.name.startswith("bench.")]
    if not (modules or ops) or not host:
        return None
    host.sort(key=lambda e: e.start)
    modules.sort(key=lambda e: e.start)
    ops.sort(key=lambda e: e.start)
    lo, hi = host[0].start, max(h.end for h in host)
    busy = union([(e.start, e.end) for e in modules + ops], lo, hi)
    return Reduction(lo=lo, hi=hi, modules=modules, ops=ops, busy=busy, host=host,
                     step_kinds=list(step_kinds))


def reduce_dir(directory: Path, window) -> Optional[Reduction]:
    """The trace under ``directory`` of a run's window (``harness.Window``)."""
    files = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        return None
    kinds = [s.kind for s in window.steps if s.in_window]
    return reduce_file(files[-1], kinds)
