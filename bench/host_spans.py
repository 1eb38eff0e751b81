#!/usr/bin/env python3
"""The serving engine's host spans in a run of a cell.

The engine marks each boundary of its host path with a span of
``repro.obs.host``: a ``jax.profiler.TraceAnnotation`` whose name starts
with ``engine.`` or ``store.`` (admission, plan, store lookup, fetch,
checksum and put, assembly, host-to-device copy, launch, write-back,
device-to-host copy, landing, sync, decode, emit).  In a traced run they
lie on the trace's host plane beside the harness's ``bench.*``
annotations, on the device's clock.  This module reduces them:

* ``decode_host_ms``: per decode ``engine.step``, the host time inside it
  that the device's busy union does not cover; the median over the window;
* ``idle_by_span``: the window's device-idle time, each idle instant put
  down to the innermost engine or store span open on the host, else to the
  harness's label (``step.<kind>``, ``wait``, ``submit``, ``host.other``);
* ``span_table``: per span name, its count, seconds, device-idle seconds
  and summed byte counters over the window;
* ``setup_table`` / ``seconds_in``: the same from the spans the engine's
  in-memory recorder kept during the set-up (``repro.obs.host``), which the
  trace does not cover.

A program without these spans yields no events here, and every reduction
then reads nothing.

    python3 bench/host_spans.py --workload <name> --seed <n> --seconds <s>

runs one cell as ``bench/run.py --trace 1`` does, with the engine's span
recorder on from before the engine is built, prints two more earlier lines,
``host_spans`` and ``idle_by_span``, and ends with the result line, whose
metrics add to the cell's end-to-end and per-layer ones
``setup_assemble_s`` (set-up seconds inside ``engine.assemble``: the numpy
assembly of packed KV buckets and their copy to the device) and
``setup_store_s`` (inside ``store.put``: the write-back's checksum stamp
and tier put).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, run  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

PREFIXES = ("engine.", "store.")
BYTES = ("nbytes", "bucket_bytes", "stored_bytes")  # the byte counters summed
SELF = ("engine.step", "engine.admit")  # spans whose own time names no work
TRACES = run.OUT / "trace"  # where a traced run writes its profile, one directory per cell


@dataclasses.dataclass
class HostEvent:
    name: str
    start: int  # ns
    end: int  # ns
    attrs: Dict[str, object]


@functools.lru_cache(maxsize=2)
def read_host(path: str) -> Tuple[Tuple[HostEvent, ...], Optional[Tuple[int, int]]]:
    """The engine and store events of a trace's host plane, by start, and
    the (lo, hi) of its ``bench.*`` annotations (None without them), which
    is the window ``bench/trace.py`` reduces."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(path)
    out: List[HostEvent] = []
    lo = hi = None
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                start, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if name.startswith("bench."):
                    lo = start if lo is None else min(lo, start)
                    hi = end if hi is None else max(hi, end)
                elif name.startswith(PREFIXES):
                    out.append(HostEvent(name, start, end, dict(e.stats)))
    out.sort(key=lambda h: (h.start, -h.end))
    return tuple(out), (None if lo is None else (lo, hi))


def events_for(red) -> List[HostEvent]:
    """The engine and store events of a traced run's window (``red``, its
    ``trace.Reduction``): those of the profile under ``TRACES`` whose
    harness annotations span exactly that window.  Empty without one."""
    if red is None:
        return []
    files = sorted(glob.glob(str(TRACES / "**" / "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime, reverse=True)
    for f in files:
        events, window = read_host(f)
        if window == (red.lo, red.hi):
            return list(events)
    return []


def covered(busy: Sequence[trace_mod.Interval], s: int, e: int) -> int:
    """ns of [s, e] that the disjoint sorted ``busy`` covers."""
    i = max(0, bisect.bisect_right(busy, (s,)) - 1)
    out = 0
    while i < len(busy) and busy[i][0] < e:
        out += max(0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return out


def in_window(events: Sequence[HostEvent], lo: int, hi: int) -> List[HostEvent]:
    return [h for h in events if lo <= h.start and h.end <= hi]


def decode_steps(events: Sequence[HostEvent]) -> List[HostEvent]:
    """The ``engine.step`` spans that hold an ``engine.decode``."""
    decodes = sorted(h.start for h in events if h.name == "engine.decode")
    out = []
    for h in events:
        if h.name == "engine.step":
            i = bisect.bisect_left(decodes, h.start)
            if i < len(decodes) and decodes[i] < h.end:
                out.append(h)
    return out


def decode_host_ms(events: Sequence[HostEvent], red) -> Optional[float]:
    """Median over the window's decode steps of the host time inside the
    step that the device's busy union (``red``, a ``trace.Reduction``) does
    not cover, in ms; None without decode steps."""
    steps = decode_steps(in_window(events, red.lo, red.hi))
    if not steps:
        return None
    return statistics.median((h.end - h.start - covered(red.busy, h.start, h.end)) / 1e6
                             for h in steps)


def decode_split(events: Sequence[HostEvent], red, program: str) -> Optional[dict]:
    """Medians over the window's decode steps, in ms: the ``engine.step``
    span, its host time the device does not cover (``decode_host_ms``) and
    the device time of the decode program (``program``, as the trace names
    it); the first should be about the sum of the other two."""
    steps = decode_steps(in_window(events, red.lo, red.hi))
    runs = red.executions(program)
    if not steps or not runs:
        return None
    return {"steps": len(steps),
            "step_ms": statistics.median((h.end - h.start) / 1e6 for h in steps),
            "host_ms": decode_host_ms(events, red),
            "program_ms": statistics.median((e.end - e.start) / 1e6 for e in runs)}


def labelled(events: Sequence[HostEvent], red) -> List[Tuple[int, int, str]]:
    """(start, end, label) of every host annotation of the window: the
    harness's under the labels of ``trace.Reduction.host_activity``, the
    engine's and store's under their names."""
    kinds = iter(red.step_kinds)
    out = []
    for h in red.host:
        label = (f"step.{next(kinds, 'other')}" if h.name == "bench.step"
                 else h.name.replace("bench.", ""))
        out.append((h.start, h.end, label))
    out += [(h.start, h.end, h.name) for h in events]
    return out


def pieces(intervals: Sequence[Tuple[int, int, str]], lo: int,
           hi: int) -> List[Tuple[int, int, str, str]]:
    """[lo, hi] cut into pieces, each with the label of the innermost
    interval open over it (the latest started) and of the outermost;
    ``host.other`` where none is open."""
    out: List[Tuple[int, int, str, str]] = []
    stack: List[Tuple[int, int, str]] = []
    t = lo

    def upto(until: int) -> None:
        nonlocal t
        if until > t:
            out.append((t, until, stack[-1][2] if stack else "host.other",
                        stack[0][2] if stack else "host.other"))
            t = until

    for s, e, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append((s, e, name))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    upto(hi)
    return out


def idle_by_span(events: Sequence[HostEvent], red) -> dict:
    """The window's device-idle seconds per label of ``pieces`` (they sum to
    ``window_s - busy_s``), and of the idle inside admission steps
    (``step.admit``) the seconds and the share put down to a named span
    below the step and the admission themselves."""
    idle = trace_mod.gaps(red.busy, red.lo, red.hi)
    parts: Dict[str, int] = {}
    admit = named = 0
    i = 0
    for s, e, inner, outer in pieces(labelled(events, red), red.lo, red.hi):
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j, ns = i, 0
        while j < len(idle) and idle[j][0] < e:
            ns += max(0, min(e, idle[j][1]) - max(s, idle[j][0]))
            j += 1
        if not ns:
            continue
        parts[inner] = parts.get(inner, 0) + ns
        if outer == "step.admit":
            admit += ns
            if inner not in SELF and inner != outer:
                named += ns
    return {"idle_s": {k: v / 1e9 for k, v in sorted(parts.items(), key=lambda kv: -kv[1])},
            "window_idle_s": sum(e - s for s, e in idle) / 1e9,
            "admit_idle_s": admit / 1e9,
            "admit_named_share": named / admit if admit else None}


def _add(row: dict, seconds: float, attrs: dict) -> None:
    row["count"] += 1
    row["s"] += seconds
    for k in BYTES:
        if isinstance(attrs.get(k), int):
            row[k] = row.get(k, 0) + attrs[k]


def span_table(events: Sequence[HostEvent], red) -> Dict[str, dict]:
    """Per span name over the window: count, seconds, device-idle seconds
    inside the spans and the summed byte counters."""
    out: Dict[str, dict] = {}
    for h in in_window(events, red.lo, red.hi):
        row = out.setdefault(h.name, {"count": 0, "s": 0.0, "idle_s": 0.0})
        _add(row, (h.end - h.start) / 1e9, h.attrs)
        row["idle_s"] += (h.end - h.start - covered(red.busy, h.start, h.end)) / 1e9
    return out


def setup_table(spans: Sequence) -> Dict[str, dict]:
    """Per span name, count, seconds and summed byte counters of the
    recorder's span trees (``obs.spans.Span``, perf_counter seconds)."""
    out: Dict[str, dict] = {}
    for root in spans:
        for s in root.walk():
            _add(out.setdefault(s.name, {"count": 0, "s": 0.0}), s.duration_s, s.attrs)
    return out


def seconds_in(spans: Sequence, name: str) -> Optional[float]:
    """Seconds inside the spans called ``name`` (outermost ones only); None
    when there is none."""
    total, found = 0.0, False
    stack = list(spans)
    while stack:
        s = stack.pop()
        if s.name == name:
            total, found = total + s.duration_s, True
        else:
            stack.extend(s.children)
    return total if found else None


class Phases:
    """Takes the recorder's spans at the set-up's boundaries while a run is
    in ``runner.run_cell``: ``fill`` and ``warm`` hold the spans of the
    store fill and the warm-up; ``reduction`` the window's reduced trace;
    ``programs`` the engine's programs (``harness.programs``)."""

    def __init__(self, host):
        self.host = host
        self.fill: List = []
        self.warm: List = []
        self.reduction = None
        self.programs = None
        self._saved = []

    def _wrap(self, mod, name, before=None, after=None):
        orig = getattr(mod, name)
        self._saved.append((mod, name, orig))

        @functools.wraps(orig)
        def wrapped(*a, **kw):
            if before is not None:
                before()
            out = orig(*a, **kw)
            if after is not None:
                after(out)
            return out

        setattr(mod, name, wrapped)

    def __enter__(self) -> "Phases":
        take = self.host.take
        self._wrap(harness, "fill", before=take)  # the build's spans are not set-up work here
        self._wrap(harness, "warm", before=lambda: self.fill.extend(take()))
        self._wrap(harness, "serve", before=lambda: self.warm.extend(take()))
        self._wrap(trace_mod, "reduce_dir", after=lambda r: setattr(self, "reduction", r))
        self._wrap(harness, "programs", after=lambda p: setattr(self, "programs", p))
        return self

    def __exit__(self, *exc) -> bool:
        for mod, name, orig in reversed(self._saved):
            setattr(mod, name, orig)
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = json.loads(run.BENCHMARK.read_text())
    w = run.cell(bench, args.workload)
    try:
        devs = run.chip(int(w["chips"]))
    except run.NoChip as e:
        print(f"bench/host_spans.py: {e}", file=sys.stderr)
        return 2
    harness.ensure_src()
    cache = run.compile_cache()
    from bench import runner
    from repro.obs import host

    trace_dir = TRACES / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = (run.metric_specs(bench, args.workload, False)
               + run.metric_specs(bench, args.workload, True))
    host.start()
    with Phases(host) as ph:
        result, checked = runner.run_cell(
            bench, w, seed=args.seed, seconds=args.seconds, device=devs[0],
            n_devices=int(w["chips"]), metrics=metrics, trace_dir=trace_dir,
            t_start=T_START, notes={"compile_cache": cache, "host_spans": True})
    red = ph.reduction
    events = events_for(red)
    window = {} if red is None else {"window": span_table(events, red),
                                     "decode": decode_split(events, red,
                                                            ph.programs["decode"].name)}
    runner.say("host_spans", dropped=host.dropped(), **window,
               setup={"fill": setup_table(ph.fill), "warm": setup_table(ph.warm)})
    if red is not None:
        runner.say("idle_by_span", **idle_by_span(events, red))
    for name, span_name in (("setup_assemble_s", "engine.assemble"),
                            ("setup_store_s", "store.put")):
        v = seconds_in(ph.fill + ph.warm, span_name)
        if v is not None:
            result["metrics"][name] = {"value": v, "unit": "s"}
    host.stop()
    run.emit(result, checked)
    return 0


if __name__ == "__main__":
    sys.exit(main())
