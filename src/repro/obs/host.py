"""Host spans: measured stretches of the serving path's host code, on the
profiler's clock.

``span(name, **attrs)`` is a context manager around one stretch of host
code (an admission, a store fetch, the assembly of a packed launch, ...).
It always enters ``jax.profiler.TraceAnnotation(name, **attrs)``: under
``jax.profiler.trace`` every span lands on the profiler's host plane
(``/host:CPU``), on the same clock as the device's ``XLA Modules`` and
``XLA Ops``; with no profiler running it costs about a microsecond.

While the process-wide recorder is on (``start()``), each span is also kept
in memory as an ``obs.spans.Span`` stamped with ``time.perf_counter()``,
nested under the span that was open around it on the same thread, with its
``req`` attribute as ``req_id`` (batch spans carry ``req_ids``).
``take()`` returns the finished top-level spans and forgets them;
``obs.write_chrome_trace`` writes them on their own process track
(``HOST_PID``).  The recorder keeps at most ``limit`` spans between takes
and counts those it drops (``dropped()``): a dropped span's children are
dropped with it.

Host spans are measured seconds; the SimClock spans of ``obs.spans`` are
modeled ones.  Attributes are ints and strings already at hand: a span
never reads an array to compute one.  Spans wrap host code only, never
code inside a function that ``jax.jit`` traces (docs/OBSERVABILITY.md has
the table of spans).
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation

HOST_PID = 1 << 20  # Chrome-trace process of host spans: no replica's index
DEFAULT_LIMIT = 100_000

_DROPPED = object()  # stack marker of a span the recorder did not keep


class _Recorder:
    def __init__(self, limit: int):
        self.limit = limit
        self.roots: List = []
        self.kept = 0  # spans held since the last take
        self.dropped = 0
        self._local = threading.local()  # each thread nests its own spans
        self.lock = threading.Lock()  # the counts and roots are shared

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def open(self, name: str, attrs: dict):
        from repro.obs.spans import Span  # here: spans imports serving, which imports this

        stack = self._stack()
        with self.lock:
            drop = (stack and stack[-1] is _DROPPED) or self.kept >= self.limit
            if drop:
                self.dropped += 1
            else:
                self.kept += 1
        if drop:
            stack.append(_DROPPED)
            return _DROPPED
        attrs = dict(attrs)
        s = Span(name, time.perf_counter(), 0.0, req_id=attrs.pop("req", -1),
                 replica=HOST_PID, attrs=attrs)
        stack.append(s)
        return s

    def close(self, s) -> None:
        stack = self._stack()
        stack.pop()
        if s is _DROPPED:
            return
        s.end_s = time.perf_counter()
        if stack:
            stack[-1].children.append(s)
        else:
            with self.lock:
                self.roots.append(s)


_rec: Optional[_Recorder] = None


def start(limit: int = DEFAULT_LIMIT) -> None:
    """Turn the recorder on (afresh): keep up to ``limit`` spans."""
    global _rec
    _rec = _Recorder(limit)


def stop() -> None:
    """Turn the recorder off and forget what it held."""
    global _rec
    _rec = None


def take() -> List:
    """The finished top-level spans since the last take, oldest first;
    the recorder forgets them (a span still open is returned by a later
    take).  Empty when the recorder is off."""
    rec = _rec
    if rec is None:
        return []
    with rec.lock:
        out, rec.roots, rec.kept = rec.roots, [], 0
    return out


def dropped() -> int:
    """Spans the recorder dropped at its bound since ``start()``."""
    return 0 if _rec is None else _rec.dropped


class span:
    """``with span("store.fetch", req=7, tier="io2") as sp: ...``; counters
    known only at the end go on with ``sp.set(nbytes=...)``."""

    __slots__ = ("_name", "_attrs", "_ann", "_rec", "_span")

    def __init__(self, name: str, **attrs):
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self._name, **self._attrs)
        self._ann.__enter__()
        self._rec = rec = _rec
        self._span = rec.open(self._name, self._attrs) if rec is not None else None
        return self

    def set(self, **attrs) -> None:
        self._ann.set_metadata(**attrs)
        if self._span is not None and self._span is not _DROPPED:
            self._span.attrs.update(attrs)

    def __exit__(self, *exc) -> bool:
        if self._rec is not None:
            self._rec.close(self._span)
        self._ann.__exit__(*exc)
        return False
