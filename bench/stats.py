"""The arithmetic of the end-to-end metrics: percentiles and the window.

A request is attempted when it falls due inside the window.  One that never
got its first token is failed, and enters every tail as missing the limit:
its time is taken as running to the end of the run (the drain's end, a
minute or more after the window), a lower bound that no served request
reaches.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p`` percent
    of the sample at or below it.  An empty sample has no percentile."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def ttfts(due: Sequence[float], first_token: Sequence[Optional[float]],
          run_end: float) -> List[float]:
    """Time from due to first token of every attempted request; where none
    came, the time from due to the end of the run."""
    return [run_end - d if t is None else t - d for d, t in zip(due, first_token)]


def token_gaps(stamps: Iterable[Sequence[float]], start: float, end: float) -> List[float]:
    """Every gap between consecutive output tokens of a request, for each
    request's token stamps, where the later token came inside the window
    [start, end]."""
    out = []
    for ts in stamps:
        for a, b in zip(ts, ts[1:]):
            if start <= b <= end:
                out.append(b - a)
    return out


def tokens_per_s(stamps: Iterable[Sequence[float]], start: float, end: float) -> float:
    """All output tokens emitted in the window over the whole window."""
    n = sum(1 for ts in stamps for t in ts if start <= t <= end)
    return n / (end - start)
