#!/usr/bin/env python3
"""Spreads of two sets of runs, and the bound they suggest.

    python3 bench/spread.py --set1 a1.out a2.out ... --set2 b1.out b2.out ...

Each file is one run's standard output; its last line is the result.  Per
end-to-end metric, and per number of the run's ``latency`` line, this
prints each set's median and quartile spread (the distance between the
first and third quartile of ``statistics.quantiles(values, n=4)``, as a
share of the median), the wider of the two, five times it (at least 1%)
as the suggested bound, and how far the second set's median lies from the
first's.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from typing import Dict, List


def readings(path: str) -> Dict[str, float]:
    """The result's metrics and the ``latency`` line's numbers of one run."""
    with open(path) as f:
        lines = [json.loads(ln) for ln in f.read().splitlines() if ln.startswith("{")]
    out = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    for ln in lines:
        if ln.get("line") == "latency":
            out.update({f"latency.{k}": v for k, v in ln.items() if k != "line"})
    return out


def spread(values: List[float]) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def table(set1: List[Dict[str, float]], set2: List[Dict[str, float]]) -> Dict[str, dict]:
    out = {}
    for name in set1[0]:
        a = [r[name] for r in set1]
        b = [r[name] for r in set2]
        wide = max(spread(a), spread(b))
        out[name] = {
            "median1": statistics.median(a), "median2": statistics.median(b),
            "spread1": spread(a), "spread2": spread(b), "widest": wide,
            "bound": max(0.01, 5 * wide),
            "median_shift": statistics.median(b) / statistics.median(a) - 1,
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--set1", nargs="+", required=True)
    ap.add_argument("--set2", nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = table([readings(p) for p in args.set1], [readings(p) for p in args.set2])
    for name, row in rows.items():
        print(json.dumps({"metric": name, **row}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
