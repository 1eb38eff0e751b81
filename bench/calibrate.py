#!/usr/bin/env python3
"""Readings for a cell's correctness limit, over many seeds in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed: weights, engine, fill, warm-up and a window at the cell's
own rate, exactly as a run makes them; then, on the sample a run would
check, the program's widest logit gap against the float32 reference and
the negative control's (the reference at float8 e4m3, ``reference.FP8``)
at the same positions.  The limit in ``bench/limits/<workload>.json`` is
set between the largest program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=None,
                    help="read the control on the first N seeds only (default: all)")
    args = ap.parse_args(argv)
    bench = json.loads(run.BENCHMARK.read_text())
    w = run.cell(bench, args.workload)
    devs = run.chip(int(w["chips"]))
    harness.ensure_src()
    run.compile_cache()
    from bench import correct, runner
    from bench import traffic as traffic_mod
    from bench import weights as bench_weights

    conf = harness.load_config(w["config"])
    model = harness.model_of(conf)
    dims = model.sizes(conf["config"])
    spec = traffic_mod.load(w["traffic"], w["config"])
    seeds = [int(s) for s in args.seeds.split(",")]
    n_control = len(seeds) if args.control is None else args.control
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        wts = bench_weights.make(model, dims, seed, devs[0])
        engine, _ = harness.build(conf, seed, devs[0], weights=wts)
        tr = traffic_mod.generate(spec, seed, args.seconds, dims.vocab)
        harness.warm(engine, tr, harness.fill(engine, tr))
        window = harness.serve(engine, tr, args.seconds)
        finished = runner.served(window, tr)
        del engine
        harness.free_device(keep=wts)
        t1 = time.perf_counter()
        read = correct.readings(model, wts, dims, correct.sample(finished, seed),
                                control=i < n_control)
        print(json.dumps({"line": "calibrate", "seed": seed, **read,
                          "failed": runner.phase_counts(window)["failed"],
                          "run_s": t1 - t0, "check_s": time.perf_counter() - t1}), flush=True)
        del wts
        harness.free_device()
    return 0


if __name__ == "__main__":
    sys.exit(main())
