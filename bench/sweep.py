#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several offered rates, in one
process, and print per rate what was attempted, what finished inside the
window and whether the backlog grew.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 1,1.5,2

The knee is the highest rate at which completions keep pace with arrivals
and the backlog (requests queued or in a slot) does not grow over the
window.  A cell is then fixed at about 0.8 of it.  Each rate gets a fresh
engine (the weights are made once), its own fill, warm-up and the traffic's
lead-in, so that its window opens on a loaded engine.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, run  # noqa: E402

DRAIN_S = 10.0  # a sweep reads nothing after the window; enough to end the steps in flight


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = json.loads(run.BENCHMARK.read_text())
    w = run.cell(bench, args.workload)
    devs = run.chip(int(w["chips"]))
    harness.ensure_src()
    run.compile_cache()
    from bench import runner
    from bench import traffic as traffic_mod
    from bench import weights as bench_weights

    conf = harness.load_config(w["config"])
    model = harness.model_of(conf)
    dims = model.sizes(conf["config"])
    spec = traffic_mod.load(w["traffic"], w["config"])
    wts = bench_weights.make(model, dims, args.seed, devs[0])
    e2e = run.metric_specs(bench, args.workload, False)
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.perf_counter()
        engine, _ = harness.build(conf, args.seed, devs[0], weights=wts)
        tr = traffic_mod.generate(spec, args.seed, args.seconds, dims.vocab, rate=rate)
        matched = harness.fill(engine, tr)
        warmed = harness.warm(engine, tr, matched)
        setup = time.perf_counter() - t0
        window = harness.serve(engine, tr, args.seconds, drain_s=DRAIN_S)
        view = runner.RunView(window=window, dims=dims, peak=None, setup_s=setup, model=model)
        metrics = {m["name"]: runner.reader(m["name"])(view) for m in e2e}
        print(json.dumps({"line": "sweep", "rate_per_s": rate, "metrics": metrics,
                          **runner.phase_counts(window), **runner.backlog(window),
                          "latency": runner.latency(window),
                          "reused_context_share": runner.reused_share(window),
                          **runner.written_back(window),
                          "warmed": warmed}), flush=True)
        del engine, window
        harness.free_device(keep=wts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
