#!/usr/bin/env python3
"""Record the small chip trace that the trace-reduction tests read.

    python3 bench/record_trace.py <out_dir>

Serves the small test configuration (``tests/bench/data``: 2 layers, head
dim 128, so the TPU kernels run) for two seconds under the profiler, with
the harness's own annotations, and writes to ``<out_dir>`` the trace
(``trace.xplane.pb.gz``) and ``window.json``: the window's steps and requests
as ``tests/bench/test_bench_trace.py`` rebuilds them.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, run  # noqa: E402

DATA = harness.REPO / "tests" / "bench" / "data"


def main(argv=None) -> int:
    out = Path((argv if argv is not None else sys.argv[1:])[0])
    devs = run.chip(1)
    harness.ensure_src()
    run.compile_cache()
    import jax
    from bench import traffic as traffic_mod

    conf = harness.load_config("small", DATA / "configs")
    spec = traffic_mod.load("docqa_small", "small", DATA / "traffic")
    engine, dims = harness.build(conf, 7, devs[0])
    tr = traffic_mod.generate(spec, 7, 2.0, dims.vocab)
    harness.warm(engine, tr, harness.fill(engine, tr))
    tmp = out / "profile"
    shutil.rmtree(tmp, ignore_errors=True)
    window = harness.serve(engine, tr, 2.0, annotate=True,
                           on_window_start=lambda: jax.profiler.start_trace(str(tmp)),
                           on_window_end=jax.profiler.stop_trace)
    (pb,) = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)
    (out / "trace.xplane.pb.gz").write_bytes(gzip.compress(Path(pb).read_bytes(), 9))
    shutil.rmtree(tmp)
    (out / "window.json").write_text(json.dumps(dataclasses.asdict(window)))
    print(json.dumps({"steps": len(window.steps), "requests": len(window.reqs),
                      "bytes": (out / "trace.xplane.pb.gz").stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
