#!/usr/bin/env python3
"""Run one cell of the serving benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a cell of ``BENCHMARK.json``.  Its configuration, traffic mix,
metrics and limits are the files of those names under ``bench/``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries its
per-layer metrics, the device's busy seconds and a breakdown.  Either way
the run ends with the check of what it served against the plain reference
(``bench/correct.py``).

Earlier lines of standard output report the device, how late the generator
ran, compilations inside the window, dispatch counts, requests per phase
and peak memory.  The last line is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``checked``
(each number compared, with its limit).  The same numbers close standard
error.  With no TPU, or fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

BENCHMARK = harness.REPO / "BENCHMARK.json"
OUT = harness.REPO / ".bench_out"
CACHE = harness.REPO / ".jax_cache"


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than its cell asks for."""


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metric_specs(bench: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def chip(chips: int):
    """The devices, after checking that JAX sees enough TPU chips.  The TPU
    runtime's logs go into the checkout (its default is a fixed /tmp path)."""
    if "TPU_LOG_DIR" not in os.environ:
        (OUT / "tpu_logs").mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(OUT / "tpu_logs")
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


def compile_cache() -> str:
    """JAX's persistent cache at the fixed ``.jax_cache/`` of the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program,
    however small, so that only a checkout's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def emit(result: dict, checked: dict) -> None:
    for name, c in checked.items():
        print(f"checked {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = json.loads(BENCHMARK.read_text())
    w = cell(bench, args.workload)
    try:
        devs = chip(int(w["chips"]))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    harness.ensure_src()
    cache = compile_cache()
    from bench import runner

    trace_dir = None
    if args.trace:
        trace_dir = OUT / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    result, checked = runner.run_cell(
        bench, w, seed=args.seed, seconds=args.seconds, device=devs[0],
        n_devices=int(w["chips"]), metrics=metric_specs(bench, args.workload, bool(args.trace)),
        trace_dir=trace_dir, t_start=T_START,
        notes={"compile_cache": cache},
    )
    emit(result, checked)
    return 0


if __name__ == "__main__":
    sys.exit(main())
