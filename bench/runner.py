"""A whole run of one cell, from set-up to the result line.

``run_cell`` is what ``bench/run.py`` calls once it has found the chip; the
tests call it on the CPU at a tiny size.  It prints the earlier lines
(device, generator lateness, compilations in the window, dispatch counts,
requests per phase, the backlog, latency statistics, the share of context
served from the store, peak memory) and
returns the result and the numbers compared.  A program compiled or loaded
from the cache inside the window makes the run fail with no result.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import statistics
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import correct, harness, stats
from bench import traffic as traffic_mod
from bench.flops import peaks

METRICS = harness.BENCH / "metrics"


@dataclasses.dataclass
class RunView:
    """What a metric reader reads: the window, the model module and its
    sizes, the chip's peaks, the set-up time, the engine's admission and
    decode programs (``harness.programs``) and, in a traced run, the
    trace's reduction."""

    window: harness.Window
    dims: object  # the model module's sizes
    peak: Optional[dict]
    setup_s: float
    trace: Optional[object] = None  # bench.trace.Reduction
    model: Optional[object] = None  # bench/models/<m>.py
    programs: Optional[Dict[str, harness.Program]] = None


def reader(name: str, directory: Path = METRICS) -> Callable[[RunView], Optional[float]]:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Wall-clock stamps of backend compilations and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles: List[float] = []
        self.cache_hits: List[float] = []
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.perf_counter())
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(time.perf_counter())

    def between(self, a: float, b: float) -> Dict[str, int]:
        return {"compiles": sum(a <= t <= b for t in self.compiles),
                "cache_loads": sum(a <= t <= b for t in self.cache_hits)}


def say(tag: str, **kw) -> None:
    print(json.dumps({"line": tag, **kw}), flush=True)


class CompiledInWindow(RuntimeError):
    """A program was compiled, or loaded from the cache, inside the window."""


def served(window: harness.Window, traffic) -> List[correct.Served]:
    """Every request that finished, the lead-in's too, with its prompt and
    served tokens."""
    reqs = {harness.RID0 + r.idx: r for r in traffic.requests}
    out = []
    for wr in window.served:
        if wr.finished:
            r = reqs[wr.rid]
            out.append(correct.Served(req=wr.rid, prompt=traffic.docs[r.doc] + r.question,
                                      tokens=list(wr.tokens)))
    return out


def roofline_bounds(view: RunView) -> dict:
    """Per kernel of the model, how many traced calls each roofline bound
    limits."""
    from bench import flops, match

    out = {}
    for kernel, k in view.model.KERNELS.items():
        counts = {"compute": 0, "memory": 0}
        for step, _ in match.pairs(view, k.step) or []:
            work = k.call(view.dims, match.work(view, step))
            counts[flops.least_time(*work, view.peak)[1]] += 1
        out[kernel] = counts
    return out


def backlog(window: harness.Window) -> dict:
    """Mean requests owed (queued or in a slot) over each half of the window,
    and the attempted requests still owed when it closed."""
    mid = window.start + window.seconds / 2
    first = [n for t, n in window.queue_depth if window.start <= t <= mid]
    second = [n for t, n in window.queue_depth if mid < t <= window.end]
    owed = sum(1 for r in window.reqs if not (r.finished and r.finished_at <= window.end))
    return {"owed_mean_first_half": statistics.fmean(first) if first else 0.0,
            "owed_mean_second_half": statistics.fmean(second) if second else 0.0,
            "owed_at_close": owed}


def latency(window: harness.Window) -> dict:
    """What a user of the window saw, beside the metrics: time to first token
    and the wait for admission over every attempted request (one never
    admitted or served waits to the end of the run), token gaps, and every
    output token in the window over the window.  Printed, not bounded: over
    seeds their spread is wider than any bound (PERF.md)."""
    w = window
    t = stats.ttfts([r.due for r in w.reqs], [r.first_token for r in w.reqs], w.run_end)
    q = [w.run_end - r.due if r.admit_began is None else r.admit_began - r.due for r in w.reqs]
    g = stats.token_gaps([r.token_times for r in w.served], w.start, w.end)
    out = {"output_tokens_per_s": stats.tokens_per_s([r.token_times for r in w.served],
                                                     w.start, w.end)}
    if t:
        out.update(ttft_p50_s=stats.percentile(t, 50), ttft_p90_s=stats.percentile(t, 90),
                   ttft_mean_s=statistics.fmean(t), queue_wait_p90_s=stats.percentile(q, 90))
    if g:
        out.update(gap_p50_ms=1e3 * stats.percentile(g, 50),
                   gap_p99_ms=1e3 * stats.percentile(g, 99),
                   gap_mean_ms=1e3 * statistics.fmean(g))
    return out


def written_back(window: harness.Window) -> dict:
    """What the engine wrote back to the store inside the window: its
    planner decides, per fresh context."""
    w = [b for t, b in window.written_back if window.start <= t <= window.end]
    return {"written_back": len(w), "written_back_bytes": sum(w)}


def reused_share(window: harness.Window) -> Optional[float]:
    """Store (``kvcache/hierarchy.py`` lookup and fetch): the share, in
    percent, of the context tokens of the requests admitted inside the
    window (the lead-in's too) that came from stored KV (the engine's
    ``KVLoaded.matched_tokens``)."""
    w = window
    admitted = [r for r in w.served
                if r.admitted is not None and w.start <= r.admitted <= w.end]
    ctx = sum(r.ctx_len for r in admitted)
    return 100.0 * sum(r.matched for r in admitted) / ctx if ctx else None


def phase_counts(window: harness.Window) -> dict:
    end = window.end
    return {
        "lead_in": len(window.carried),
        "attempted": len(window.reqs),
        "admitted_in_window": sum(r.admitted is not None and r.admitted <= end for r in window.reqs),
        "first_token_in_window": sum(r.first_token is not None and r.first_token <= end
                                     for r in window.reqs),
        "finished": sum(r.finished for r in window.reqs),
        "failed": sum(r.first_token is None for r in window.reqs),
        "steps_in_window": sum(s.in_window for s in window.steps),
        "admissions_in_window": sum(s.in_window and s.kind == "admit" for s in window.steps),
    }


def run_cell(
    bench: dict, w: dict, *, seed: int, seconds: float, device, n_devices: int,
    metrics: List[dict], trace_dir: Optional[Path] = None, t_start: Optional[float] = None,
    notes: Optional[dict] = None,
    configs_dir: Path = harness.CONFIGS, traffic_dir: Path = traffic_mod.TRAFFIC_DIR,
    limits_dir: Path = correct.LIMITS_DIR, models_dir: Path = harness.MODELS,
    engine_hook: Optional[Callable] = None,
):
    """One run: returns (result, checked).  ``engine_hook(engine)`` may
    replace parts of the engine before the fill (the fault tests)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    clog = CompileLog()
    conf = harness.load_config(w["config"], configs_dir)
    spec = traffic_mod.load(w["traffic"], w["config"], traffic_dir)
    say("device", platform=device.platform, device_kind=device.device_kind,
        count=n_devices, **(notes or {}))
    engine, dims = harness.build(conf, seed, device, models_dir=models_dir)
    model = harness.model_of(conf, models_dir)
    progs = harness.programs(engine)
    if engine_hook is not None:
        engine_hook(engine)
    traffic = traffic_mod.generate(spec, seed, seconds, dims.vocab)
    t0 = time.perf_counter()
    matched = harness.fill(engine, traffic)
    t_fill = time.perf_counter() - t0
    warmed = harness.warm(engine, traffic, matched, log=lambda m: say("warm", note=m))
    setup_s = time.perf_counter() - t_start
    from repro.kernels import ops

    say("setup", setup_s=setup_s, fill_s=t_fill, documents=len(traffic.docs),
        document_tokens=sum(map(len, traffic.docs)), requests=len(traffic.requests),
        rate_per_s=traffic.rate_per_s, warmed=warmed, compile_s=clog.compile_s)
    say("dispatch", counts=ops.dispatch_counts(),
        note="kernel or jnp per attention op, as the set-up traced its programs; not executions")

    tracing = trace_dir is not None
    if tracing:
        trace_dir.mkdir(parents=True, exist_ok=True)
    started, stopped = [], []

    def window_start():
        if tracing:
            jax.profiler.start_trace(str(trace_dir))
            started.append(time.perf_counter())

    def window_end():
        if started and not stopped:
            jax.profiler.stop_trace()
            stopped.append(time.perf_counter())

    t_serve = time.perf_counter()
    window = harness.serve(engine, traffic, seconds, annotate=tracing,
                           on_window_start=window_start, on_window_end=window_end)
    window_end()
    late = sorted(window.generator_late)
    say("generator", late_max_s=late[-1] if late else 0.0,
        late_p50_s=stats.percentile(late, 50) if late else 0.0,
        late_p99_s=stats.percentile(late, 99) if late else 0.0,
        note="submit time minus due time; a step in progress delays the submit")
    in_window = clog.between(window.start, window.end)
    say("compilations_in_window", **in_window)
    say("compilations_in_lead_in", **clog.between(t_serve, window.start))
    if in_window["compiles"] or in_window["cache_loads"]:
        raise CompiledInWindow(f"programs compiled or loaded inside the window: {in_window}")
    counts = phase_counts(window)
    say("requests", **counts)
    say("backlog", **backlog(window))
    say("latency", **latency(window))
    say("store", reused_context_share=reused_share(window), **written_back(window))
    stats_mem = device.memory_stats() or {}
    peak_mem = stats_mem.get("peak_bytes_in_use")
    say("memory", peak_bytes_in_use=peak_mem, bytes_limit=stats_mem.get("bytes_limit"))

    finished = served(window, traffic)
    del engine
    harness.free_device()
    t0 = time.perf_counter()
    from bench import weights as bench_weights

    wts = bench_weights.make(model, dims, seed, device)
    read = correct.readings(model, wts, dims, correct.sample(finished, seed))
    del wts
    harness.free_device()
    say("check", seconds=time.perf_counter() - t0, **read)
    lim = correct.limits(w["name"], limits_dir)
    ok = bool(finished) and correct.judge(read, lim)
    checked = {k: {"value": read[k], "limit": v} for k, v in lim.items()}

    reduction = None
    if tracing:
        from bench import trace as trace_mod

        reduction = trace_mod.reduce_dir(trace_dir, window)
    peak = None
    if device.platform == "tpu":
        peak = peaks(device.device_kind)
    view = RunView(window=window, dims=dims, peak=peak, setup_s=setup_s, trace=reduction,
                   model=model, programs=progs)
    if reduction is not None and peak is not None:
        say("roofline_bounds", **roofline_bounds(view))
    out = {}
    for m in metrics:
        v = reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind, "count": n_devices,
           "memory_peak_bytes": peak_mem}
    result = {"correct": ok, "attempted": counts["attempted"], "failed": counts["failed"],
              "metrics": out, "device": dev}
    if reduction is not None:
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checked"] = checked
    return result, checked
