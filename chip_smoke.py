#!/usr/bin/env python3
"""Bring-up smoke run of the serving engine on a TPU.

Default (one chip): serves qwen2-1.5b at its published widths in bf16, with
random weights and workload from ``--seed``, through the launcher
(``repro.launch.serve``) twice — the launcher's default engine path (packed
admission + dense decode), then ``paged_decode=True, unified_step=True`` —
on the same 16 requests (4 contexts of 1024 tokens, each asked 4 times;
prompt 64, output 16, 8 slots, max_len 2048).  Then one reused request's
first-token logits from its stored KV + suffix are checked against a
full-recompute prefill and against a float32 jnp forward at highest matmul
precision; the logits of its next token, through the dense decode, paged
decode and unified-step kernels, against the float32 forward too.  Two
negative controls must land outside the tolerance: a lower-precision run,
and the stored KV of another context.

``--chips 4`` runs only the cluster phase: a 4-replica ``ServingCluster``
behind the affinity router with each replica on its own chip, compared with
the same cluster stacked on chip 0 (routing decisions and logits).

Prints reuse hits, per-op kernel/jnp dispatch counts, host wall-clock and
compile seconds (not a benchmark), device memory, and as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits non-zero, printing no result, when JAX finds no TPU, when the
repository's sources are not beside this file, or when any phase fails.

    python3 chip_smoke.py [--seed N]
    python3 chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

LAUNCH_ARGV = [
    "--arch", "qwen2-1.5b", "--no-reduced",
    "--requests", "16", "--contexts", "4",
    "--context-len", "1024", "--prompt-len", "64", "--output-len", "16",
    "--slots", "8", "--policy", "always",
]
# EngineConfig fields the launcher has no flag for
ENGINE = {"max_len": 2048}

# Logit tolerance: relative L2 error ||x - ref|| / ||ref|| over the vocab.
# bf16 keeps 8 significant bits (unit roundoff u = 2^-8 ~ 0.0039).  The
# matmuls accumulate in f32, but every layer rounds its residual stream,
# q/k/v, attention output and MLP activations to bf16, and 28 random-weight
# layers carry those roundings to the logits: on a v5e chip a bf16 prefill
# landed ~4u (0.0155) from the float32 reference.  TOL is ~13u, 3x that, for
# each pair among stored KV + suffix, full recompute, the three decode
# launches and float32: the bf16 paths round at different points (different
# launches and kernels), so each is its own ~4u draw around the float32
# logits.
TOL = 0.05
# Negative controls, each of which must land ABOVE TOL from the float32
# reference, or TOL could not tell a bf16 run from
#  - a lower-precision one: the full recompute with every weight rounded to
#    3 mantissa bits (u = 2^-4, 16x bf16's);
#  - a wrong stored KV: the request's prompt prefilled over ANOTHER
#    context's stored artifact (same length, same positions).
CONTROL_MANTISSA_BITS = 3
# the unified step's idle query position (serving.engine's padding)
IDLE_POS = -(2 ** 30)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def rel_err(x, ref) -> float:
    import numpy as np

    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-30))


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def serve_phase(name, argv, clock, *, params=None, **overrides):
    """Serve the launcher workload once; returns (engine, report)."""
    from repro.kernels import ops
    from repro.launch import serve as launch

    ops.reset_dispatch_counts()
    c0, t0 = clock.seconds, time.perf_counter()
    engine, summary = launch.serve(
        launch.parse_args(argv), params=params, **ENGINE, **overrides)
    wall = time.perf_counter() - t0
    report = {
        "phase": name,
        "requests": summary.n_requests,
        "reuse_hits": summary.reuse_hits,
        "dispatch": ops.dispatch_counts(),
        "host_wall_s": round(wall, 3),
        "compile_s": round(clock.seconds - c0, 3),
    }
    return engine, report


def check_phase(report, kernels, strict):
    """Print a phase's report; then every op in ``kernels`` must have taken
    its Pallas kernel, ops in ``strict`` (no short-prefill rule) must never
    have taken the jnp path, and a serving phase must have reused a
    context."""
    print(json.dumps(report), flush=True)
    d = report["dispatch"]
    for op in kernels:
        if d.get(op, {}).get("kernel", 0) < 1:
            fail(f"{report['phase']}: no compiled {op} call ({d})")
    for op in strict:
        if d.get(op, {}).get("jnp", 0):
            fail(f"{report['phase']}: {op} fell back to jnp ({d})")
    if report.get("reuse_hits", 1) < 1:
        fail(f"{report['phase']}: no reuse hits")


def stored_artifact(engine, context):
    """The engine's stored KV artifact of ``context``."""
    _, entry = engine.store.lookup(list(context))
    artifact, _ = engine.store.fetch(entry.entry_id)
    return artifact


def reused_request(engine, requests):
    """A request the engine served from its whole stored context, and the
    stored artifact."""
    for rec in engine.records:
        if rec.action == "load" and rec.matched_tokens == rec.context_len:
            break
    else:
        fail("no request reused its stored context")
    req = next(r for r in requests if r.req_id == rec.req_id)
    return req, stored_artifact(engine, req.context_tokens)


def full_prefill(cfg, params, tokens, max_len):
    """A plain full prefill (``api.prefill``) of ``tokens`` in ``cfg``'s
    dtype, through the dispatched kernels: (first-token logits, state)."""
    import jax
    import jax.numpy as jnp

    from repro.models import registry

    api = registry.get_model(cfg)
    fwd = jax.jit(lambda p, t, s: api.prefill(p, cfg, t, s))
    logits, state = fwd(params, jnp.asarray([tokens], jnp.int32),
                        api.init_state(cfg, 1, max_len))
    return jax.block_until_ready(logits[0]), state


def decode_logits(cfg, params, state, token, block=128):
    """Logits after ``token`` is appended to the batch-1 prefilled ``state``,
    through each decode launch the engine serves with: dense decode
    (``decode_attention``), paged decode (``paged_decode``) and a decode row
    of the unified step (``chunked_prefill``).  The block pool is the
    reserved dump block (pool block 0, where the unified step's padding
    tokens write) followed by the slot's cache rows in table order."""
    import jax
    import jax.numpy as jnp

    from repro.kvcache import paged
    from repro.models import registry

    api = registry.get_model(cfg)
    n = int(state.pos[0])
    tok = jnp.asarray([[token]], jnp.int32)
    dense = jax.jit(lambda p, t, s: api.decode(p, cfg, t, s)[0])(params, tok, state)

    def rows(x):  # [periods, 1, L, KV, hd] -> dump block + [periods, L, KV, hd]
        return jnp.concatenate([jnp.zeros_like(x[:, 0, :block]), x[:, 0]], axis=1)

    pool = tuple(
        paged.BlockCache(paged.KVCache(rows(c.attn.k), rows(c.attn.v)), None)
        for c in state.caches
    )
    n_blocks = state.caches[0].attn.k.shape[2] // block
    table = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None]
    paged_lg = jax.jit(lambda p, t, c, bt, pos: api.decode_paged(
        p, cfg, t, c, block_table=bt, pos=pos, block=block)[0])(
        params, tok, pool, table, jnp.asarray([n], jnp.int32))
    chunk = jnp.zeros((1, block), jnp.int32).at[0, 0].set(token)
    q_pos = jnp.full((1, block), IDLE_POS, jnp.int32).at[0, 0].set(n)
    unified = jax.jit(lambda p, t, c, bt, qp, li: api.prefill_chunked(
        p, cfg, t, c, block_table=bt, q_pos=qp, last_idx=li, block=block)[0])(
        params, chunk, pool, table, q_pos, jnp.zeros((1,), jnp.int32))
    return jax.block_until_ready(
        {"dense": dense[0], "paged": paged_lg[0], "unified": unified[0]})


def f32_reference_logits(cfg, params, token_lists, max_len):
    """First-token logits after each of ``token_lists``, from a float32 jnp
    forward at highest matmul precision."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import common

    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    p32 = common.cast_tree(params, jnp.float32)
    ops.set_kernel_mode("ref")
    try:
        with jax.default_matmul_precision("highest"):
            return [full_prefill(cfg32, p32, t, max_len)[0] for t in token_lists]
    finally:
        ops.set_kernel_mode(None)


def low_precision_weights(params):
    """Params with every float rounded to ``CONTROL_MANTISSA_BITS`` mantissa
    bits (exponent range kept).  ``reduce_precision`` is kept by XLA, where
    a round trip through a narrow dtype may be simplified away."""
    import jax
    import jax.numpy as jnp

    def q(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return jax.lax.reduce_precision(
            x, exponent_bits=8, mantissa_bits=CONTROL_MANTISSA_BITS)

    return jax.jit(lambda p: jax.tree_util.tree_map(q, p))(params)


def logits_phase(engine, args, setup):
    """For one reused request: its first-token logits from its stored KV +
    suffix (the packed admission launch) and from a full-recompute prefill,
    and the logits of its next token (the recompute's greedy choice) through
    the three decode launches, each vs the float32 reference; then the two
    negative controls."""
    import numpy as np

    from repro.kernels import ops
    from repro.launch import serve as launch

    ops.reset_dispatch_counts()
    requests = launch.workload(setup.cfg, args)
    req, artifact = reused_request(engine, requests)
    ctx, prompt = list(req.context_tokens), list(req.prompt_tokens)
    other = next(r for r in requests if list(r.context_tokens) != ctx)
    max_len = setup.engine_cfg.max_len
    reuse = engine.prefill_logits(ctx, prompt, artifact)
    full, state = full_prefill(setup.cfg, engine.params, ctx + prompt, max_len)
    token = int(np.argmax(np.asarray(full)))
    dec = decode_logits(setup.cfg, engine.params, state, token)
    del state
    dispatch = ops.dispatch_counts()
    wrong_kv = engine.prefill_logits(
        ctx, prompt, stored_artifact(engine, other.context_tokens))
    ref, ref_next = f32_reference_logits(
        setup.cfg, engine.params, [ctx + prompt, ctx + prompt + [token]], max_len)
    low, _ = full_prefill(setup.cfg, low_precision_weights(engine.params),
                          ctx + prompt, max_len)
    checked = {
        "reuse_vs_recompute": rel_err(reuse, full),
        "reuse_vs_f32": rel_err(reuse, ref),
        "recompute_vs_f32": rel_err(full, ref),
        **{f"decode_{k}_vs_f32": rel_err(v, ref_next) for k, v in dec.items()},
    }
    controls = {
        "low_precision_vs_f32": rel_err(low, ref),
        "wrong_kv_vs_f32": rel_err(wrong_kv, ref),
    }
    ok = max(checked.values()) <= TOL and min(controls.values()) > TOL
    return {
        "phase": "logits", "req_id": req.req_id, "matched": len(ctx),
        "wrong_kv_req_id": other.req_id, "decode_token": token,
        "dispatch": dispatch, "rel_err": checked, "controls": controls,
        "tol": TOL, "control_mantissa_bits": CONTROL_MANTISSA_BITS, "ok": ok,
    }


def single_chip(clock, argv):
    import jax

    from repro.launch import serve as launch

    engine, rep = serve_phase("default", argv, clock)
    check_phase(rep, ["packed_prefill", "decode_attention"], ["decode_attention"])
    _, rep = serve_phase("paged_unified", argv, clock, params=engine.params,
                         paged_decode=True, unified_step=True)
    check_phase(rep, ["chunked_prefill", "paged_decode"],
                ["chunked_prefill", "paged_decode"])
    largs = launch.parse_args(argv)
    rep = logits_phase(engine, largs, launch.setup(largs, **ENGINE))
    decode_ops = ["decode_attention", "paged_decode", "chunked_prefill"]
    check_phase(rep, ["flash_prefill"] + decode_ops, decode_ops)
    if not rep["ok"]:
        fail(f"logits outside tolerance {TOL}, or a control inside it: "
             f"{rep['rel_err']} {rep['controls']}")
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use")}))


def cluster_run(setup, args, params, devices):
    from repro.launch import serve as launch
    from repro.serving import events as ev
    from repro.serving.cluster import ClusterConfig, ServingCluster
    from repro.serving.router import AffinityRouter

    cluster = ServingCluster(
        setup.cfg, params, cluster_cfg=ClusterConfig(n_replicas=4),
        engine_cfg=setup.engine_cfg, router=AffinityRouter(),
        planner_factory=setup.planner_factory, pricing=setup.pricing,
        perf=setup.perf, devices=devices,
    )
    for req in launch.workload(setup.cfg, args):
        cluster.submit(req)
    summary = cluster.run()
    routes = sorted(
        (e.req_id, e.replica) for _, e in cluster.events
        if isinstance(e, ev.RequestRouted)
    )
    return cluster, summary, routes


def replica_devices(cluster):
    """Per replica, the devices holding its params and KV state."""
    import jax

    return [
        sorted({str(d) for leaf in jax.tree_util.tree_leaves(
            (e.params, e._state, getattr(e, "_pool_caches", None)))
            for d in leaf.devices()})
        for e in cluster.replicas
    ]


def four_chips(clock, argv):
    import jax

    from repro.launch import serve as launch

    devs = jax.devices()[:4]
    largs = launch.parse_args(argv)
    setup = launch.setup(largs, **ENGINE)
    params = launch.init_params(setup.cfg, largs.seed)
    t0, c0 = time.perf_counter(), clock.seconds
    spread, s_sum, s_routes = cluster_run(setup, largs, params, devs)
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use") for d in devs}
    stacked, t_sum, t_routes = cluster_run(setup, largs, params, None)
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    placed, on_dev0 = replica_devices(spread), replica_devices(stacked)
    if sorted(placed) != sorted([str(d)] for d in devs):
        fail(f"replicas are not on four distinct devices: {placed}")
    if on_dev0 != [[str(devs[0])]] * 4:
        fail(f"stacked replicas are not all on {devs[0]}: {on_dev0}")
    if s_routes != t_routes:
        fail(f"routing differs: spread {s_routes} vs stacked {t_routes}")
    errs = {}
    for i, (a, b) in enumerate(zip(spread.replicas, stacked.replicas)):
        if not any(r.action == "load" for r in a.records):
            continue
        req, artifact = reused_request(a, launch.workload(setup.cfg, largs))
        ctx, prompt = list(req.context_tokens), list(req.prompt_tokens)
        errs[i] = rel_err(a.prefill_logits(ctx, prompt, artifact),
                          b.prefill_logits(ctx, prompt, artifact))
    if not errs or max(errs.values()) > TOL:
        fail(f"spread vs stacked logits: {errs}")
    print(json.dumps({
        "phase": "cluster4", "replica_devices": placed, "bytes_in_use": in_use,
        "routes": len(s_routes), "reuse_hits": [s_sum.reuse_hits, t_sum.reuse_hits],
        "logits_rel_err": errs, "host_wall_s": round(wall, 3),
        "compile_s": round(comp, 3),
    }))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the workload")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"repository sources not found at {SRC}")
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < args.chips:
        fail(f"{args.chips} chips asked for, {len(devs)} found")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock()
    argv = LAUNCH_ARGV + ["--seed", str(args.seed)]
    t0 = time.perf_counter()
    if args.chips == 1:
        single_chip(clock, argv)
    else:
        four_chips(clock, argv)
    print(json.dumps({
        "host_wall_s_total": round(time.perf_counter() - t0, 3),
        "compile_s_total": round(clock.seconds, 3),
        "compile_cache": cache, "compile_cache_hits": clock.cache_hits,
        "note": "host wall-clock of a smoke run, not a benchmark",
    }))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips,
    }}))


if __name__ == "__main__":
    main()
