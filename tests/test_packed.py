"""Packed ragged suffix-prefill: parity with the per-request path.

Three levels, mirroring the layering:

  * kernel  — ``ref.packed_attention_ref`` / Pallas ``packed_prefill`` vs the
    per-segment oracle, across MHA / GQA / sliding-window and partial-reuse
    offsets;
  * model   — ``lm.prefill_packed`` vs per-request ``lm.prefill`` over real
    reduced archs (logits AND resulting caches, to f32 reordering tolerance);
  * engine  — batched admission vs ``admit_batch=1`` produces identical
    generations, emits multi-request BatchAdmitted events, spends strictly
    less modeled admission time, and reuses jit buckets (hit counters).

(batch=1 golden parity vs the seed engine lives in tests/test_serving.py.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import ops, ref
from repro.kvcache import paged
from repro.models import lm, registry
from repro.serving import AlwaysReusePlanner, EngineConfig, Request, ServingEngine
from repro.serving import events as ev
from repro.serving.jit_cache import JitBucketStats

# The packed and per-request launches run matmuls and reductions of
# different shapes, which XLA may sum in a different order: f32 results
# then differ in the last bits (observed <= 3e-6 at magnitudes <= 5 on the
# CPU backend, ~1e-6 relative, after two layers).  2e-5 is ~10x that, and
# far below the O(0.1) error a wrong mask, offset or segment id causes.
F32_REORDER_TOL = 2e-5


def assert_reorder_close(got, want):
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want),
        rtol=F32_REORDER_TOL, atol=F32_REORDER_TOL,
    )


# --------------------------------------------------------------------------- #
# Kernel level
# --------------------------------------------------------------------------- #
def _pack_qkv(segs, H, KV, hd, align, seed=0):
    """Build per-segment q/k/v plus the packed buffers + index arrays.

    segs: list of (matched, n_new).  Returns (per_segment list, packed dict).
    Each segment's kv span holds [matched prefix rows ++ n_new new rows] at
    an align-multiple start — the engine's layout, built by hand here so the
    kernel is tested independently of the paged-state machinery."""
    rng = np.random.default_rng(seed)
    kv_len = 0
    per = []
    for matched, n_new in segs:
        total = matched + n_new
        alloc = -(-total // align) * align  # the segment's aligned kv span
        k = np.zeros((1, alloc, KV, hd), np.float32)
        v = np.zeros((1, alloc, KV, hd), np.float32)
        k[:, :total] = rng.standard_normal((1, total, KV, hd))
        v[:, :total] = rng.standard_normal((1, total, KV, hd))
        q = rng.standard_normal((1, n_new, H, hd)).astype(np.float32)
        kv_pos = np.full((1, alloc), -1, np.int32)
        kv_pos[0, :total] = np.arange(total, dtype=np.int32)
        per.append(
            dict(
                q=q, k=k, v=v,
                q_pos=np.arange(matched, total, dtype=np.int32)[None],
                kv_pos=kv_pos,
                start=kv_len, matched=matched, n_new=n_new, total=total,
                alloc=alloc,
            )
        )
        kv_len += alloc
    Sq = sum(s["n_new"] for s in per)
    kp = np.full((1, kv_len), -1, np.int32)
    ks = np.full((1, kv_len), -2, np.int32)
    K = np.zeros((1, kv_len, KV, hd), np.float32)
    V = np.zeros((1, kv_len, KV, hd), np.float32)
    Q = np.zeros((1, Sq, H, hd), np.float32)
    qp = np.full((1, Sq), -(2**30), np.int32)
    qs = np.full((1, Sq), -1, np.int32)
    off = 0
    for i, s in enumerate(per):
        rows = slice(s["start"], s["start"] + s["alloc"])
        K[0, rows], V[0, rows] = s["k"][0], s["v"][0]
        kp[0, rows] = s["kv_pos"][0]
        ks[0, rows.start : rows.start + s["total"]] = i
        q = slice(off, off + s["n_new"])
        Q[0, q] = s["q"][0]
        qp[0, q] = s["q_pos"][0]
        qs[0, q] = i
        s["q_slice"] = q
        off += s["n_new"]
    return per, dict(q=Q, k=K, v=V, q_pos=qp, kv_pos=kp, q_seg=qs, kv_seg=ks)


@pytest.mark.parametrize(
    "H,KV,window",
    [(4, 4, None), (4, 2, None), (4, 2, 24)],  # MHA, GQA, GQA+sliding-window
)
def test_packed_ref_matches_per_segment_exactly(H, KV, window):
    """Segment-masked packed attention == running each segment alone (to f32
    reordering tolerance), across partial-reuse offsets (matched 0 / mid /
    full-prefix)."""
    segs = [(0, 40), (32, 24), (56, 8)]
    per, packed = _pack_qkv(segs, H, KV, hd=16, align=64)
    out = ref.packed_attention_ref(
        jnp.asarray(packed["q"]), jnp.asarray(packed["k"]), jnp.asarray(packed["v"]),
        q_pos=jnp.asarray(packed["q_pos"]), kv_pos=jnp.asarray(packed["kv_pos"]),
        q_seg=jnp.asarray(packed["q_seg"]), kv_seg=jnp.asarray(packed["kv_seg"]),
        causal=True, window=window,
    )
    for s in per:
        alone = ref.attention_ref(
            jnp.asarray(s["q"]), jnp.asarray(s["k"]), jnp.asarray(s["v"]),
            q_pos=jnp.asarray(s["q_pos"]), kv_pos=jnp.asarray(s["kv_pos"]),
            causal=True, window=window,
        )
        assert_reorder_close(out[0, s["q_slice"]], alone[0])


@pytest.mark.parametrize("H,KV,window", [(4, 4, None), (8, 2, None), (4, 2, 96)])
def test_packed_pallas_interpret_matches_ref(H, KV, window):
    """The Pallas packed kernel (interpret mode) agrees with the jnp oracle
    on a multi-block packed sequence (exercises the block-aligned segment
    spans and the fully-masked cross-segment kv blocks)."""
    from repro.kernels import packed_prefill

    segs = [(0, 150), (128, 90), (64, 33)]
    per, packed = _pack_qkv(segs, H, KV, hd=16, align=128, seed=3)
    args = {k: jnp.asarray(v) for k, v in packed.items()}
    want = ref.packed_attention_ref(
        args["q"], args["k"], args["v"], q_pos=args["q_pos"],
        kv_pos=args["kv_pos"], q_seg=args["q_seg"], kv_seg=args["kv_seg"],
        causal=True, window=window,
    )
    got = packed_prefill.packed_flash_attention(
        args["q"], args["k"], args["v"], q_pos=args["q_pos"],
        kv_pos=args["kv_pos"], q_seg=args["q_seg"], kv_seg=args["kv_seg"],
        causal=True, window=window, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6)


def test_ops_packed_attention_dispatches_on_cpu():
    segs = [(0, 16), (8, 8)]
    per, packed = _pack_qkv(segs, 4, 4, hd=8, align=32, seed=7)
    args = {k: jnp.asarray(v) for k, v in packed.items()}
    out = ops.packed_attention(
        args["q"], args["k"], args["v"], q_pos=args["q_pos"],
        kv_pos=args["kv_pos"], q_seg=args["q_seg"], kv_seg=args["kv_seg"],
    )
    assert out.shape == args["q"].shape and np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------------------- #
# Model level
# --------------------------------------------------------------------------- #
def _setup(arch, seed=0):
    cfg = reduced_config(get_config(arch))
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(seed), cfg)
    return cfg, api, params


@pytest.mark.parametrize("arch", ["llama-7b", "qwen2-1.5b", "olmoe-1b-7b"])
def test_model_packed_prefill_bit_exact(arch):
    """lm.prefill_packed == per-request lm.prefill: last-token logits AND the
    per-segment KV rows scattered back (to f32 reordering tolerance),
    including a partial-reuse segment whose prefix KV is preloaded from a
    stored artifact."""
    cfg, api, params = _setup(arch)
    rng = np.random.default_rng(2)
    max_len = 128
    ctx0 = list(map(int, rng.integers(0, cfg.vocab, 48)))
    ctx1 = ctx0[:32] + list(map(int, rng.integers(0, cfg.vocab, 16)))
    pr0 = list(map(int, rng.integers(0, cfg.vocab, 8)))
    pr1 = list(map(int, rng.integers(0, cfg.vocab, 8)))

    st_a = api.init_state(cfg, 1, max_len)
    _, st_a = api.prefill(params, cfg, jnp.asarray([ctx0], jnp.int32), st_a)
    art = paged.extract_slot(cfg, st_a, 0, 48)

    def per_request(ctx, prompt, matched, artifact=None):
        st = api.init_state(cfg, 1, max_len)
        if artifact is not None:
            st = paged.insert_slot(cfg, st, 0, artifact, n_tokens=matched)
        logits, st = api.prefill(
            params, cfg, jnp.asarray([ctx[matched:] + prompt], jnp.int32), st
        )
        return logits, st

    lg0, st0 = per_request(ctx0, pr0, 0)
    lg1, st1 = per_request(ctx1, pr1, 32, artifact=art)

    layout = paged.pack_layout([0, 1], [0, 32], [56, 24], align=128)
    arrays = paged.pack_arrays(layout, [ctx0 + pr0, ctx1[32:] + pr1])
    caches = paged.build_packed_caches(cfg, layout, [None, art])
    logits, new_caches = lm.prefill_packed(
        params, cfg, jnp.asarray(arrays["tokens"]), caches,
        q_pos=jnp.asarray(arrays["q_pos"]), q_seg=jnp.asarray(arrays["q_seg"]),
        q_rows=jnp.asarray(arrays["q_rows"]), kv_pos=jnp.asarray(arrays["kv_pos"]),
        kv_seg=jnp.asarray(arrays["kv_seg"]),
        last_idx=jnp.asarray([s.q_last for s in layout.segments], jnp.int32),
    )
    assert_reorder_close(logits[0], lg0[0])
    assert_reorder_close(logits[1], lg1[0])
    for i, (st, n) in enumerate([(st0, 56), (st1, 56)]):
        got = paged.packed_to_artifact(cfg, new_caches, layout.segments[i], n)
        for c_got, c_want in zip(got.caches, st.caches):
            assert_reorder_close(c_got.attn.k, c_want.attn.k[:, :, :n])
            assert_reorder_close(c_got.attn.v, c_want.attn.v[:, :, :n])


def test_pack_layout_alignment_and_buckets():
    layout = paged.pack_layout([0, 1, 2], [0, 32, 16], [40, 24, 90], align=128)
    starts = [s.kv_start for s in layout.segments]
    assert starts == [0, 128, 256]  # every span starts at an align multiple
    assert layout.q_len == 256 and layout.q_tokens == 154  # pow2 bucket
    assert layout.kv_len == 512
    assert 0 < layout.occupancy <= 1
    assert paged.pack_bucket(17) == 32 and paged.pack_bucket(4) == 16
    assert paged.pack_bucket(128) == 128


def test_packable_arch_predicate():
    assert paged.packable_arch(reduced_config(get_config("llama-7b")), 128)
    assert paged.packable_arch(reduced_config(get_config("olmoe-1b-7b")), 128)
    # ring-buffer SWA (window < max_len), SSM, hybrid, enc-dec: per-request
    assert not paged.packable_arch(reduced_config(get_config("mixtral-8x22b")), 128)
    assert not paged.packable_arch(reduced_config(get_config("mamba2-1.3b")), 128)
    assert not paged.packable_arch(
        reduced_config(get_config("jamba-1.5-large-398b")), 128
    )
    assert not paged.packable_arch(reduced_config(get_config("whisper-tiny")), 128)


# --------------------------------------------------------------------------- #
# Engine level
# --------------------------------------------------------------------------- #
def _burst_requests(cfg, n=8, n_ctx=2, ctx_len=64, prompt_len=8, new=3, seed=0):
    rng = np.random.default_rng(seed)
    ctxs = [list(map(int, rng.integers(0, cfg.vocab, ctx_len))) for _ in range(n_ctx)]
    return [
        dict(
            req_id=i,
            context_tokens=ctxs[i % n_ctx],
            prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, prompt_len))),
            max_new_tokens=new,
            arrival_s=0.0,  # burst: everything admissible at once
            expected_reuses=n // n_ctx,
        )
        for i in range(n)
    ]


def _run_engine(cfg, params, reqs, **ec_kw):
    kw = dict(max_slots=4, max_len=128, chunk_tokens=16)
    kw.update(ec_kw)
    eng = ServingEngine(
        cfg, params, engine_cfg=EngineConfig(**kw), planner=AlwaysReusePlanner()
    )
    for r in reqs:
        eng.submit(Request(**r))
    events = []
    while not eng.idle:
        events.extend(eng.step())
    return eng, events


def test_engine_batched_admission_matches_single_and_is_faster():
    """A burst served by packed batch admission generates token-for-token what
    one-at-a-time admission generates, while spending strictly less modeled
    time in admission (shared kernel + single parameter read) and actually
    packing multiple requests per launch."""
    cfg, _, params = _setup("llama-7b")
    reqs = _burst_requests(cfg)
    eng_b, events_b = _run_engine(cfg, params, reqs, cost_arch="llama-7b")
    eng_s, _ = _run_engine(cfg, params, reqs, cost_arch="llama-7b", admit_batch=1)

    toks_b = {r.req_id: r.tokens for r in eng_b.records}
    toks_s = {r.req_id: r.tokens for r in eng_s.records}
    assert toks_b == toks_s
    batches = [e for e in events_b if isinstance(e, ev.BatchAdmitted)]
    assert batches and max(len(b.req_ids) for b in batches) > 1
    assert all(len(b.req_ids) >= 1 for b in batches)
    # >= 2x admission throughput on the burst (acceptance criterion floor)
    assert eng_b.admission_busy_s * 2 <= eng_s.admission_busy_s
    # packing occupancy + counters are exposed
    stats = eng_b.packed_stats()
    assert 0 < stats["occupancy"] <= 1
    assert stats["batches"] == len(batches)


def test_engine_batch_events_are_consistent():
    """Per-request lifecycle events survive batching: one RequestAdmitted /
    PlanChosen / PrefillDone / RequestFinished per request, time-ordered."""
    cfg, _, params = _setup("llama-7b")
    reqs = _burst_requests(cfg, n=6)
    eng, events = _run_engine(cfg, params, reqs)
    admitted = [e for e in events if isinstance(e, ev.RequestAdmitted)]
    plans = [e for e in events if isinstance(e, ev.PlanChosen)]
    prefills = [e for e in events if isinstance(e, ev.PrefillDone)]
    finished = [e for e in events if isinstance(e, ev.RequestFinished)]
    assert len(admitted) == len(plans) == len(prefills) == len(finished) == len(reqs)
    times = [e.t_s for e in events]
    assert times == sorted(times)
    assert ev.tokens_from_events(events) == {
        r.req_id: r.tokens for r in eng.records
    }


def test_jit_bucket_cache_stops_recompiling():
    """Steady-state: repeated same-shape batches land on already-seen jit
    buckets — zero misses after warmup."""
    cfg, _, params = _setup("llama-7b")
    reqs = _burst_requests(cfg, n=12, n_ctx=3)
    eng, _ = _run_engine(cfg, params, reqs, max_slots=2)
    stats = eng.packed_stats()["jit"]
    assert stats["misses"] == stats["n_buckets"] <= 3
    assert stats["hits"] == eng.batches - stats["misses"] > 0

    s = JitBucketStats()
    assert s.record((128, 256)) is False  # first sight compiles
    assert s.record((128, 256)) is True
    assert s.record((256, 256)) is False
    assert s.as_dict()["n_buckets"] == 2


def test_prefetch_lookup_carried_to_admission():
    """The prefetch pass's trie walk is reused at admission (no double walk)
    and invalidated by store mutation — generations unchanged either way."""
    cfg, _, params = _setup("llama-7b")
    rng = np.random.default_rng(4)
    ctx = list(map(int, rng.integers(0, cfg.vocab, 64)))
    reqs = [
        dict(
            req_id=i, context_tokens=ctx,
            prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, 8))),
            max_new_tokens=3, arrival_s=i * 0.01, expected_reuses=8,
        )
        for i in range(8)
    ]
    eng_p, _ = _run_engine(
        cfg, params, reqs, max_slots=1, cost_arch="llama-7b", prefetch_lookahead=4
    )
    eng_n, _ = _run_engine(cfg, params, reqs, max_slots=1, cost_arch="llama-7b")
    assert {r.req_id: r.tokens for r in eng_p.records} == {
        r.req_id: r.tokens for r in eng_n.records
    }
    assert eng_p.lookup_reuses > 0
    # every admission either reused the prefetch walk or walked once itself;
    # with the carry there are strictly fewer walks than lookups needed
    assert eng_p.lookup_walks + eng_p.lookup_reuses >= len(reqs)
    assert eng_p.lookup_reuses >= eng_n.lookup_reuses == 0


def test_non_packable_arch_still_serves_through_fallback():
    """SSM archs ride the per-request path under the batched API (no packed
    launch, identical reuse==recompute generations)."""
    cfg, _, params = _setup("mamba2-1.3b")
    reqs = _burst_requests(cfg, n=4, n_ctx=1)
    eng, events = _run_engine(cfg, params, reqs)
    assert not [e for e in events if isinstance(e, ev.BatchAdmitted)]
    assert eng.batches == 0 and len(eng.records) == len(reqs)
