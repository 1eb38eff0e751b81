"""Fused non-prefix reuse (CacheBlend-style): chunk-composite matching and
selective-recompute prefill.

Four levels, mirroring the layering:

  * invariants — hypothesis properties on ``CompositeMatch`` /
    ``FusedSchedule`` (spans partition the context, reused spans are
    content-identical to their source entries, the selected recompute count
    is exactly ceil(r * matched)) with a deterministic mirror;
  * kernel  — ``ref.fused_prefill_ref`` equals plain causal attention at
    full query coverage (bitwise) and the Pallas kernel (interpret mode)
    agrees with the oracle on gappy multi-block shapes;
  * model   — ``lm.prefill_fused`` at r=1.0 matches a full ``lm.prefill``
    (logits AND caches; bitwise, except qwen2 to f32 reordering tolerance);
    at r<1 reused rows pass through the launch untouched (bitwise);
  * engine  — fused admissions at r=1.0 generate token-for-token what full
    recompute generates under dense AND paged decode; partial r serves with
    consistent counters/events; BlendPlanner gates on cost.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config, reduced_config
from repro.kernels import ops, ref
from repro.kvcache import fusion, paged
from repro.kvcache.fusion import ChunkIndex, content_hashes, select_recompute
from repro.models import lm, registry
from repro.serving import (
    AlwaysReusePlanner,
    BlendPlanner,
    EngineConfig,
    Request,
    ServingEngine,
)
from repro.serving import events as ev
from repro.serving.planner import StoreLookup

# The fused launch and a plain prefill run matmuls and reductions of
# different shapes, which XLA may sum in a different order: f32 results
# then differ in the last bits (observed <= 2e-6 at magnitudes <= 4 on the
# CPU backend, ~1e-6 relative, after two layers).  2e-5 is ~10x that, and
# far below the O(0.1) error a wrong position, row or mask causes.
F32_REORDER_TOL = 2e-5

# Of the reduced archs, only qwen2 (the one with q/k/v biases) is summed in a
# different order by the two launches; the others still match bitwise
# (tolerance 0: assert_allclose then demands |got - want| <= 0).
R1_TOL = {"qwen2-1.5b": F32_REORDER_TOL}


def assert_reorder_close(got, want, tol=F32_REORDER_TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


# --------------------------------------------------------------------------- #
# CompositeMatch / FusedSchedule invariants
# --------------------------------------------------------------------------- #
def _assert_partition(spans, total):
    pos = 0
    for s in spans:
        assert s.start == pos and s.end > s.start, (spans, total)
        pos = s.end
    assert pos == total, (spans, total)


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_composite_match_and_schedule_invariants(data):
    chunk = data.draw(st.integers(2, 6), label="chunk_tokens")
    n_pool = data.draw(st.integers(1, 5), label="pool size")
    tok = st.integers(0, 30)  # pool alphabet; noise uses a disjoint one
    pool = [
        data.draw(st.lists(tok, min_size=chunk, max_size=chunk))
        for _ in range(n_pool)
    ]
    idx = ChunkIndex(chunk)
    entries = {}
    for e in range(data.draw(st.integers(1, 3), label="n entries")):
        picks = data.draw(
            st.lists(st.integers(0, n_pool - 1), min_size=1, max_size=4)
        )
        toks = sum((pool[i] for i in picks), [])
        eid = f"e{e}"
        idx.insert(toks, eid)
        entries[eid] = toks

    q_picks = data.draw(
        st.lists(st.integers(-1, n_pool - 1), min_size=0, max_size=6),
        label="query chunks (-1 = noise)",
    )
    query = []
    for i in q_picks:
        if i >= 0:
            query += pool[i]
        else:
            query += data.draw(
                st.lists(st.integers(31, 60), min_size=chunk, max_size=chunk)
            )
    query += data.draw(
        st.lists(st.integers(0, 60), min_size=0, max_size=chunk - 1),
        label="ragged tail",
    )

    m = idx.match(query)
    assert m.total_tokens == len(query)
    _assert_partition(m.spans, len(query))
    for s in m.reuse_spans:
        # chunk-aligned maximal runs...
        assert s.start % chunk == 0 and s.n_tokens % chunk == 0
        assert s.src_start >= 0
        # ...content-identical to the rows of the source entry they name...
        src = entries[s.entry_id]
        assert query[s.start : s.end] == src[s.src_start : s.src_start + s.n_tokens]
        # ...and carrying exactly their chunks' content hashes
        assert s.chunk_hashes == tuple(
            content_hashes(query[s.start : s.end], chunk)
        )

    r = data.draw(st.floats(0.0, 1.0), label="recompute_frac")
    sched = select_recompute(m, r)
    _assert_partition(sched.spans, len(query))
    assert sched.selected_tokens == math.ceil(r * m.matched_tokens)
    assert sched.reused_tokens == m.matched_tokens - sched.selected_tokens
    assert sched.reused_tokens + sched.recompute_tokens == len(query)
    for s in sched.spans:
        if s.kind != "reuse":
            continue
        src = entries[s.entry_id]
        assert query[s.start : s.end] == src[s.src_start : s.src_start + s.n_tokens]


def test_composite_match_deterministic_mirror():
    """Fixed example: permuted chunk order, adjacent-source merging, a miss
    chunk, and a ragged tail — exact span structure pinned."""
    chunk = 4
    c = [list(range(10 * i, 10 * i + chunk)) for i in range(4)]
    idx = ChunkIndex(chunk)
    idx.insert(c[0] + c[1] + c[2], "e0")
    # query: [c1 c2] (consecutive in e0 -> ONE merged span), noise, c0, tail
    noise = [99, 98, 97, 96]
    query = c[1] + c[2] + noise + c[0] + [1, 2]
    m = idx.match(query)
    got = [(s.start, s.end, s.kind, s.entry_id, s.src_start) for s in m.spans]
    assert got == [
        (0, 8, "reuse", "e0", 4),  # c1+c2 merged: source rows 4..12
        (8, 12, "recompute", None, -1),
        (12, 16, "reuse", "e0", 0),
        (16, 18, "recompute", None, -1),  # ragged tail
    ]
    assert m.matched_tokens == 12 and m.source_entries == ("e0",)

    sched = select_recompute(m, 0.5)  # budget ceil(0.5*12) = 6: 4 + 2 heads
    assert sched.selected_tokens == 6
    got = [(s.start, s.end, s.kind, s.src_start) for s in sched.spans]
    assert got == [
        (0, 4, "recompute", -1),  # head of the 8-token span (4 = floor+rem)
        (4, 8, "reuse", 8),
        (8, 14, "recompute", -1),  # noise gap + the c0 span's 2-token head,
        (14, 16, "reuse", 2),      # merged into one launch span
        (16, 18, "recompute", -1),
    ]

    # r=1.0: everything recomputes, one big span (the bit-exactness anchor)
    s1 = select_recompute(m, 1.0)
    assert [s.kind for s in s1.spans] == ["recompute"]
    assert s1.reused_tokens == 0 and s1.recompute_tokens == 18

    # eviction removes the owner's hashes
    idx.remove(c[0] + c[1] + c[2], "e0")
    assert len(idx) == 0
    assert idx.match(query).matched_tokens == 0


def test_chunk_index_survives_first_owner_eviction():
    """A chunk held by several entries stays matchable after the first
    owner's eviction — ownership falls to the next live entry instead of
    orphaning content another resident entry still holds."""
    chunk = 4
    c0, c1 = [1, 2, 3, 4], [5, 6, 7, 8]
    idx = ChunkIndex(chunk)
    idx.insert(c0 + c1, "e0")
    idx.insert(c1 + c0, "e1")  # same content, both owners registered
    assert idx.match(c1).reuse_spans[0].entry_id == "e0"
    idx.remove(c0 + c1, "e0")  # evict e0
    m = idx.match(c1 + c0)
    assert [s.entry_id for s in m.reuse_spans] == ["e1"]
    assert m.matched_tokens == 8
    idx.remove(c1 + c0, "e1")
    assert len(idx) == 0


def test_select_recompute_r0_is_pure_reuse():
    chunk = 4
    idx = ChunkIndex(chunk)
    idx.insert(list(range(8)), "e0")
    m = idx.match(list(range(4, 8)) + list(range(4)))
    sched = select_recompute(m, 0.0)
    assert sched.selected_tokens == 0
    assert sched.reused_tokens == m.matched_tokens == 8


# --------------------------------------------------------------------------- #
# Kernel level
# --------------------------------------------------------------------------- #
def _rand_qkv(rng, Sq, Skv, H, KV, hd):
    q = jnp.asarray(rng.standard_normal((1, Sq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, Skv, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, Skv, KV, hd)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("H,KV,window", [(4, 4, None), (4, 2, None), (4, 2, 24)])
def test_fused_ref_full_coverage_equals_plain_attention(H, KV, window):
    """With a query at EVERY position (r=1.0) the fused oracle is ordinary
    causal attention, bitwise."""
    rng = np.random.default_rng(0)
    S = 40
    q, k, v = _rand_qkv(rng, S, S, H, KV, 16)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    want = ref.attention_ref(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                             window=window)
    got = ref.fused_prefill_ref(q, k, v, q_pos=pos, kv_pos=pos, window=window)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("H,KV,window", [(4, 4, None), (8, 2, None), (4, 2, 96)])
def test_fused_pallas_interpret_matches_ref(H, KV, window):
    """The Pallas fused kernel (interpret mode) agrees with the jnp oracle on
    a gappy multi-block query set over a padded buffer (exercises the
    fully-masked-block early-out and the invalid-row tail)."""
    from repro.kernels import fused_prefill

    rng = np.random.default_rng(3)
    Skv, total, Sq = 384, 300, 140
    q, k, v = _rand_qkv(rng, Sq, Skv, H, KV, 16)
    kv_pos = np.full((1, Skv), -1, np.int32)
    kv_pos[0, :total] = np.arange(total)
    q_pos = np.sort(rng.choice(total, Sq, replace=False)).astype(np.int32)[None]
    want = ref.fused_prefill_ref(
        q, k, v, q_pos=jnp.asarray(q_pos), kv_pos=jnp.asarray(kv_pos),
        window=window,
    )
    got = fused_prefill.fused_flash_attention(
        q, k, v, q_pos=jnp.asarray(q_pos), kv_pos=jnp.asarray(kv_pos),
        window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6
    )


def test_ops_fused_prefill_dispatches_on_cpu():
    rng = np.random.default_rng(7)
    q, k, v = _rand_qkv(rng, 8, 32, 4, 4, 8)
    kv_pos = np.full((1, 32), -1, np.int32)
    kv_pos[0, :24] = np.arange(24)
    q_pos = np.asarray([[1, 5, 9, 13, 17, 20, 22, 23]], np.int32)
    out = ops.fused_prefill(
        q, k, v, q_pos=jnp.asarray(q_pos), kv_pos=jnp.asarray(kv_pos)
    )
    assert out.shape == q.shape and np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------------------- #
# Model level
# --------------------------------------------------------------------------- #
def _setup(arch, seed=0):
    cfg = reduced_config(get_config(arch))
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(seed), cfg)
    return cfg, api, params


def _fused_launch(cfg, params, sched, ctx, prompt, sources):
    layout = fusion.fused_layout(sched, len(prompt), align=128, bucket_min=16)
    arrays = fusion.fused_arrays(sched, ctx, prompt, layout)
    caches = fusion.build_fused_caches(cfg, sched, sources, layout.kv_len)
    logits, new_caches = lm.prefill_fused(
        params, cfg, jnp.asarray(arrays["tokens"]), caches,
        q_pos=jnp.asarray(arrays["q_pos"]), q_rows=jnp.asarray(arrays["q_rows"]),
        kv_pos=jnp.asarray(arrays["kv_pos"]),
        last_idx=jnp.asarray(arrays["last_idx"]),
    )
    return layout, caches, logits, new_caches


@pytest.mark.parametrize("arch", ["llama-7b", "qwen2-1.5b", "olmoe-1b-7b"])
def test_model_fused_prefill_r1_bit_exact(arch):
    """lm.prefill_fused at recompute_frac=1.0 == a plain full lm.prefill of
    the same sequence: last-token logits AND every context+prompt cache row
    (bitwise, or to ``R1_TOL[arch]``) — on a chunk-shuffled context the
    prefix path cannot serve."""
    cfg, api, params = _setup(arch)
    rng = np.random.default_rng(2)
    chunk = 16
    pool = [list(map(int, rng.integers(0, cfg.vocab, chunk))) for _ in range(4)]
    ctx_stored = pool[0] + pool[1] + pool[2]
    ctx_query = pool[2] + pool[0] + pool[3]  # shuffled + one fresh chunk
    prompt = list(map(int, rng.integers(0, cfg.vocab, 8)))

    st_a = api.init_state(cfg, 1, 128)
    _, st_a = api.prefill(params, cfg, jnp.asarray([ctx_stored], jnp.int32), st_a)
    art = paged.extract_slot(cfg, st_a, 0, len(ctx_stored))

    idx = ChunkIndex(chunk)
    idx.insert(ctx_stored, "e0")
    m = idx.match(ctx_query)
    assert m.matched_tokens == 2 * chunk  # non-prefix matches found

    sched = select_recompute(m, 1.0)
    layout, _, logits, new_caches = _fused_launch(
        cfg, params, sched, ctx_query, prompt, {"e0": art}
    )
    st_full = api.init_state(cfg, 1, 128)
    want, st_full = api.prefill(
        params, cfg, jnp.asarray([ctx_query + prompt], jnp.int32), st_full
    )
    tol = R1_TOL.get(arch, 0.0)
    assert_reorder_close(logits[0], want[0], tol)
    n = layout.total
    for got_c, want_c in zip(new_caches, st_full.caches):
        assert_reorder_close(got_c.attn.k[:, :, :n], want_c.attn.k[:, :, :n], tol)
        assert_reorder_close(got_c.attn.v[:, :, :n], want_c.attn.v[:, :, :n], tol)


def test_model_fused_prefill_partial_preserves_reused_rows():
    """At r < 1 the launch must not touch the preloaded reused rows: they
    flow through to the output caches bitwise (only recompute rows and the
    prompt are scattered)."""
    cfg, api, params = _setup("llama-7b")
    rng = np.random.default_rng(5)
    chunk = 16
    pool = [list(map(int, rng.integers(0, cfg.vocab, chunk))) for _ in range(3)]
    ctx_stored = pool[0] + pool[1] + pool[2]
    ctx_query = pool[1] + pool[2] + pool[0]
    prompt = list(map(int, rng.integers(0, cfg.vocab, 8)))

    st_a = api.init_state(cfg, 1, 128)
    _, st_a = api.prefill(params, cfg, jnp.asarray([ctx_stored], jnp.int32), st_a)
    art = paged.extract_slot(cfg, st_a, 0, len(ctx_stored))

    idx = ChunkIndex(chunk)
    idx.insert(ctx_stored, "e0")
    sched = select_recompute(idx.match(ctx_query), 0.25)
    assert sched.reused_tokens > 0 and sched.selected_tokens > 0
    _, caches, logits, new_caches = _fused_launch(
        cfg, params, sched, ctx_query, prompt, {"e0": art}
    )
    assert np.isfinite(np.asarray(logits)).all()
    for s in sched.spans:
        if s.kind != "reuse":
            continue
        rows = slice(s.start, s.end)
        for got_c, in_c in zip(new_caches, caches):
            assert np.array_equal(
                np.asarray(got_c.attn.k[:, :, rows]),
                np.asarray(in_c.attn.k[:, :, rows]),
            )
            assert np.array_equal(
                np.asarray(got_c.attn.v[:, :, rows]),
                np.asarray(in_c.attn.v[:, :, rows]),
            )


# --------------------------------------------------------------------------- #
# Engine level
# --------------------------------------------------------------------------- #
CHUNK = 16


def _shuffled_requests(cfg, rng, *, n_shuffled=3, prompt_len=8, new=3):
    """One canonical-order request (stores the chunks) + n shuffled-order
    requests arriving later against the warm store."""
    pool = [list(map(int, rng.integers(0, cfg.vocab, CHUNK))) for _ in range(4)]
    perms = [[2, 0, 3, 1], [3, 2, 1, 0], [1, 3, 0, 2]][:n_shuffled]
    reqs = [dict(
        req_id=0, context_tokens=sum(pool, []),
        prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, prompt_len))),
        max_new_tokens=new, arrival_s=0.0, expected_reuses=4,
    )]
    for i, p in enumerate(perms):
        reqs.append(dict(
            req_id=i + 1, context_tokens=sum((pool[j] for j in p), []),
            prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, prompt_len))),
            max_new_tokens=new, arrival_s=30.0, expected_reuses=4,
        ))
    return reqs


def _run_engine(cfg, params, reqs, planner, **ec_kw):
    kw = dict(max_slots=2, max_len=128, chunk_tokens=CHUNK)
    kw.update(ec_kw)
    eng = ServingEngine(cfg, params, engine_cfg=EngineConfig(**kw), planner=planner)
    for r in reqs:
        eng.submit(Request(**r))
    events = []
    while not eng.idle:
        events.extend(eng.step())
    return eng, events


@pytest.mark.parametrize("paged_decode", [False, True])
def test_engine_fused_r1_matches_recompute_bitwise(paged_decode):
    """Shuffled-chunk requests served FUSED at recompute_frac=1.0 generate
    token-for-token what full recompute generates (which itself runs the
    packed prefill) — under dense and paged decode."""
    cfg, _, params = _setup("llama-7b")
    reqs = _shuffled_requests(cfg, np.random.default_rng(1))
    eng_f, events = _run_engine(
        cfg, params, reqs, BlendPlanner(recompute_frac=1.0, always=True),
        fusion_enabled=True, paged_decode=paged_decode,
    )
    eng_n, _ = _run_engine(
        cfg, params, reqs, AlwaysReusePlanner(), reuse_enabled=False,
        paged_decode=paged_decode,
    )
    toks_f = {r.req_id: r.tokens for r in eng_f.records}
    toks_n = {r.req_id: r.tokens for r in eng_n.records}
    assert toks_f == toks_n
    acts = {r.req_id: r.action for r in eng_f.records}
    assert acts[0] == "recompute"
    assert all(acts[i] == "fused" for i in (1, 2, 3))
    fused_events = [e for e in events if isinstance(e, ev.FusedAdmitted)]
    assert len(fused_events) == 3
    # r=1.0: every matched token recomputes, nothing fetched
    assert all(e.reused_tokens == 0 and e.n_sources == 0 for e in fused_events)
    stats = eng_f.fused_stats()
    assert stats["enabled"] and stats["admissions"] == 3
    assert stats["recompute_tokens"] == 3 * 4 * CHUNK


def test_engine_fused_partial_counts_and_events_consistent():
    """r < 1: fused admissions fetch their sources, reuse + recompute
    partition every context, and the engine counters agree with the event
    stream; the summary counts fused admissions as reuse hits."""
    cfg, _, params = _setup("llama-7b")
    reqs = _shuffled_requests(cfg, np.random.default_rng(4))
    eng, events = _run_engine(
        cfg, params, reqs, BlendPlanner(recompute_frac=0.25, always=True),
        fusion_enabled=True,
    )
    fused_events = [e for e in events if isinstance(e, ev.FusedAdmitted)]
    assert len(fused_events) == 3
    ctx_len = 4 * CHUNK
    for e in fused_events:
        assert e.reused_tokens > 0 and e.n_sources >= 1
        assert e.reused_tokens + e.recompute_tokens == ctx_len
    stats = eng.fused_stats()
    assert stats["admissions"] == 3
    assert stats["reused_tokens"] == sum(e.reused_tokens for e in fused_events)
    assert stats["recompute_tokens"] == sum(
        e.recompute_tokens for e in fused_events
    )
    assert stats["sources"] == sum(e.n_sources for e in fused_events)
    assert stats["busy_s"] > 0
    # each fused request's KVLoaded events name its sources
    loads = [e for e in events if isinstance(e, ev.KVLoaded)]
    assert len(loads) == stats["sources"]
    # records carry the fused plan; the summary counts them as reuse hits
    recs = {r.req_id: r for r in eng.records}
    for i in (1, 2, 3):
        assert recs[i].action == "fused"
        assert recs[i].plan.fused is not None
        assert recs[i].matched_tokens == recs[i].plan.fused.reused_tokens
    assert eng.summary().reuse_hits >= 3
    # time-ordered stream survives the fused path
    times = [e.t_s for e in events]
    assert times == sorted(times)


def test_engine_fusion_disabled_never_fuses():
    """fusion_enabled=False: a BlendPlanner sees no composite (lookup gate)
    and degrades to its base planner; no fused events, no fused stats."""
    cfg, _, params = _setup("llama-7b")
    reqs = _shuffled_requests(cfg, np.random.default_rng(4))
    eng, events = _run_engine(
        cfg, params, reqs, BlendPlanner(recompute_frac=0.25, always=True),
        fusion_enabled=False,
    )
    assert not [e for e in events if isinstance(e, ev.FusedAdmitted)]
    assert eng.fused_stats()["admissions"] == 0
    assert all(r.action != "fused" for r in eng.records)


def test_blend_planner_cost_gating():
    """always=False: fused competes on marginal cost — it wins when the
    composite covers a long context (prefill compute dwarfs fetch fees) and
    loses when nothing is matched."""
    from repro.core.cost_model import Workload
    from repro.core.perf_model import PerfModel, V100_X4_HF
    from repro.core.pricing import AWS_PAPER

    cfg = get_config("llama-7b")
    planner = BlendPlanner(recompute_frac=0.15)
    planner.configure(
        cost_cfg=cfg, pricing=AWS_PAPER, perf=PerfModel(V100_X4_HF),
        write_back=True, min_store_tokens=32,
    )
    chunk = 256
    idx = ChunkIndex(chunk)
    stored = list(range(8 * chunk))
    idx.insert(stored, "e0")
    query = sum(
        (stored[i * chunk : (i + 1) * chunk] for i in (4, 5, 0, 1, 2, 3, 6, 7)),
        [],
    )
    comp = idx.match(query)
    assert comp.matched_tokens == len(query)
    from repro.core.cost_model import s_storage_bytes

    lookup = StoreLookup(
        match=None, entry=None, fraction=0.0, partial_ok=True,
        composite=comp,
        fused_bytes_by_tier={"host_dram": s_storage_bytes(cfg, len(query))},
    )
    req = Request(req_id=0, context_tokens=query, prompt_tokens=[1] * 16,
                  max_new_tokens=16, expected_reuses=4)
    w = Workload(L_context=len(query), L_prompt=16, L_output=16, N=4)
    plan = planner.plan(req, lookup, w)
    assert plan.action == "fused"
    assert plan.fused is not None and plan.fetch_bytes > 0
    assert plan.est_cost < planner.base.plan(req, StoreLookup.miss(), w).est_cost

    miss = planner.plan(req, StoreLookup.miss(), w)
    assert miss.action == "recompute" and miss.fused is None
