"""Serving-engine end-to-end: the paper's pipelines, numerically exact.

All engine construction goes through the plan/execute API: a ``ReusePlanner``
picks recompute/load/partial per request, the step-driven engine executes the
plan over pluggable storage backends.  Golden-parity tests pin the refactored
engine to the seed engine's recorded actions and costs (1e-9)."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import registry
from repro.serving import (
    AlwaysReusePlanner,
    CostAwarePlanner,
    EngineConfig,
    Request,
    ServingEngine,
)
from repro.serving import events as ev
from repro.serving.scheduler import AdmissionQueue, HedgePolicy

GOLDEN = pathlib.Path(__file__).parent / "data" / "serving_golden_seed.json"


def _setup(arch, seed=0):
    cfg = reduced_config(get_config(arch))
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _requests(cfg, n=6, n_ctx=2, ctx_len=64, prompt_len=8, new=4, seed=0):
    rng = np.random.default_rng(seed)
    ctxs = [list(map(int, rng.integers(0, cfg.vocab, ctx_len))) for _ in range(n_ctx)]
    out = []
    for i in range(n):
        out.append(
            dict(
                req_id=i,
                context_tokens=ctxs[i % n_ctx],
                prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, prompt_len))),
                max_new_tokens=new,
                arrival_s=i * 0.01,
                expected_reuses=n // n_ctx,
            )
        )
    return out


def _partial_requests(cfg, seed=3):
    rng = np.random.default_rng(seed)
    shared = list(map(int, rng.integers(0, cfg.vocab, 32)))
    ctx_a = shared + list(map(int, rng.integers(0, cfg.vocab, 16)))
    ctx_b = shared + list(map(int, rng.integers(0, cfg.vocab, 16)))
    prompt = list(map(int, rng.integers(0, cfg.vocab, 8)))
    return [
        dict(req_id=0, context_tokens=ctx_a, prompt_tokens=prompt, max_new_tokens=3,
             arrival_s=0.0, expected_reuses=2),
        dict(req_id=1, context_tokens=ctx_b, prompt_tokens=prompt, max_new_tokens=3,
             arrival_s=0.01, expected_reuses=2),
    ]


def _run(cfg, params, reqs, planner=None, **ec_kw):
    kw = dict(max_slots=2, max_len=128, chunk_tokens=16)
    kw.update(ec_kw)
    ec = EngineConfig(**kw)
    eng = ServingEngine(cfg, params, engine_cfg=ec, planner=planner)
    for r in reqs:
        eng.submit(Request(**r))
    summary = eng.run()
    tokens = {rec.req_id: rec.tokens for rec in eng.records}
    actions = {rec.req_id: rec.action for rec in eng.records}
    return eng, summary, tokens, actions


@pytest.mark.parametrize(
    "arch", ["llama-7b", "qwen2-1.5b", "mixtral-8x22b", "mamba2-1.3b",
             "jamba-1.5-large-398b", "olmoe-1b-7b", "granite-34b"]
)
def test_reuse_tokens_identical_to_recompute(arch):
    """The core property: loading stored context state produces token-for-token
    identical generations vs full recomputation."""
    cfg, params = _setup(arch)
    reqs = _requests(cfg)
    _, s_yes, toks_yes, acts = _run(cfg, params, reqs, planner=AlwaysReusePlanner())
    _, s_no, toks_no, _ = _run(cfg, params, reqs, reuse_enabled=False)
    assert toks_yes == toks_no
    assert sum(1 for a in acts.values() if a == "load") >= len(reqs) - 2
    assert s_yes.reuse_hits >= len(reqs) - 2


def test_partial_prefix_reuse_dense():
    """Two contexts sharing a 32-token prefix: the second request partially
    reuses the first's stored KV and still matches recompute exactly."""
    cfg, params = _setup("llama-7b")
    reqs = _partial_requests(cfg)
    _, _, toks_yes, acts = _run(cfg, params, reqs, planner=AlwaysReusePlanner())
    _, _, toks_no, _ = _run(cfg, params, reqs, reuse_enabled=False)
    assert acts[1] == "partial"
    assert toks_yes == toks_no


def test_partial_reuse_disallowed_for_ssm():
    """SSM context state is all-or-nothing (DESIGN.md §6): a shared prefix
    must NOT produce a partial load for mamba2."""
    cfg, params = _setup("mamba2-1.3b")
    rng = np.random.default_rng(4)
    shared = list(map(int, rng.integers(0, cfg.vocab, 32)))
    ctx_a = shared + list(map(int, rng.integers(0, cfg.vocab, 16)))
    ctx_b = shared + list(map(int, rng.integers(0, cfg.vocab, 16)))
    prompt = [1, 2, 3, 4]
    reqs = [
        dict(req_id=0, context_tokens=ctx_a, prompt_tokens=prompt, max_new_tokens=2,
             arrival_s=0.0, expected_reuses=2),
        dict(req_id=1, context_tokens=ctx_b, prompt_tokens=prompt, max_new_tokens=2,
             arrival_s=0.01, expected_reuses=2),
    ]
    _, _, toks_yes, acts = _run(cfg, params, reqs, planner=AlwaysReusePlanner())
    _, _, toks_no, _ = _run(cfg, params, reqs, reuse_enabled=False)
    assert acts[1] == "recompute"
    assert toks_yes == toks_no


def test_compressed_tier_close_but_cheaper():
    """int8 storage tier: generations may differ slightly (lossy) but the
    engine runs and the stored bytes shrink ~2x."""
    cfg, params = _setup("llama-7b")
    reqs = _requests(cfg, n=4, n_ctx=1)
    eng, s, toks, acts = _run(cfg, params, reqs, planner=AlwaysReusePlanner(),
                              compress_tier="io2")
    assert s.reuse_hits >= 2
    e = next(iter(eng.store.entries.values()))
    assert e.compressed


def test_whisper_cross_kv_reuse():
    """Enc-dec: reusing the stored encoder/cross-KV state skips re-encoding
    and matches the recompute pipeline's generations."""
    cfg, params = _setup("whisper-tiny")
    rng = np.random.default_rng(5)
    frames = jnp.asarray(rng.standard_normal((1, 32, cfg.d_model)), jnp.float32)
    ctx_proxy = list(map(int, rng.integers(0, 1000, 32)))  # audio identity hash
    prompt = list(map(int, rng.integers(0, cfg.vocab, 8)))
    reqs = [
        dict(req_id=i, context_tokens=ctx_proxy, prompt_tokens=prompt,
             max_new_tokens=3, arrival_s=i * 0.01, expected_reuses=3, embeds=frames)
        for i in range(3)
    ]
    _, _, toks_yes, acts = _run(cfg, params, reqs, planner=AlwaysReusePlanner())
    _, _, toks_no, _ = _run(cfg, params, reqs, reuse_enabled=False)
    assert toks_yes == toks_no
    assert list(acts.values()).count("load") == 2


def test_vlm_image_context_reuse():
    cfg, params = _setup("internvl2-1b")
    rng = np.random.default_rng(6)
    ft = cfg.frontend_tokens
    embeds = jnp.asarray(rng.standard_normal((1, ft, cfg.d_model)) * 0.02, jnp.float32)
    ctx_proxy = list(map(int, rng.integers(0, 1000, ft)))
    reqs = [
        dict(req_id=i, context_tokens=ctx_proxy,
             prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, 8))),
             max_new_tokens=3, arrival_s=i * 0.01, expected_reuses=3, embeds=embeds)
        for i in range(3)
    ]
    # chunk must not exceed the (reduced) 8-token image-context proxy
    _, _, toks_yes, acts = _run(cfg, params, reqs, planner=AlwaysReusePlanner(),
                                chunk_tokens=8)
    _, _, toks_no, _ = _run(cfg, params, reqs, reuse_enabled=False, chunk_tokens=8)
    assert toks_yes == toks_no
    assert list(acts.values()).count("load") == 2


def test_cost_policy_skips_worthless_contexts():
    """With the honest cost policy and a tiny model, storing tiny contexts
    never clears break-even => engine recomputes (the paper's economics)."""
    cfg, params = _setup("llama-7b")
    reqs = _requests(cfg, n=4, n_ctx=1)
    for r in reqs:
        r["expected_reuses"] = 1.0
    _, s, _, acts = _run(cfg, params, reqs, planner=CostAwarePlanner())
    assert all(a == "recompute" for a in acts.values())
    assert s.storage_cost == 0.0


def test_hedged_load_caps_tail():
    h = HedgePolicy(threshold_s=0.5, parallelism=2)
    assert h.effective_delay(0.3) == 0.3
    assert h.effective_delay(2.5) == pytest.approx(0.5 + 2.0 / 2)


def test_prefetch_lookahead_reduces_ttft():
    """Queued requests' stored contexts are fetched during earlier requests'
    service: their TTFT drops to the unfinished remainder, tokens unchanged."""
    from repro.core.perf_model import PerfModel, V100_X4_HF
    from repro.core.pricing import AWS_PAPER

    cfg, params = _setup("llama-7b")
    reqs = _requests(cfg, n=8, n_ctx=2, ctx_len=64)

    def run(prefetch):
        ec = EngineConfig(
            max_slots=1, max_len=128, chunk_tokens=16,
            cost_arch="llama-7b", prefetch_lookahead=prefetch,
        )
        eng = ServingEngine(cfg, params, engine_cfg=ec, planner=AlwaysReusePlanner(),
                            pricing=AWS_PAPER, perf=PerfModel(V100_X4_HF))
        for r in reqs:
            eng.submit(Request(**r))
        s = eng.run()
        return s, {rec.req_id: rec.tokens for rec in eng.records}

    s_plain, t_plain = run(0)
    s_pre, t_pre = run(4)
    assert t_plain == t_pre
    assert s_pre.mean_ttft_s < s_plain.mean_ttft_s
    assert s_pre.reuse_hits == s_plain.reuse_hits >= 6


def test_admission_queue_edf():
    q = AdmissionQueue()
    q.push(Request(req_id=0, context_tokens=[], prompt_tokens=[1], max_new_tokens=1,
                   arrival_s=0.0, slo_ttft_s=10.0))
    q.push(Request(req_id=1, context_tokens=[], prompt_tokens=[1], max_new_tokens=1,
                   arrival_s=0.1, slo_ttft_s=0.2))  # tighter deadline
    q.push(Request(req_id=2, context_tokens=[], prompt_tokens=[1], max_new_tokens=1,
                   arrival_s=5.0, slo_ttft_s=0.01))  # not arrived yet
    first = q.pop_admissible(now=1.0)
    assert first.req_id == 1  # EDF among arrived
    assert q.pop_admissible(now=1.0).req_id == 0
    assert q.pop_admissible(now=1.0) is None  # req 2 hasn't arrived
    assert q.next_arrival() == 5.0


def test_admission_queue_two_heap_consistency():
    """peek_arrived agrees with pop order, and promotion never loses or
    duplicates requests across pending/ready heaps."""
    rng = np.random.default_rng(0)
    q = AdmissionQueue()
    n = 40
    for i in range(n):
        q.push(Request(
            req_id=i, context_tokens=[], prompt_tokens=[1], max_new_tokens=1,
            arrival_s=float(rng.uniform(0, 10)),
            slo_ttft_s=float(rng.uniform(0.1, 5)) if i % 3 else None,
        ))
    assert len(q) == n
    peeked = [r.req_id for r in q.peek_arrived(now=5.0, limit=5)]
    popped = [q.pop_admissible(now=5.0).req_id for _ in range(5)]
    assert peeked == popped
    seen = set(popped)
    while True:
        nxt = q.pop_admissible(now=20.0)
        if nxt is None:
            break
        assert nxt.req_id not in seen
        seen.add(nxt.req_id)
    assert len(seen) == n and len(q) == 0


# --------------------------------------------------------------------------- #
# Plan/execute parity with the seed engine
# --------------------------------------------------------------------------- #
def _golden_scenarios(cfg, params):
    reqs = _requests(cfg)
    return {
        "always": (reqs, dict(planner=AlwaysReusePlanner())),
        "cost": (reqs, dict(planner=CostAwarePlanner())),
        "recompute": (reqs, dict(reuse_enabled=False)),
        "partial_always": (_partial_requests(cfg), dict(planner=AlwaysReusePlanner())),
    }


@pytest.mark.parametrize("decode_mode", ["dense", "paged"])
def test_golden_parity_with_seed_engine(decode_mode):
    """The refactored plan/execute engine reproduces the seed (pre-refactor)
    engine's per-request actions and all modeled times/costs to 1e-9 on the
    canonical serving scenarios (golden file captured from the seed code) —
    replayed under BOTH decode configs: the paged block-pool decode path
    must be indistinguishable from the dense one on the seed trace (uniform
    batches; ``t_decode_paged``'s delegation contract)."""
    golden = json.loads(GOLDEN.read_text())
    cfg, params = _setup("llama-7b")
    for name, (reqs, kw) in _golden_scenarios(cfg, params).items():
        eng, s, _, _ = _run(
            cfg, params, reqs, paged_decode=decode_mode == "paged", **kw
        )
        assert eng.decode_stats()["paged"] is (decode_mode == "paged")
        want = golden[name]
        recs = sorted(eng.records, key=lambda r: r.req_id)
        assert len(recs) == len(want["records"]), name
        for rec, w in zip(recs, want["records"]):
            assert rec.action == w["action"], (name, rec.req_id)
            assert rec.matched_tokens == w["matched_tokens"], (name, rec.req_id)
            for field in ("load_s", "prefill_s", "decode_s", "start_s",
                          "finish_s", "compute_cost"):
                assert getattr(rec, field) == pytest.approx(w[field], abs=1e-9), (
                    name, rec.req_id, field)
        got = s.as_dict()
        for k, v in want["summary"].items():
            assert got[k] == pytest.approx(v, abs=1e-9), (name, k)


def test_step_event_stream_matches_run():
    """Driving the engine by explicit step() produces the same records and
    summary as run(), and the event stream is complete and consistent."""
    cfg, params = _setup("llama-7b")
    reqs = _requests(cfg)

    def fresh():
        eng = ServingEngine(
            cfg, params,
            engine_cfg=EngineConfig(max_slots=2, max_len=128, chunk_tokens=16),
            planner=AlwaysReusePlanner(),
        )
        for r in reqs:
            eng.submit(Request(**r))
        return eng

    eng_run = fresh()
    s_run = eng_run.run()

    eng_step = fresh()
    events = []
    while not eng_step.idle:
        events.append(eng_step.step())
        assert events[-1], "a non-idle step must produce events"
    s_step = eng_step.summary()

    assert s_run.as_dict() == s_step.as_dict()
    flat = [e for step in events for e in step]
    # the event stream alone reproduces the summary (streaming consumers)
    from repro.serving import metrics as metrics_mod

    s_ev = metrics_mod.summarize_events(
        flat,
        storage_cost=eng_step.store.storage_cost(eng_step.pricing),
        transfer_cost=eng_step.transfer.transfer_fees(),
    )
    assert s_ev.as_dict() == s_step.as_dict()
    # every record carries the plan it executed
    assert all(rec.plan is not None and rec.plan.action == rec.action
               for rec in eng_step.records)
    assert ev.tokens_from_events(flat) == {
        rec.req_id: rec.tokens for rec in eng_step.records
    }
    assert ev.actions_from_events(flat) == {
        rec.req_id: rec.action for rec in eng_step.records
    }
    finished = [e for e in flat if isinstance(e, ev.RequestFinished)]
    assert sorted(e.req_id for e in finished) == sorted(r["req_id"] for r in reqs)
    admitted = [e for e in flat if isinstance(e, ev.RequestAdmitted)]
    plans = [e for e in flat if isinstance(e, ev.PlanChosen)]
    assert len(admitted) == len(plans) == len(reqs)
    loads = [e for e in flat if isinstance(e, ev.KVLoaded)]
    assert len(loads) == sum(1 for r in eng_step.records if r.action != "recompute")
    # events are time-ordered within the stream
    times = [e.t_s for e in flat]
    assert times == sorted(times)
    # drain() on a third engine yields the same event sequence types
    eng_drain = fresh()
    drained = list(eng_drain.drain())
    assert [type(e) for e in drained] == [type(e) for e in flat]
    assert eng_drain.idle and not list(eng_drain.drain())


def test_on_token_callback_order_matches_decode():
    """The streaming hook fires once per generated token, in emission order:
    the callback sequence is exactly the TokenEmitted event stream, and per
    request it reconstructs the final record's tokens in decode order."""
    cfg, params = _setup("llama-7b")
    reqs = _requests(cfg)
    seen = []
    eng = ServingEngine(
        cfg, params,
        engine_cfg=EngineConfig(max_slots=2, max_len=128, chunk_tokens=16),
        planner=AlwaysReusePlanner(),
        on_token=seen.append,
    )
    for r in reqs:
        eng.submit(Request(**r))
    events = []
    while not eng.idle:
        events.extend(eng.step())
    emitted = [e for e in events if isinstance(e, ev.TokenEmitted)]
    # the callback saw the exact same event objects, in the same order
    assert [id(e) for e in seen] == [id(e) for e in emitted]
    # and per request the callback stream IS the decode order
    by_req = {}
    for e in seen:
        assert e.index == len(by_req.setdefault(e.req_id, []))
        by_req[e.req_id].append(e.token)
    assert by_req == {rec.req_id: rec.tokens for rec in eng.records}
    # off by default: no hook, no callbacks
    assert ServingEngine(cfg, params).on_token is None


def test_min_cache_tokens_gates_write_back():
    """``EngineConfig.min_cache_tokens``: contexts shorter than the floor are
    never written back (they'd never repay a fetch), while the default (0)
    leaves behavior untouched — tokens and actions bit-identical."""
    cfg, params = _setup("llama-7b")
    reqs = _requests(cfg, n=4, n_ctx=1, ctx_len=64)

    eng_def, _, tok_def, act_def = _run(cfg, params, reqs,
                                        planner=AlwaysReusePlanner())
    assert len(eng_def.store.entries) >= 1  # 64 >= chunk floor: stored

    # floor above the context length: nothing is ever stored, every
    # request recomputes, tokens unchanged
    eng_hi, _, tok_hi, act_hi = _run(
        cfg, params, reqs, planner=AlwaysReusePlanner(),
        min_cache_tokens=128,
    )
    assert len(eng_hi.store.entries) == 0
    assert all(a == "recompute" for a in act_hi.values())
    assert tok_hi == tok_def

    # explicit 0 is the default: identical run
    eng_z, _, tok_z, act_z = _run(
        cfg, params, reqs, planner=AlwaysReusePlanner(),
        min_cache_tokens=0,
    )
    assert tok_z == tok_def and act_z == act_def
    assert len(eng_z.store.entries) == len(eng_def.store.entries)

    # a floor at-or-below the context length stores normally (the gate is
    # >=, and chunk_tokens already floors shorter contexts)
    eng_eq, _, tok_eq, _ = _run(
        cfg, params, reqs, planner=AlwaysReusePlanner(),
        min_cache_tokens=64,
    )
    assert len(eng_eq.store.entries) == len(eng_def.store.entries)
    assert tok_eq == tok_def


def test_dense_decode_donates_state_and_admits_between_steps():
    """Dense decode donates the engine's state to each step (the caches are
    updated in place), and requests admitted between decode steps still get
    the tokens of a full greedy recompute of their own sequence."""
    cfg, params = _setup("llama-7b")
    api = registry.get_model(cfg)
    reqs = _requests(cfg, n=5, new=6)
    for i, r in enumerate(reqs):  # arrivals spread over the decode steps
        r["arrival_s"] = 0.0 if i < 2 else 1e9
    eng = ServingEngine(
        cfg, params,
        engine_cfg=EngineConfig(max_slots=3, max_len=128, chunk_tokens=16),
        planner=AlwaysReusePlanner(),
    )
    pending = list(reqs)
    for r in pending[:2]:
        eng.submit(Request(**r))
    pending = pending[2:]
    donated = 0
    while not eng.idle or pending:
        if pending and any(s.active for s in eng.slots):
            # admit one more between decode steps, with a slot still decoding
            eng.submit(Request(**dict(pending.pop(0), arrival_s=0.0)))
        before = eng._state
        events = eng.step()
        if any(isinstance(e, ev.TokenEmitted) for e in events) and eng._state is not before:
            donated += all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(before))
    assert donated > 0

    forward = jax.jit(lambda p, t: api.forward(p, cfg, t)[0])
    for rec in eng.records:
        r = reqs[rec.req_id]
        seq = list(r["context_tokens"]) + list(r["prompt_tokens"])
        want = []
        for _ in range(r["max_new_tokens"]):
            padded = jnp.asarray([seq + [0] * (128 - len(seq))], jnp.int32)
            want.append(int(jnp.argmax(forward(params, padded)[0, len(seq) - 1])))
            seq.append(want[-1])
        assert rec.tokens == want, rec.req_id
