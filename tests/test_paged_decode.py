"""Paged batched decode: bit-exact parity with the dense path, pool safety.

Three levels, mirroring tests/test_packed.py's pyramid:

  * kernel  — ``ref.paged_decode_ref`` / Pallas ``paged_decode`` vs the dense
    decode oracle and the dense Pallas decode kernel, across MHA / GQA /
    sliding windows and ragged live lengths;
  * model   — ``lm.decode_paged`` vs per-slot ``lm.decode`` over real reduced
    archs (logits AND pool-resident KV rows, exact, across block-boundary
    appends);
  * engine  — a full serve under ``paged_decode=True`` generates
    token-identical output to the dense path; uniform batches also match all
    modeled times/costs at 1e-9, mixed-length batches are strictly cheaper
    (live-blocks pricing), and the block pool drains clean.

Plus hypothesis invariants (with a deterministic mirror) for the shared
block pool: refcounts == live table references, every freed block returns to
the free list exactly once, no block is writable by two live slots after a
copy-on-write split, and used pool bytes == bytes of live table entries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config, reduced_config
from repro.kernels import ops, ref
from repro.kvcache import paged
from repro.models import registry
from repro.serving import AlwaysReusePlanner, EngineConfig, Request, ServingEngine


# --------------------------------------------------------------------------- #
# Kernel level
# --------------------------------------------------------------------------- #
def _pool_case(lens, KV, hd, block, max_len, seed=0):
    """Random pool + block tables for ``lens`` live tokens per slot, plus the
    equivalent dense slotted cache (same rows, same padded length)."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    nb = max_len // block
    n_blocks = 1 + B * nb
    pool_k = rng.standard_normal((n_blocks * block, KV, hd)).astype(np.float32)
    pool_v = rng.standard_normal((n_blocks * block, KV, hd)).astype(np.float32)
    tables = np.zeros((B, nb), np.int32)
    dense_k = np.zeros((B, max_len, KV, hd), np.float32)
    dense_v = np.zeros((B, max_len, KV, hd), np.float32)
    nxt = 1
    for b, L in enumerate(lens):
        for j in range(-(-L // block)):
            tables[b, j] = nxt
            rows = slice(nxt * block, (nxt + 1) * block)
            dense_k[b, j * block : (j + 1) * block] = pool_k[rows]
            dense_v[b, j * block : (j + 1) * block] = pool_v[rows]
            nxt += 1
    q_pos = np.array([[L - 1] for L in lens], np.int32)
    idx = np.arange(max_len, dtype=np.int32)[None]
    kv_pos = np.where(idx <= q_pos, idx, -1)
    q = rng.standard_normal((B, 1, 2 * KV, hd)).astype(np.float32)
    return dict(
        q=q, pool_k=pool_k, pool_v=pool_v, tables=tables, q_pos=q_pos,
        dense_k=dense_k, dense_v=dense_v, kv_pos=kv_pos,
    )


@pytest.mark.parametrize(
    "KV,window", [(4, None), (2, None), (2, 96)]  # MHA, GQA, GQA+window
)
def test_paged_ref_matches_dense_ref_exactly(KV, window):
    """Gathering the live blocks through the table and attending is BITWISE
    the dense decode attention over a slotted cache of the same padded
    length — ragged live lengths, boundary blocks, 0-padded table tails."""
    c = _pool_case([5, 97, 128, 64], KV=KV, hd=16, block=32, max_len=128)
    paged_out = ref.paged_decode_ref(
        jnp.asarray(c["q"]), jnp.asarray(c["pool_k"]), jnp.asarray(c["pool_v"]),
        block_table=jnp.asarray(c["tables"]), q_pos=jnp.asarray(c["q_pos"]),
        block=32, window=window,
    )
    dense_out = ref.attention_ref(
        jnp.asarray(c["q"]), jnp.asarray(c["dense_k"]), jnp.asarray(c["dense_v"]),
        q_pos=jnp.asarray(c["q_pos"]), kv_pos=jnp.asarray(c["kv_pos"]),
        causal=True, window=window,
    )
    assert np.array_equal(np.asarray(paged_out), np.asarray(dense_out))


@pytest.mark.parametrize("KV,window", [(4, None), (2, None), (2, 200)])
def test_paged_pallas_interpret_matches_ref(KV, window):
    """The Pallas block-table kernel (interpret mode) agrees with the jnp
    oracle — exercises the scalar-prefetch table indirection, multi-block
    sequences, and the positional masking of dump-block padding."""
    from repro.kernels import paged_decode as pdk

    c = _pool_case([130, 257, 33], KV=KV, hd=16, block=128, max_len=384, seed=3)
    want = ref.paged_decode_ref(
        jnp.asarray(c["q"]), jnp.asarray(c["pool_k"]), jnp.asarray(c["pool_v"]),
        block_table=jnp.asarray(c["tables"]), q_pos=jnp.asarray(c["q_pos"]),
        block=128, window=window,
    )
    got = pdk.paged_decode_attention(
        jnp.asarray(c["q"]), jnp.asarray(c["pool_k"]), jnp.asarray(c["pool_v"]),
        block_table=jnp.asarray(c["tables"]), q_pos=jnp.asarray(c["q_pos"]),
        block=128, window=window, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6)


def test_paged_pallas_matches_dense_decode_kernel():
    """Top of the kernel pyramid: the paged Pallas kernel vs the dense Pallas
    decode kernel on equivalent layouts (same flash recurrence, kv axis
    indirected through the block table)."""
    from repro.kernels import decode_attention as dk
    from repro.kernels import paged_decode as pdk

    c = _pool_case([100, 256, 17], KV=2, hd=16, block=128, max_len=256, seed=5)
    dense = dk.decode_attention(
        jnp.asarray(c["q"]), jnp.asarray(c["dense_k"])[None],
        jnp.asarray(c["dense_v"])[None], layer=0,
        q_pos=jnp.asarray(c["q_pos"]), kv_pos=jnp.asarray(c["kv_pos"]),
        interpret=True,
    )
    got = pdk.paged_decode_attention(
        jnp.asarray(c["q"]), jnp.asarray(c["pool_k"]), jnp.asarray(c["pool_v"]),
        block_table=jnp.asarray(c["tables"]), q_pos=jnp.asarray(c["q_pos"]),
        block=128, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), atol=2e-6, rtol=2e-6)


def test_ops_paged_decode_dispatches_on_cpu():
    c = _pool_case([9, 40], KV=2, hd=8, block=16, max_len=48, seed=7)
    out = ops.paged_decode(
        jnp.asarray(c["q"]), jnp.asarray(c["pool_k"]), jnp.asarray(c["pool_v"]),
        block_table=jnp.asarray(c["tables"]), q_pos=jnp.asarray(c["q_pos"]),
        block=16,
    )
    assert out.shape == c["q"].shape and np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------------------- #
# Model level
# --------------------------------------------------------------------------- #
def _setup(arch, seed=0):
    cfg = reduced_config(get_config(arch))
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(seed), cfg)
    return cfg, api, params


@pytest.mark.parametrize("arch", ["llama-7b", "qwen2-1.5b", "olmoe-1b-7b"])
def test_model_decode_paged_bit_exact(arch):
    """lm.decode_paged == batched lm.decode, bitwise: logits every step AND
    the pool-resident KV rows, across enough steps that the shorter slot
    appends through a block boundary (fresh-block table growth)."""
    cfg, api, params = _setup(arch)
    rng = np.random.default_rng(2)
    max_len, block, lens = 64, 16, [13, 37]
    B = len(lens)

    state = api.init_state(cfg, B, max_len)
    for b, L in enumerate(lens):
        st = api.init_state(cfg, 1, max_len)
        toks = jnp.asarray([list(map(int, rng.integers(0, cfg.vocab, L)))], jnp.int32)
        _, st = api.prefill(params, cfg, toks, st)
        state = paged.insert_slot(cfg, state, b, paged.extract_slot(cfg, st, 0, L))

    ps = paged.PagedSlots(B, max_len, block)
    caches = paged.init_pool_caches(cfg, ps.pool.n_blocks, block, dtype=jnp.float32)
    new = []
    for ki, c in enumerate(caches):
        k, v = c.attn.k, c.attn.v
        for b, L in enumerate(lens):
            if ki == 0:
                ps.admit(b, L)
            nb = -(-L // block)
            dst = paged.block_rows(ps.tables[b, :nb], block)
            k = k.at[:, dst].set(state.caches[ki].attn.k[:, b, : nb * block])
            v = v.at[:, dst].set(state.caches[ki].attn.v[:, b, : nb * block])
        new.append(paged.BlockCache(paged.KVCache(k, v), None))
    caches = tuple(new)

    toks = jnp.asarray([[3], [7]], jnp.int32)
    for step in range(block + 3):  # slot 0 crosses a block boundary
        lg_d, state = api.decode(params, cfg, toks, state)
        for b in range(B):
            assert ps.prepare_append(b) is None  # private blocks: no CoW
        lg_p, caches = api.decode_paged(
            params, cfg, toks, caches,
            block_table=jnp.asarray(ps.tables),
            pos=jnp.asarray(ps.lens, jnp.int32), block=block,
        )
        for b in range(B):
            ps.note_token(b)
        assert np.array_equal(np.asarray(lg_d), np.asarray(lg_p)), (arch, step)
        toks = jnp.argmax(lg_d, axis=-1)[:, None].astype(jnp.int32)

    # pool rows == dense cache rows for every live token
    for b in range(B):
        L = int(ps.lens[b])
        nb = -(-L // block)
        rows = paged.block_rows(ps.tables[b, :nb], block)[:L]
        for ki in range(len(caches)):
            got_k = np.asarray(caches[ki].attn.k[:, rows])
            want_k = np.asarray(state.caches[ki].attn.k[:, b, :L])
            assert np.array_equal(got_k, want_k), (arch, b, ki)
    ps.audit()


# --------------------------------------------------------------------------- #
# Engine level
# --------------------------------------------------------------------------- #
def _burst(cfg, *, n, ctx_lens, prompt_len=8, new=4, seed=0, arrival=0.0):
    rng = np.random.default_rng(seed)
    ctxs = [list(map(int, rng.integers(0, cfg.vocab, L))) for L in ctx_lens]
    return [
        dict(
            req_id=i,
            context_tokens=ctxs[i % len(ctxs)],
            prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, prompt_len))),
            max_new_tokens=new,
            arrival_s=arrival,
            expected_reuses=max(n // len(ctxs), 1),
        )
        for i in range(n)
    ]


def _run(cfg, params, reqs, **ec_kw):
    kw = dict(max_slots=4, max_len=128, chunk_tokens=16)
    kw.update(ec_kw)
    eng = ServingEngine(
        cfg, params, engine_cfg=EngineConfig(**kw), planner=AlwaysReusePlanner()
    )
    for r in reqs:
        eng.submit(Request(**r))
    summary = eng.run()
    return eng, summary


@pytest.mark.parametrize("arch", ["llama-7b", "qwen2-1.5b", "olmoe-1b-7b"])
def test_engine_paged_decode_full_parity(arch):
    """Acceptance criterion: a full serve under paged decode is bit-identical
    to the dense path for every packable arch — same tokens, and (uniform
    batches) every modeled time/cost within 1e-9, records and summary."""
    cfg, _, params = _setup(arch)
    reqs = _burst(cfg, n=8, ctx_lens=[64, 64], seed=1)
    eng_d, s_d = _run(cfg, params, reqs)
    eng_p, s_p = _run(cfg, params, reqs, paged_decode=True)
    assert eng_p.decode_stats()["paged"] is True

    assert {r.req_id: r.tokens for r in eng_d.records} == {
        r.req_id: r.tokens for r in eng_p.records
    }
    recs_d = sorted(eng_d.records, key=lambda r: r.req_id)
    recs_p = sorted(eng_p.records, key=lambda r: r.req_id)
    for rd, rp in zip(recs_d, recs_p):
        assert rd.action == rp.action
        for f in ("load_s", "prefill_s", "decode_s", "start_s", "finish_s",
                  "compute_cost"):
            assert getattr(rd, f) == pytest.approx(getattr(rp, f), abs=1e-9), (
                arch, rd.req_id, f)
    got, want = s_p.as_dict(), s_d.as_dict()
    for k, v in want.items():
        assert got[k] == pytest.approx(v, abs=1e-9), (arch, k)
    # every slot freed its blocks back to the pool on completion
    eng_p._paged.audit()
    assert eng_p._paged.pool.n_used == 0


def test_engine_paged_decode_mixed_lengths_cheaper():
    """Live-blocks pricing: with ragged context lengths across slots the
    paged decode step prices sum-of-live instead of the dense path's
    batch * max — identical tokens, strictly less modeled decode time."""
    cfg, _, params = _setup("llama-7b")
    reqs = _burst(cfg, n=4, ctx_lens=[32, 96, 160, 352], new=6, seed=2)
    kw = dict(max_slots=4, max_len=512, cost_arch="llama-7b")
    eng_d, _ = _run(cfg, params, reqs, **kw)
    eng_p, _ = _run(cfg, params, reqs, paged_decode=True, **kw)
    assert {r.req_id: r.tokens for r in eng_d.records} == {
        r.req_id: r.tokens for r in eng_p.records
    }
    assert eng_d.decode_tokens == eng_p.decode_tokens > 0
    assert eng_p.decode_busy_s < eng_d.decode_busy_s
    assert sum(r.decode_s for r in eng_p.records) < sum(
        r.decode_s for r in eng_d.records
    )


def test_engine_paged_shared_prefix_blocks():
    """Batch-mates loading the SAME stored context share its full prefix
    blocks in the pool (refcounted — the write-back dedup carried through to
    decode); generations still match the dense path bitwise."""
    cfg, _, params = _setup("llama-7b")
    seed_req = _burst(cfg, n=1, ctx_lens=[300], new=1, seed=3)
    mates = [
        dict(r, req_id=10 + i, arrival_s=1.0, max_new_tokens=3)
        for i, r in enumerate(_burst(cfg, n=3, ctx_lens=[300], new=3, seed=3))
    ]
    kw = dict(max_slots=4, max_len=512)
    eng_d, _ = _run(cfg, params, seed_req + mates, **kw)
    eng_p, _ = _run(cfg, params, seed_req + mates, paged_decode=True, **kw)
    assert {r.req_id: r.tokens for r in eng_d.records} == {
        r.req_id: r.tokens for r in eng_p.records
    }
    # 300 matched tokens = 2 full shared blocks; mates 2 and 3 alias mate 1's
    assert eng_p.decode_stats()["shared_block_hits"] >= 2
    eng_p._paged.audit()
    assert eng_p._paged.pool.n_used == 0


def test_non_packable_arch_falls_back_to_dense_decode():
    """SSM archs under paged_decode=True silently keep the dense decode path
    (the paged layout needs per-position attention state)."""
    cfg, _, params = _setup("mamba2-1.3b")
    reqs = _burst(cfg, n=3, ctx_lens=[64], seed=4)
    eng_d, _ = _run(cfg, params, reqs)
    eng_p, _ = _run(cfg, params, reqs, paged_decode=True)
    assert eng_p.decode_stats()["paged"] is False
    assert {r.req_id: r.tokens for r in eng_d.records} == {
        r.req_id: r.tokens for r in eng_p.records
    }


# --------------------------------------------------------------------------- #
# Block pool invariants
# --------------------------------------------------------------------------- #
def _apply_ops(ps: paged.PagedSlots, ops_seq):
    """Interpret a raw op stream against a PagedSlots, auditing after every
    applied op.  Invalid ops (admitting a live slot, appending past max_len,
    over-sharing) are skipped — the stream is a fuzzer, not a protocol."""
    n_slots = ps.tables.shape[0]
    applied = 0
    for kind, slot, arg, other in ops_seq:
        slot = slot % n_slots
        if kind == 0:  # admit, possibly sharing a live mate's prefix blocks
            if ps.live[slot]:
                continue
            n_total = 1 + arg % (ps.nb_max * ps.block)
            shared_from, shared = None, 0
            donor = other % n_slots
            if donor != slot and ps.live[donor]:
                shared_from = donor
                limit = min(
                    int(ps.n_blocks[donor]), -(-n_total // ps.block)
                )
                shared = other % (limit + 1)
                if shared == 0:
                    shared_from = None
            ps.admit(slot, n_total, shared_from=shared_from, shared_blocks=shared)
        elif kind == 1:  # append one token
            if not ps.live[slot] or ps.lens[slot] >= ps.nb_max * ps.block:
                continue
            split = ps.prepare_append(slot)
            if split is not None:
                # post-CoW: the boundary block is exclusively this slot's
                assert ps.pool.ref[split.dst] == 1
                assert not any(
                    split.dst in ps.tables[s, : int(ps.n_blocks[s])]
                    for s in range(n_slots)
                    if s != slot and ps.live[s]
                )
            # the write-target block is never visible to another live slot
            ib = int(ps.lens[slot]) // ps.block
            bid = int(ps.tables[slot, ib])
            assert ps.pool.ref[bid] == 1
            ps.note_token(slot)
        else:  # free
            if not ps.live[slot]:
                continue
            ps.free(slot)
        ps.audit()
        applied += 1
    return applied


@settings(max_examples=60, deadline=None)
@given(
    ops_seq=st.lists(
        st.tuples(
            st.integers(0, 2), st.integers(0, 7),
            st.integers(0, 1023), st.integers(0, 63),
        ),
        min_size=1, max_size=60,
    )
)
def test_block_pool_invariants_hypothesis(ops_seq):
    """Under arbitrary admit/share/append/free interleavings: refcounts ==
    live table references, the free list never holds a referenced block or a
    duplicate (each freed block returns exactly once), copy-on-write keeps
    appended-to blocks private to one live slot, and used pool bytes equal
    the live block-table entries'."""
    ps = paged.PagedSlots(4, 8 * 16, block=16)
    _apply_ops(ps, ops_seq)
    for slot in range(4):
        if ps.live[slot]:
            ps.free(slot)
    ps.audit()
    assert ps.pool.n_used == 0 and ps.pool.n_free == ps.pool.n_blocks - 1


def test_block_pool_invariants_deterministic():
    """Deterministic mirror of the hypothesis fuzz (runs without the
    optional dependency): long random op streams over several seeds."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        ps = paged.PagedSlots(4, 8 * 16, block=16)
        ops_seq = zip(
            rng.integers(0, 3, 300), rng.integers(0, 8, 300),
            rng.integers(0, 1024, 300), rng.integers(0, 64, 300),
        )
        assert _apply_ops(ps, ops_seq) > 50
        for slot in range(4):
            if ps.live[slot]:
                ps.free(slot)
        ps.audit()
        assert ps.pool.n_used == 0


def test_block_pool_cow_on_shared_boundary():
    """The copy-on-write split, explicitly: a follower aliasing a donor's
    blocks appends into the shared boundary block -> it gets a fresh private
    block, the donor keeps the original, and the original frees only when
    its LAST reference drops."""
    ps = paged.PagedSlots(2, 8 * 16, block=16)
    ps.admit(0, 32)  # two full blocks
    ps.admit(1, 30, shared_from=0, shared_blocks=2)  # aliases both
    boundary = int(ps.tables[1, 1])
    assert boundary == int(ps.tables[0, 1]) and ps.pool.ref[boundary] == 2
    split = ps.prepare_append(1)  # append at 30: inside the shared block
    assert split is not None and split.src == boundary
    ps.note_token(1)
    assert int(ps.tables[1, 1]) == split.dst != boundary
    assert ps.pool.ref[boundary] == 1 and ps.pool.ref[split.dst] == 1
    ps.audit()
    free_before = set(ps.pool.free_list())
    ps.free(0)
    assert boundary in set(ps.pool.free_list()) - free_before  # last ref
    ps.free(1)
    ps.audit()
    assert ps.pool.n_used == 0
