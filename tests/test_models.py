"""Layer-level model tests: RoPE, ring buffers, MoE vs dense oracle, SSD
model path vs sequential oracle, suffix-prefill equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config, reduced_config
from repro.configs.base import ArchConfig, MoEConfig
from repro.kernels import ref
from repro.models import attention, layers, moe, registry
from repro.models.attention import _ring_positions

RNG = np.random.default_rng(7)


def test_rope_rotation_preserves_norm_and_relativity():
    x = jnp.asarray(RNG.standard_normal((2, 8, 4, 16)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8)).astype(jnp.int32)
    y = layers.apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )
    # relative property: <q_m, k_n> depends only on (m - n)
    q = jnp.asarray(RNG.standard_normal((1, 1, 1, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 1, 1, 16)), jnp.float32)

    def dot_at(m, n):
        qm = layers.apply_rope(q, jnp.full((1, 1), m, jnp.int32), 1e4)
        kn = layers.apply_rope(k, jnp.full((1, 1), n, jnp.int32), 1e4)
        return float(jnp.sum(qm * kn))

    assert dot_at(5, 3) == pytest.approx(dot_at(102, 100), abs=1e-4)


@settings(max_examples=40, deadline=None)
@given(length=st.integers(0, 100), w=st.sampled_from([4, 8, 16]))
def test_ring_positions_invariants(length, w):
    pos = np.asarray(_ring_positions(jnp.asarray([length]), w, 1))[0]
    for j, p in enumerate(pos):
        if p < 0:
            assert length <= j  # slot never written
        else:
            assert p % w == j
            assert length - w <= p < length  # within the live window


def test_moe_matches_dense_oracle_when_dropless():
    cfg = reduced_config(
        get_config("olmoe-1b-7b"),
        moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0),
    )
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.standard_normal((2, 12, cfg.d_model)) * 0.3, jnp.float32)
    out, aux = moe.apply_moe(p, cfg, x)
    want = ref.moe_ref(
        x.reshape(-1, cfg.d_model), p["router"], p["w_gate"], p["w_up"], p["w_down"],
        top_k=2,
    ).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    assert float(aux) >= 1.0 - 1e-6  # switch loss lower bound at balance


def test_moe_capacity_drops_are_bounded():
    cfg = reduced_config(
        get_config("olmoe-1b-7b"),
        moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=0.5),
    )
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    out, _ = moe.apply_moe(p, cfg, x)  # must not crash; dropped tokens -> 0 contrib
    assert bool(jnp.isfinite(out).all())


def test_attention_prefill_ring_matches_full_attention():
    """SWA prefill through the ring buffer == windowed attention over the
    full sequence, even when S > window."""
    cfg = reduced_config(get_config("mixtral-8x22b"))  # window 16
    p = attention.init_attention(jax.random.PRNGKey(0), cfg)
    B, S = 2, 40  # spans the ring 2.5x
    x = jnp.asarray(RNG.standard_normal((B, S, cfg.d_model)) * 0.2, jnp.float32)
    full = attention.forward(p, cfg, x)

    cache = attention.init_kv_cache(cfg, B, 64)
    out, cache = attention.prefill(p, cfg, x, cache, jnp.zeros((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), atol=1e-4)

    # and decode continues correctly off the ring state
    x1 = jnp.asarray(RNG.standard_normal((B, 1, cfg.d_model)) * 0.2, jnp.float32)
    stacked = attention.KVCache(cache.k[None], cache.v[None])  # one layer
    dec, _ = attention.decode(p, cfg, x1, stacked, 0, jnp.full((B,), S, jnp.int32))
    full2 = attention.forward(p, cfg, jnp.concatenate([x, x1], 1))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full2[:, -1]), atol=1e-4)


def test_vocab_padding_never_predicted():
    """Padded vocab rows exist for sharding; check logits shape covers them
    and real token rows dominate (padding rows are random init, untrained —
    just assert shape plumbing)."""
    cfg = reduced_config(get_config("qwen2-0.5b"), vocab=100)  # pads to 128
    assert cfg.padded_vocab == 128
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(RNG.integers(0, 100, (1, 8)), jnp.int32)
    logits, _ = api.forward(params, cfg, toks)
    assert logits.shape[-1] == 128


@pytest.mark.parametrize("arch", ["llama-7b", "jamba-1.5-large-398b", "mamba2-1.3b"])
def test_suffix_prefill_equals_full_prefill(arch):
    """The paper's mechanism at the model level: prefix state + suffix
    prefill == one-shot prefill, for attention, hybrid and SSM families."""
    cfg = reduced_config(get_config(arch))
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(2), cfg)
    B, S = 2, 24
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)

    full_state = api.init_state(cfg, B, 64)
    l_full, full_state = api.prefill(params, cfg, toks, full_state)

    st2 = api.init_state(cfg, B, 64)
    _, st2 = api.prefill(params, cfg, toks[:, : S // 2], st2)
    l_suffix, st2 = api.prefill(params, cfg, toks[:, S // 2 :], st2)
    np.testing.assert_allclose(np.asarray(l_suffix), np.asarray(l_full), atol=3e-4)

    # states must produce identical continuations
    nxt = jnp.argmax(l_full, -1)[:, None].astype(jnp.int32)
    d1, _ = api.decode(params, cfg, nxt, full_state)
    d2, _ = api.decode(params, cfg, nxt, st2)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=3e-4)


def _scan_decode(params, cfg, tokens, state):
    """The previous dense decode: a scan with (params, caches) as its ``xs``,
    each layer's cache sliced out of the stack and written back as ``ys``."""
    from repro.models import blocks, lm

    kinds, _ = lm._layout(cfg)
    x = layers.embed_tokens(params["embed"], cfg, tokens)

    def period_fn(x, per):
        layer_params, caches = per
        new = []
        for i, kind in enumerate(kinds):
            one = jax.tree_util.tree_map(lambda a: a[None], caches[i])
            x, c = blocks.decode(layer_params[i], cfg, kind, x, one, 0, state.pos)
            new.append(jax.tree_util.tree_map(lambda a: a[0], c))
        return x, tuple(new)

    x, caches = jax.lax.scan(period_fn, x, (tuple(params["layers"]), state.caches))
    x = layers.apply_norm(params["final_norm"], cfg, x)
    logits = layers.lm_logits(params["embed"], cfg, x)[:, 0]
    return logits, lm.LMState(pos=state.pos + 1, caches=caches)


@pytest.mark.parametrize("arch,mode", [
    ("qwen2-1.5b", "ref"),
    ("qwen2-1.5b", "pallas_interpret"),
    ("mixtral-8x22b", "ref"),  # ring-buffer sliding window
    ("mixtral-8x22b", "pallas_interpret"),
    ("jamba-1.5-large-398b", "ref"),  # hybrid attention + SSM period
    ("jamba-1.5-large-398b", "pallas_interpret"),
    ("mamba2-1.3b", "ref"),  # no attention cache: the carry holds SSM state
])
def test_in_place_decode_matches_scan_and_recompute(arch, mode):
    """Dense decode with the stacked caches in the layer loop's carry (the
    state donated, as the engine runs it) equals the previous scan over
    per-layer slices, step by step, with a slot frozen while inactive and a
    context landed in another slot mid-way by ``insert_slot``; each active
    slot's logits equal a full recompute of its tokens (``forward``, whose
    attention is ``ref.attention_ref`` here)."""
    from repro.kernels import ops
    from repro.kvcache import paged

    over = {"head_dim": 128} if mode == "pallas_interpret" else {}
    cfg = reduced_config(get_config(arch), **over)
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(11), cfg)
    B, S, max_len, steps = 3, 20, 32, 4
    rng = np.random.default_rng(3)
    hist = [list(r) for r in rng.integers(0, cfg.vocab, (B, S))]
    landed = list(rng.integers(0, cfg.vocab, 9))

    def impl(p, t, s, a):
        logits, new = api.decode(p, cfg, t, s)
        return logits, new._replace(pos=jnp.where(a, new.pos, s.pos))

    def old(p, t, s, a):
        logits, new = _scan_decode(p, cfg, t, s)
        return logits, new._replace(pos=jnp.where(a, new.pos, s.pos))

    ops.set_kernel_mode(mode)
    try:
        new_step = jax.jit(impl, donate_argnums=(2,))
        old_step = jax.jit(old)
        prefill = jax.jit(lambda p, t, s: api.prefill(p, cfg, t, s))
        first, state = prefill(params, jnp.asarray(hist, jnp.int32),
                               api.init_state(cfg, B, max_len))
        pending = [int(t) for t in np.asarray(jnp.argmax(first, -1))]
        one_first, one = prefill(params, jnp.asarray([landed], jnp.int32),
                                 api.init_state(cfg, 1, max_len))
        art = paged.extract_slot(cfg, one, 0, len(landed))
        state_old = jax.tree_util.tree_map(jnp.copy, state)
        active = np.array([True, True, False])  # slot 2 frozen
        for step in range(steps):
            if step == 2:  # land a stored context in slot 1 between steps
                state = paged.insert_slot(cfg, state, 1, art)
                state_old = paged.insert_slot(cfg, state_old, 1, art)
                hist[1] = list(landed)
                pending[1] = int(jnp.argmax(one_first[0]))
            toks = jnp.asarray([[t] for t in pending], jnp.int32)
            a = jnp.asarray(active)
            got, state = new_step(params, toks, state, a)
            want, state_old = old_step(params, toks, state_old, a)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
            nxt = np.asarray(jnp.argmax(got, -1))
            for b in np.flatnonzero(active):
                hist[b].append(pending[b])
                pending[b] = int(nxt[b])
        for b in np.flatnonzero(active):  # the last step against recompute
            full, _ = jax.jit(lambda p, t: api.forward(p, cfg, t))(
                params, jnp.asarray([hist[b]], jnp.int32))
            np.testing.assert_allclose(
                np.asarray(got[b]), np.asarray(full[0, -1]), atol=2e-4)
        assert np.asarray(state.pos).tolist() == [S + steps, len(landed) + steps - 2, S]
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5),
            state, state_old)
    finally:
        ops.set_kernel_mode(None)
