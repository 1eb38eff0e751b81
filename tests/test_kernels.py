"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_prefill import flash_attention
from repro.kernels.kv_quant import kv_dequant, kv_quant
from repro.kernels.ssd_scan import ssd_chunked
from repro.kernels.ops import ssd_chunked_jnp

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --------------------------------------------------------------------------- #
# flash (suffix-)prefill
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "B,Sq,Skv,H,KV,hd",
    [
        (1, 16, 16, 2, 2, 8),    # MHA square
        (2, 24, 40, 4, 2, 16),   # GQA, suffix longer than queries
        (1, 8, 64, 8, 1, 32),    # MQA
        (2, 33, 47, 4, 4, 24),   # non-multiple-of-block shapes (padding)
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(B, Sq, Skv, H, KV, hd, dtype):
    q, k, v = randn(B, Sq, H, hd, dtype=dtype), randn(B, Skv, KV, hd, dtype=dtype), randn(
        B, Skv, KV, hd, dtype=dtype
    )
    offset = Skv - Sq  # suffix prefill: queries sit at the end of the kv span
    q_pos = ref.causal_positions(B, Sq, offset)
    kv_pos = ref.causal_positions(B, Skv)
    out = flash_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True, interpret=True,
        block_q=8, block_kv=16,
    )
    want = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=TOL[dtype]
    )


@pytest.mark.parametrize("window", [4, 16])
def test_flash_sliding_window(window):
    B, S, H, KV, hd = 2, 32, 4, 2, 16
    q, k, v = randn(B, S, H, hd), randn(B, S, KV, hd), randn(B, S, KV, hd)
    pos = ref.causal_positions(B, S)
    out = flash_attention(
        q, k, v, q_pos=pos, kv_pos=pos, causal=True, window=window,
        interpret=True, block_q=8, block_kv=8,
    )
    want = ref.attention_ref(q, k, v, q_pos=pos, kv_pos=pos, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_noncausal():
    B, Sq, Skv, H, KV, hd = 1, 16, 24, 2, 2, 8
    q, k, v = randn(B, Sq, H, hd), randn(B, Skv, KV, hd), randn(B, Skv, KV, hd)
    q_pos = jnp.zeros((B, Sq), jnp.int32)
    kv_pos = ref.causal_positions(B, Skv)
    out = flash_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=False, interpret=True,
        block_q=8, block_kv=8,
    )
    want = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# --------------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "B,L,H,KV,hd", [(2, 40, 4, 2, 16), (1, 17, 8, 1, 32), (3, 64, 6, 6, 8)]
)
def test_decode_matches_ref(B, L, H, KV, hd):
    q = randn(B, 1, H, hd)
    # a three-layer stack: the kernel reads layer 1 where it lies
    k, v = randn(3, B, L, KV, hd), randn(3, B, L, KV, hd)
    pos = jnp.asarray(RNG.integers(L // 2, L, (B, 1)), jnp.int32)
    idx = jnp.arange(L)[None]
    kv_pos = jnp.where(idx <= pos, idx, -1)
    out = decode_attention(
        q, k, v, layer=jnp.int32(1), q_pos=pos, kv_pos=kv_pos, interpret=True,
        block_kv=8,
    )
    want = ref.attention_ref(q, k[1], v[1], q_pos=pos, kv_pos=kv_pos, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_decode_ring_buffer_positions():
    """SWA ring semantics: slots hold arbitrary absolute positions."""
    B, W, H, KV, hd = 2, 16, 4, 2, 8
    q = randn(B, 1, H, hd)
    k, v = randn(2, B, W, KV, hd), randn(2, B, W, KV, hd)
    from repro.models.attention import _ring_positions

    length = jnp.asarray([20, 9])
    kv_pos = _ring_positions(length, W, B)
    pos = (length - 1)[:, None]
    out = decode_attention(
        q, k, v, layer=jnp.int32(1), q_pos=pos, kv_pos=kv_pos, window=W,
        interpret=True, block_kv=8,
    )
    want = ref.attention_ref(
        q, k[1], v[1], q_pos=pos, kv_pos=kv_pos, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# --------------------------------------------------------------------------- #
# kv quant
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", [(8, 16), (3, 5, 32), (2, 7, 4, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matches_ref_and_bounds(shape, dtype):
    x = randn(*shape, dtype=dtype)
    q, s = kv_quant(x, interpret=True, block_rows=4)
    qr, sr = ref.kv_quant_ref(x)
    assert (np.asarray(q) == np.asarray(qr)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = kv_dequant(q, s, dtype=jnp.float32, interpret=True, block_rows=4)
    err = np.abs(np.asarray(y) - np.asarray(x, np.float32))
    bound = np.asarray(s) / 2 + 1e-6
    assert (err <= bound).all()


# --------------------------------------------------------------------------- #
# SSD chunked scan
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "B,L,H,P,G,S,chunk",
    [
        (1, 16, 2, 8, 1, 8, 8),
        (2, 40, 4, 8, 2, 16, 16),   # L not a chunk multiple (padding)
        (1, 64, 8, 16, 1, 32, 32),
        (2, 24, 4, 8, 4, 8, 8),
    ],
)
def test_ssd_kernel_matches_sequential_oracle(B, L, H, P, G, S, chunk):
    x = randn(B, L, H, P)
    dt = jnp.abs(randn(B, L, H)) * 0.1
    A = -jnp.abs(randn(H)) - 0.1
    Bm, Cm = randn(B, L, G, S), randn(B, L, G, S)
    h0 = randn(B, H, P, S) * 0.1
    y_ref, hT_ref = ref.ssd_scan_ref(x, dt, A, Bm, Cm, initial_state=h0)
    y, hT = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk, initial_state=h0, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref), atol=5e-5)
    # and the jnp chunked path used by the models on CPU
    y2, hT2 = ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=chunk, initial_state=h0)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref), atol=5e-5)
    np.testing.assert_allclose(np.asarray(hT2), np.asarray(hT_ref), atol=5e-5)


# --------------------------------------------------------------------------- #
# KV-sharded flash attention: online-softmax combine + chunked reference
# --------------------------------------------------------------------------- #
def test_kvshard_combine():
    """Splitting KV into shards and combining per-shard (m, l, o) pieces with
    the pmax/psum formula must equal the attention oracle exactly — the math
    behind ops._kv_sharded_attention (EXPERIMENTS.md §Perf hillclimbs A/B)."""
    from repro.kernels.ops import _flash_pieces

    B, Sq, Skv, H, KV, hd = 2, 24, 64, 4, 2, 16
    q = randn(B, Sq, H, hd)
    k, v = randn(B, Skv, KV, hd), randn(B, Skv, KV, hd)
    q_pos = ref.causal_positions(B, Sq, Skv - Sq)
    kv_pos = ref.causal_positions(B, Skv)
    want = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True, window=20)

    shards, piece = 4, Skv // 4
    pieces = []
    for i in range(shards):
        sl = slice(i * piece, (i + 1) * piece)
        pieces.append(
            _flash_pieces(q, k[:, sl], v[:, sl], q_pos, kv_pos[:, sl],
                          causal=True, window=20, q_chunk=8)
        )
    m_glob = jnp.max(jnp.stack([m for m, _, _ in pieces]), 0)
    l_glob = sum(l * jnp.exp(m - m_glob) for m, l, _ in pieces)
    o_glob = sum(o * jnp.exp(m - m_glob)[..., None] for m, _, o in pieces)
    out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_chunked_ref_matches_plain_ref():
    B, Sq, Skv, H, KV, hd = 2, 40, 56, 4, 2, 8
    q = randn(B, Sq, H, hd)
    k, v = randn(B, Skv, KV, hd), randn(B, Skv, KV, hd)
    q_pos = ref.causal_positions(B, Sq, Skv - Sq)
    kv_pos = ref.causal_positions(B, Skv)
    want = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True)
    got = ref.attention_ref_chunked(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True, q_chunk=16
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ssd_state_carry_equals_full_scan():
    """Suffix-prefill invariant: scanning [a|b] == scan(a) then scan(b|state)."""
    B, L, H, P, G, S = 1, 32, 2, 8, 1, 8
    x = randn(B, L, H, P)
    dt = jnp.abs(randn(B, L, H)) * 0.1
    A = -jnp.abs(randn(H)) - 0.1
    Bm, Cm = randn(B, L, G, S), randn(B, L, G, S)
    y_full, hT_full = ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=8)
    half = L // 2
    _, h1 = ssd_chunked_jnp(x[:, :half], dt[:, :half], A, Bm[:, :half], Cm[:, :half], chunk=8)
    y2, h2 = ssd_chunked_jnp(
        x[:, half:], dt[:, half:], A, Bm[:, half:], Cm[:, half:], chunk=8,
        initial_state=h1,
    )
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, half:]), atol=5e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hT_full), atol=5e-5)


# --------------------------------------------------------------------------- #
# ops dispatch under Pallas: kernel, explicit jnp rule, or an error
# --------------------------------------------------------------------------- #
@pytest.fixture
def interpret_mode():
    from repro.kernels import ops

    ops.set_kernel_mode("pallas_interpret")
    ops.reset_dispatch_counts()
    yield ops
    ops.set_kernel_mode(None)
    ops.reset_dispatch_counts()


def _dispatch_calls(ops, hd, S=128):
    """op name -> a call of its ops entry point at head dim ``hd``."""
    B, H, KV, blk = 2, 4, 2, 128
    q, q1 = randn(B, S, H, hd), randn(B, 1, H, hd)
    k, v = randn(B, S, KV, hd), randn(B, S, KV, hd)
    pool = randn(2 * blk, KV, hd)
    pos = ref.causal_positions(B, S)
    tbl = jnp.asarray([[0], [1]], jnp.int32)
    last = jnp.full((B, 1), S - 1, jnp.int32)
    return {
        "flash_prefill": lambda: ops.flash_attention(q, k, v, q_pos=pos, kv_pos=pos),
        "packed_prefill": lambda: ops.packed_attention(
            q, k, v, q_pos=pos, kv_pos=pos, q_seg=jnp.zeros_like(pos),
            kv_seg=jnp.zeros_like(pos)),
        "fused_prefill": lambda: ops.fused_prefill(q, k, v, q_pos=pos, kv_pos=pos),
        "decode_attention": lambda: ops.decode_attention(
            q1, k[None], v[None], layer=0, q_pos=last, kv_pos=pos),
        "paged_decode": lambda: ops.paged_decode(
            q1, pool, pool, block_table=tbl, q_pos=last, block=blk),
        "chunked_prefill": lambda: ops.chunked_prefill(
            q[:, :8], pool, pool, block_table=tbl, q_pos=pos[:, :8], block=blk),
    }


@pytest.mark.parametrize("op", [
    "flash_prefill", "packed_prefill", "fused_prefill", "decode_attention",
    "paged_decode", "chunked_prefill",
])
def test_pallas_dispatch_raises_on_unsupported_shapes(interpret_mode, op):
    """A head dim the kernel cannot tile (64: not lane-aligned) raises an
    error naming the op and the operand shapes; it is never silently served
    by the jnp reference."""
    call = _dispatch_calls(interpret_mode, hd=64)[op]
    with pytest.raises(interpret_mode.KernelUnsupported, match=rf"{op}: .*\[2, ") as e:
        call()
    assert "64]" in str(e.value)
    assert interpret_mode.dispatch_counts() == {}


def test_dispatch_counts_kernel_calls_and_short_prefill_rule(interpret_mode):
    """Supported shapes take the kernel; a prefill shorter than MIN_KERNEL_Q
    takes the jnp reference by the explicit rule; both are counted."""
    ops = interpret_mode
    calls = _dispatch_calls(ops, hd=128, S=ops.MIN_KERNEL_Q)
    calls["decode_attention"]()
    calls["flash_prefill"]()
    _dispatch_calls(ops, hd=128, S=ops.MIN_KERNEL_Q // 2)["flash_prefill"]()
    assert ops.dispatch_counts() == {
        "decode_attention": {"kernel": 1, "jnp": 0},
        "flash_prefill": {"kernel": 1, "jnp": 1},
    }
