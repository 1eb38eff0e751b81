"""AOT compiles of the attention kernels for a described TPU v5e chip.

Interpret mode accepts block shapes the TPU compiler refuses, so every
attention kernel of the serving path is compiled here at qwen2-1.5b's
published widths (12 query heads, 2 KV heads, head dim 128, bf16, kv block
128, batch > 1) for one chip of a described v5e:2x2 topology; no chip is
attached.  The topology is described inside a fixture, never while a module
is imported (only one process at a time may load the TPU library), and the
persistent compilation cache is off around the compiles (an entry written
for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    chunked_prefill,
    decode_attention,
    flash_prefill,
    fused_prefill,
    packed_prefill,
    paged_decode,
)

H, KV, HD, BLOCK, B = 12, 2, 128, 128, 4  # qwen2-1.5b widths, kv block 128
SQ, SKV, NB, N_BLOCKS = 256, 1024, 8, 33  # prefill q/kv rows, table, pool


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # keep libtpu's logs off /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler / library lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _cases():
    """name -> (kernel call, operand shapes/dtypes)."""
    bf, i32 = jnp.bfloat16, jnp.int32
    q_dec = ((B, 1, H, HD), bf)
    pool = ((N_BLOCKS * BLOCK, KV, HD), bf)
    q_pre, kv_pre = ((B, SQ, H, HD), bf), ((B, SKV, KV, HD), bf)
    q_rows, kv_rows = ((B, SQ), i32), ((B, SKV), i32)
    return {
        "paged_decode": (
            lambda q, k, v, t, p: paged_decode.paged_decode_attention(
                q, k, v, block_table=t, q_pos=p, block=BLOCK),
            [q_dec, pool, pool, ((B, NB), i32), ((B, 1), i32)],
        ),
        "chunked_prefill": (
            lambda q, k, v, t, p: chunked_prefill.chunked_prefill_attention(
                q, k, v, block_table=t, q_pos=p, block=BLOCK),
            [((B, BLOCK, H, HD), bf), pool, pool, ((B, NB), i32),
             ((B, BLOCK), i32)],
        ),
        "decode_attention": (
            lambda q, k, v, qp, kp: decode_attention.decode_attention(
                q, k, v, q_pos=qp, kv_pos=kp),
            [q_dec, kv_pre, kv_pre, ((B, 1), i32), kv_rows],
        ),
        "flash_prefill": (
            lambda q, k, v, qp, kp: flash_prefill.flash_attention(
                q, k, v, q_pos=qp, kv_pos=kp),
            [q_pre, kv_pre, kv_pre, q_rows, kv_rows],
        ),
        "packed_prefill": (
            lambda q, k, v, qp, kp, qs, ks: packed_prefill.packed_flash_attention(
                q, k, v, q_pos=qp, kv_pos=kp, q_seg=qs, kv_seg=ks),
            [q_pre, kv_pre, kv_pre, q_rows, kv_rows, q_rows, kv_rows],
        ),
        "fused_prefill": (
            lambda q, k, v, qp, kp: fused_prefill.fused_flash_attention(
                q, k, v, q_pos=qp, kv_pos=kp),
            [q_pre, kv_pre, kv_pre, q_rows, kv_rows],
        ),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    """The TPU compiler accepts the kernel's tiling at real widths, and the
    program it builds holds the Pallas kernel."""
    fn, operands = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
