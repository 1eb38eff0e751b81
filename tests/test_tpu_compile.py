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
import re

import jax
import jax.numpy as jnp
import numpy as np
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
    kv_stack = ((3, B, SKV, KV, HD), bf)  # a decode state's stacked cache
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
            lambda q, k, v, li, qp, kp: decode_attention.decode_attention(
                q, k, v, layer=li, q_pos=qp, kv_pos=kp),
            [q_dec, kv_stack, kv_stack, ((), i32), ((B, 1), i32), kv_rows],
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


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def test_dense_decode_updates_its_cache_in_place(one_chip):
    """The serving engine's dense decode program, its state donated as the
    engine donates it, compiled at qwen2-1.5b widths (batch 8, 3 layers):
    the stacked caches alias the program's outputs, the attention kernel
    reads them where they lie, and no copy, relayout (a reshape that is not
    a bitcast), dynamic-slice or dynamic-update-slice spans a whole layer's
    cache."""
    import dataclasses
    import functools
    import types

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import registry
    from repro.serving.engine import ServingEngine

    n_layers, slots, max_len = 3, 8, 2176  # 17 kv blocks: no width is 2176
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=n_layers)
    api = registry.get_model(cfg)
    impl = functools.partial(ServingEngine._decode_impl,
                             types.SimpleNamespace(api=api, cfg=cfg))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: api.init(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)))
    state = on_chip(jax.eval_shape(lambda: api.init_state(cfg, slots, max_len)))
    ops.set_kernel_mode("pallas")
    try:
        compiled = jax.jit(impl, donate_argnums=(2,)).lower(
            params, on_chip(jax.ShapeDtypeStruct((slots, 1), jnp.int32)), state,
            on_chip(jax.ShapeDtypeStruct((slots,), jnp.bool_))).compile()
    finally:
        ops.set_kernel_mode(None)
    text = compiled.as_text()

    layer = slots * max_len * cfg.n_kv_heads * cfg.resolved_head_dim
    cache_bytes = 2 * n_layers * layer * 2  # K and V, bf16
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    assert re.search(r"%decode_attention\S* = .*tpu_custom_call", text)
    whole_layer = []
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if not m or m.group(3) not in (
                "copy", "copy-start", "reshape", "dynamic-slice",
                "dynamic-update-slice"):
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if max_len in dims and int(np.prod(dims)) >= layer:
            whole_layer.append(line.strip()[:200])
    assert not whole_layer, whole_layer
