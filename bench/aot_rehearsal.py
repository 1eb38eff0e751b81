#!/usr/bin/env python3
"""Ahead-of-time memory rehearsal of each configuration's largest programs,
compiled for a described TPU v5e chip (no chip needed, nothing runs).

    JAX_PLATFORMS=cpu python3 bench/aot_rehearsal.py [cell ...]

For each cell of ``BENCHMARK.json`` (of a dense model) it compiles, at
the engine settings of its configuration, the dense decode step, the
largest packed prefill of the set-up fill (one fresh document of the
longest length) and the largest packed admission the window can form
(``admit_batch`` requests of the longest document and question: behind
the stored document where the traffic asks each document more than once,
else the whole document fresh), with the Pallas kernels, and prints each
program's ``memory_analysis()`` as one JSON line.  The weights and the
dense decode state are among a program's arguments; the engine holds both
at once, so the largest program's total is about a run's peak.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def programs(conf: dict, spec: dict):
    """(name, fn, argument shapes) of the programs to rehearse."""
    import jax
    import jax.numpy as jnp
    from repro.kvcache import paged
    from repro.models import registry
    from repro.models.attention import KVCache
    from repro.models.blocks import BlockCache

    cfg = harness.program_config(conf)
    api = registry.get_model(cfg)
    e = conf["engine"]
    slots, max_len = e["slots"], e["overrides"]["max_len"]
    k_max = e["overrides"].get("admit_batch") or slots
    params = jax.eval_shape(lambda k: api.init(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = jax.eval_shape(lambda: api.init_state(cfg, slots, max_len))
    i32 = jnp.int32

    def decode(p, t, s, a):
        logits, new = api.decode(p, cfg, t, s)
        return logits, new._replace(pos=jnp.where(a, new.pos, s.pos))

    yield ("decode", decode, (params, jax.ShapeDtypeStruct((slots, 1), i32), state,
                              jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    doc = int(spec["documents"]["length"]["max"])
    q = int(spec["question"]["length"]["max"])
    fill = (1, doc + int(spec["fill_prompt_tokens"]), 0)
    if int(spec["documents"]["asks_per_document"]) > 1:
        window = (k_max, q, doc)
    else:
        window = (k_max, doc + q, 0)
    for name, (k, n_new, matched) in (("packed_fill", fill), ("packed_window", window)):
        layout = paged.pack_layout(list(range(k)), [matched] * k, [n_new] * k)
        # a dense stack is one block kind, stacked over its layers
        kv = jax.ShapeDtypeStruct((cfg.n_layers, 1, layout.kv_len, cfg.n_kv_heads,
                                   cfg.resolved_head_dim), jnp.bfloat16)
        caches = (BlockCache(KVCache(kv, kv), None),)
        ql = jax.ShapeDtypeStruct((1, layout.q_len), i32)
        kl = jax.ShapeDtypeStruct((1, layout.kv_len), i32)

        def packed(p, t, c, qp, qs, qr, kp, ks, li):
            return api.prefill_packed(p, cfg, t, c, q_pos=qp, q_seg=qs, q_rows=qr,
                                      kv_pos=kp, kv_seg=ks, last_idx=li)

        yield (f"{name}(q={layout.q_len},kv={layout.kv_len})", packed,
               (params, ql, caches, ql, ql, ql, kl, kl, jax.ShapeDtypeStruct((slots,), i32)))


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    harness.ensure_src()
    from bench import traffic as traffic_mod
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    names = (argv if argv is not None else sys.argv[1:]) or list(cells)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    ops.set_kernel_mode("pallas")
    for name in names:
        w = cells[name]
        conf = harness.load_config(w["config"])
        spec = traffic_mod.load(w["traffic"], w["config"])
        for prog, fn, shapes in programs(conf, spec):
            placed = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
            compiled = jax.jit(fn).lower(*placed).compile()
            m = compiled.memory_analysis()
            print(json.dumps({
                "cell": name, "program": prog,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "total_bytes": (m.argument_size_in_bytes + m.output_size_in_bytes
                                + m.temp_size_in_bytes - m.alias_size_in_bytes),
                "kernels": "tpu_custom_call" in compiled.as_text(),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
