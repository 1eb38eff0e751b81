"""Mixture-of-experts FFN with sort-based capacity dispatch.

Why not GShard one-hot dispatch: its dispatch einsum costs O(T·E·C·D) FLOPs,
which inflates the compiled-HLO FLOP count quadratically in sequence length
and would poison the roofline analysis.  The sort-based formulation costs
O(T·k·D·F) in the expert matmuls — proportional to *active* parameters — plus
O(T·k·log) for the sort and O(T·k·D) for gather/scatter.

Expert-parallel sharding: the per-expert batched matmul ``ecd,edf->ecf``
shards E over the model axis when divisible (OLMoE: 64/16), otherwise the
expert FFN dim F is sharded (Mixtral 8e, Jamba 16e) — see
``distributed/sharding.py``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common
from repro.models.common import KeyGen, Params


def _ep_spec(cfg: ArchConfig):
    """Expert-parallel sharding constraint for the dispatched token block
    [E, C, D]: E over the model axis when divisible (OLMoE 64, Jamba 16),
    else the per-expert FFN dim is sharded and the block replicates.  Without
    this constraint XLA partial-sums the expert matmuls over the model axis
    (observed: 8 x 32 GB all-reduce per Jamba train step — EXPERIMENTS.md
    §Perf hillclimb C)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return None
    m = mesh.shape["model"]
    if m > 1 and cfg.moe.n_experts % m == 0:
        return jax.sharding.PartitionSpec("model", None, None)
    return None


def init_moe(key: jax.Array, cfg: ArchConfig) -> Params:
    assert cfg.moe is not None
    kg = KeyGen(key)
    pdtype = common.resolve_dtype(cfg.param_dtype)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts

    def expert_w(k, shape, fan_in):
        return common.dense_init(k, shape, pdtype, fan_in=fan_in)

    return {
        "router": common.dense_init(kg(), (D, E), jnp.float32, fan_in=D),
        "w_gate": expert_w(kg(), (E, D, F), D),
        "w_up": expert_w(kg(), (E, D, F), D),
        "w_down": expert_w(kg(), (E, F, D), F),
    }


def expert_capacity(n_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    cap = int(math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    return max(8, ((cap + 7) // 8) * 8)  # align for TPU-friendly shapes


def apply_moe(p: Params, cfg: ArchConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar).

    aux_loss is the standard switch-style load-balancing loss
    ``E * sum_e(frac_tokens_e * mean_prob_e)`` (== 1.0 at perfect balance).
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    C = expert_capacity(T, cfg)

    xf = x.reshape(T, D)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # ---- load-balancing aux loss ---------------------------------------- #
    frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=1), axis=0
    ) / k  # fraction of token-slots routed to each expert
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)

    # ---- sort token-expert pairs by expert ------------------------------- #
    e_flat = top_i.reshape(-1)  # [T*k]
    w_flat = top_p.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(e_flat, stable=True)
    e_s, w_s, t_s = e_flat[order], w_flat[order], t_flat[order]
    # position of each pair within its expert's group
    first = jnp.searchsorted(e_s, e_s, side="left")
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos_in_e < C
    slot = jnp.where(keep, e_s * C + pos_in_e, E * C)  # dropped -> overflow row

    # ---- dispatch -> per-expert batches (all-to-all under EP) ------------- #
    xs = jnp.zeros((E * C + 1, D), x.dtype).at[slot].set(xf[t_s])
    xe = xs[: E * C].reshape(E, C, D)
    ep = _ep_spec(cfg)
    xe = common.maybe_shard(xe, ep)

    # ---- expert FFN (SwiGLU), batched over E ------------------------------ #
    dt = x.dtype
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dt))
    h = common.swiglu(g, u)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
    ye = common.maybe_shard(ye, ep)

    # ---- combine ----------------------------------------------------------- #
    ys = jnp.concatenate([ye.reshape(E * C, D), jnp.zeros((1, D), dt)], axis=0)
    contrib = ys[slot] * (w_s * keep).astype(dt)[:, None]
    out = jnp.zeros((T, D), dt).at[t_s].add(contrib)
    return out.reshape(B, S, D), aux.astype(jnp.float32)
