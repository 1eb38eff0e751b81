"""The benchmark's ``bench/flops.py`` as it was before each architecture's
code moved into its model module (``bench/models/dense_gqa.py``), kept
unchanged so that the tests can show the move changed no number.

Operations and bytes of the served model's step and of each kernel call.

Counted from shapes alone, for a dense decoder with grouped-query attention
(RMSNorm, rotary positions, SwiGLU MLP), as the configuration files state
it.  A multiply-add is 2 operations; every tensor is bf16 (2 bytes).

Model FLOPs count the matmuls that a token needs and the attention it
needs under the causal mask, never padding: a prefill of ``n_new`` tokens
behind ``matched`` reused positions attends ``n_new * matched +
n_new * (n_new + 1) / 2`` (query, key) pairs, and yields one row of logits.
Kernel counts are per call, which is one layer.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Iterable, Sequence, Tuple

BYTES = 2  # bf16

PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder, in the configuration file's terms."""

    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """From the Hugging Face style keys of a configuration file."""
        return cls(
            d_model=int(c["hidden_size"]),
            n_layers=int(c["num_hidden_layers"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            d_ff=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            tied=bool(c["tie_word_embeddings"]),
            qkv_bias=bool(c["attention_bias"]),
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
        )


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in ``peaks.json`` is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def layer_matmul_params(d: Dims) -> int:
    """Weights one layer multiplies each token by: q, k, v, o and the MLP."""
    attn = d.d_model * d.head_dim * (2 * d.n_heads + 2 * d.n_kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def head_params(d: Dims) -> int:
    return d.d_model * d.vocab


def weight_bytes(d: Dims) -> int:
    """Bytes of every weight a step reads: the layers (norms and biases
    included), the final norm and the output head.  The embedding lookup
    reads rows only, so an untied table is not counted."""
    per_layer = layer_matmul_params(d) + 2 * d.d_model
    if d.qkv_bias:
        per_layer += d.head_dim * (d.n_heads + 2 * d.n_kv_heads)
    return BYTES * (d.n_layers * per_layer + d.d_model + head_params(d))


def kv_bytes_per_token(d: Dims) -> int:
    """K and V of one position over all layers."""
    return BYTES * 2 * d.n_kv_heads * d.head_dim * d.n_layers


def causal_pairs(matched: int, n_new: int) -> int:
    """(query, key) pairs of ``n_new`` queries behind ``matched`` positions."""
    return n_new * matched + n_new * (n_new + 1) // 2


def attn_pair_flops(d: Dims) -> int:
    """QK^T and PV for one (query, key) pair in one layer, all heads."""
    return 4 * d.n_heads * d.head_dim


def prefill_segment_flops(d: Dims, matched: int, n_new: int) -> int:
    """Model FLOPs of one request's (suffix) prefill: ``n_new`` tokens after
    ``matched`` reused positions, and its one row of logits."""
    return (
        2 * n_new * d.n_layers * layer_matmul_params(d)
        + d.n_layers * attn_pair_flops(d) * causal_pairs(matched, n_new)
        + 2 * head_params(d)
    )


def decode_token_flops(d: Dims, live: int) -> int:
    """Model FLOPs of one decoded token that attends ``live`` positions
    (itself included)."""
    return (
        2 * d.n_layers * layer_matmul_params(d)
        + 2 * head_params(d)
        + d.n_layers * attn_pair_flops(d) * live
    )


def prefill_flops_all_logits(d: Dims, n: int) -> float:
    """A full prefill of ``n`` tokens with logits at every position and the
    attention pairs taken as ``n * n / 2`` — the convention of the cost
    model's ``PerfModel.prefill_flops``, which the tests compare with."""
    return (
        2.0 * (d.n_layers * layer_matmul_params(d) + head_params(d)) * n
        + d.n_layers * attn_pair_flops(d) * n * n / 2.0
    )


def decode_bytes_per_token(d: Dims, context_len: int) -> int:
    """HBM bytes a batch-1 decode step reads: the weights and the cache."""
    return weight_bytes(d) + kv_bytes_per_token(d) * context_len


def packed_prefill_call(d: Dims, segments: Iterable[Tuple[int, int]]) -> Tuple[int, int]:
    """(operations, bytes) one packed-prefill kernel call needs, for one
    layer: each segment's queries against its own causal span, reading its
    K/V rows and its Q, writing its O.  ``segments`` are (matched, n_new)."""
    flops = 0
    nbytes = 0
    for matched, n_new in segments:
        flops += attn_pair_flops(d) * causal_pairs(matched, n_new)
        kv_rows = matched + n_new
        nbytes += BYTES * (
            2 * kv_rows * d.n_kv_heads * d.head_dim + 2 * n_new * d.n_heads * d.head_dim
        )
    return flops, nbytes


def decode_attention_call(d: Dims, lives: Sequence[int]) -> Tuple[int, int]:
    """(operations, bytes) one decode-attention kernel call needs, for one
    layer: each active slot's query against its ``live`` positions, reading
    their K/V and its Q, writing its O."""
    flops = sum(attn_pair_flops(d) * n for n in lives)
    nbytes = sum(
        BYTES * (2 * n * d.n_kv_heads * d.head_dim + 2 * d.n_heads * d.head_dim)
        for n in lives
    )
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The roofline's least time for a call, and which bound sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
