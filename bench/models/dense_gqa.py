"""Dense decoder with grouped-query attention (Qwen2, Mistral-NeMo, Llama).

RMSNorm, rotary positions (rotate-half), causal grouped-query attention with
q/k/v biases where the configuration has them, SwiGLU MLP; a final RMSNorm
and the output head (the embedding table itself where tied).  Everything
the benchmark needs of this architecture is here:

- ``sizes``: the sizes, from the configuration file's Hugging Face keys;
- ``program_fields``: the fields of the program's ``ArchConfig`` that must
  equal the file's sizes;
- ``shapes`` and ``std``: the leaf shapes and init scales of the weight
  tree, laid out as the program's parameter tree for a dense decoder
  (``{"embed": {"table"[, "head"]}, "layers": [stacked layer],
  "final_norm"}``); ``bench/weights.py`` makes them from the seed;
- ``logits``: the plain float32 reference (``bench/reference.py`` holds the
  precision, the padding and the blocking every model shares);
- the counts: useful FLOPs of an admission segment and of a decode token,
  and in ``KERNELS`` each kernel the layers call, the step that runs it,
  how many calls a step makes and the FLOPs and bytes of one call.

Counted from shapes alone; a multiply-add is 2 operations, every tensor
bf16.  Model FLOPs count the matmuls a token needs and the attention it
needs under the causal mask, never padding: a prefill of ``n_new`` tokens
behind ``matched`` reused positions attends ``causal_pairs(matched,
n_new)`` (query, key) pairs and yields one row of logits.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.flops import BYTES, Kernel, causal_pairs
from bench.reference import CHUNK, mm, rms, rope


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


def sizes(c: dict) -> Dims:
    return Dims.from_config(c)


def program_fields(c: dict) -> dict:
    """``ArchConfig`` field -> the value the configuration file implies."""
    return {
        "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "resolved_head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"], "padded_vocab": c["vocab_size"],
        "tie_embeddings": c["tie_word_embeddings"], "qkv_bias": c["attention_bias"],
        "rope_theta": c["rope_theta"], "norm_eps": c["rms_norm_eps"],
        "dtype": c["torch_dtype"], "param_dtype": c["torch_dtype"],
        "family": "dense", "mlp_type": "swiglu", "norm_type": "rmsnorm",
        "sliding_window": None,
    }


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #
def shapes(d: Dims) -> dict:
    """Leaf shapes of the parameter tree (``L`` leading on layer leaves)."""
    D, L, H, KV, hd, F, V = (d.d_model, d.n_layers, d.n_heads, d.n_kv_heads,
                             d.head_dim, d.d_ff, d.vocab)
    attn = {"wq": (L, D, H, hd), "wk": (L, D, KV, hd), "wv": (L, D, KV, hd),
            "wo": (L, H, hd, D)}
    if d.qkv_bias:
        attn.update({"bq": (L, H, hd), "bk": (L, KV, hd), "bv": (L, KV, hd)})
    embed = {"table": (V, D)}
    if not d.tied:
        embed["head"] = (D, V)
    layer = {
        "norm1": {"scale": (L, D)}, "attn": attn, "norm2": {"scale": (L, D)},
        "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)},
    }
    return {"embed": embed, "layers": [layer], "final_norm": {"scale": (D,)}}


def std(path: str, shape) -> float:
    """Embedding and biases 0.02; a projection 1 / sqrt(fan_in)."""
    name = path.rsplit("/", 1)[-1]
    if name == "table" or name.startswith("b"):
        return 0.02
    if name == "head":
        return shape[0] ** -0.5
    if name == "wo":  # [L, H, hd, D]
        return (shape[1] * shape[2]) ** -0.5
    return shape[1] ** -0.5  # [L, in, ...]


# --------------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _layer(x, lp, *, d: Dims, fmt):
    """One decoder layer on x [S, D] float32; ``lp`` is the layer's weights.
    Query head ``h`` reads key/value head ``h // (n_heads / n_kv_heads)``;
    scores are scaled by ``1/sqrt(head_dim)``."""
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    S = x.shape[0]
    KV, G, hd = d.n_kv_heads, d.n_heads // d.n_kv_heads, d.head_dim
    pos = jnp.arange(S, dtype=jnp.int32)
    h = rms(x, w["norm1"]["scale"], d.norm_eps)
    a = w["attn"]
    q = mm("sd,dhe->she", h, a["wq"], fmt)
    k = mm("sd,dke->ske", h, a["wk"], fmt)
    v = mm("sd,dke->ske", h, a["wv"], fmt)
    if d.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q, pos, d.rope_theta).reshape(S, KV, G, hd)
    k = rope(k, pos, d.rope_theta)

    def block(c):
        qc = jax.lax.dynamic_slice_in_dim(q, c * CHUNK, CHUNK, axis=0)
        s = mm("qkge,ske->kgqs", qc, k, fmt) / np.sqrt(hd)
        qpos = c * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32)
        keep = pos[None, :] <= qpos[:, None]
        s = jnp.where(keep[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("kgqs,ske->qkge", p, v, fmt).reshape(CHUNK, d.n_heads, hd)

    o = jax.lax.map(block, jnp.arange(S // CHUNK)).reshape(S, d.n_heads, hd)
    x = x + mm("she,hed->sd", o, a["wo"], fmt)
    h = rms(x, w["norm2"]["scale"], d.norm_eps)
    f = w["ffn"]
    g = mm("sd,df->sf", h, f["w_gate"], fmt)
    u = mm("sd,df->sf", h, f["w_up"], fmt)
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, f["w_down"], fmt)


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _logits(x_rows, final_scale, head, *, d: Dims, fmt):
    h = rms(x_rows, final_scale.astype(jnp.float32), d.norm_eps)
    w = head.astype(jnp.float32)
    if d.tied:
        return mm("sd,vd->sv", h, w, fmt)
    return mm("sd,dv->sv", h, w, fmt)


def logits(
    weights: dict, d: Dims, tokens: Sequence[int], rows: Sequence[int],
    fmt: Optional[Tuple[int, int]] = None,
) -> np.ndarray:
    """Float32 logits [len(rows), vocab] after each position in ``rows`` of
    the sequence ``tokens``, layer by layer over the padded sequence."""
    x = reference.embed(weights["embed"]["table"], jnp.asarray(reference.padded(tokens)))
    stack = weights["layers"][0]
    for i in range(d.n_layers):
        lp = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
        x = _layer(x, lp, d=d, fmt=fmt)
    head = weights["embed"]["table"] if d.tied else weights["embed"]["head"]
    x_rows = x[jnp.asarray(np.asarray(rows, np.int32))]
    out = _logits(x_rows, weights["final_norm"]["scale"], head, d=d, fmt=fmt)
    return np.asarray(jax.device_get(out))


# --------------------------------------------------------------------------- #
# counts
# --------------------------------------------------------------------------- #
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


def _every_layer(d: Dims) -> int:
    return d.n_layers


# every layer calls each kernel once per step: the packed prefill kernel in
# an admission (``kernels/packed_prefill.py``), decode attention in a decode
# step (``kernels/decode_attention.py``)
KERNELS = {
    "packed_flash_attention": Kernel(step="admit", calls=_every_layer, call=packed_prefill_call),
    "decode_attention": Kernel(step="decode", calls=_every_layer, call=decode_attention_call),
}
