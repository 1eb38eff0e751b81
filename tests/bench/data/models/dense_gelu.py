"""A second model module for the benchmark's tests: a dense decoder with
multi-query attention and the 2-matrix GELU MLP of the program's
``granite-34b`` (GPTBigCode-derived Granite Code), at a reduced size.

RMSNorm, rotary positions (rotate-half), causal attention with one key/value
head and no biases; MLP ``w2 @ gelu(w1 @ x + b1) + b2`` (GELU in its tanh
form, as the program computes it); an untied output head.  The tests copy
this file into a directory of their own and hand the harness that
directory: nothing under ``bench/`` names it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.flops import BYTES, Kernel, causal_pairs
from bench.reference import CHUNK, mm, rms, rope


@dataclasses.dataclass(frozen=True)
class Sizes:
    d_model: int
    n_layers: int
    n_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def sizes(c: dict) -> Sizes:
    return Sizes(d_model=int(c["hidden_size"]), n_layers=int(c["num_hidden_layers"]),
                 n_heads=int(c["num_attention_heads"]), head_dim=int(c["head_dim"]),
                 d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
                 rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]))


def program_fields(c: dict) -> dict:
    return {
        "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": 1,
        "resolved_head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"], "padded_vocab": c["vocab_size"],
        "tie_embeddings": False, "qkv_bias": False, "rope_theta": c["rope_theta"],
        "norm_eps": c["rms_norm_eps"], "family": "dense", "mlp_type": "gelu",
        "norm_type": "rmsnorm", "sliding_window": None,
    }


def shapes(d: Sizes) -> dict:
    D, L, H, hd, F, V = d.d_model, d.n_layers, d.n_heads, d.head_dim, d.d_ff, d.vocab
    layer = {
        "norm1": {"scale": (L, D)}, "norm2": {"scale": (L, D)},
        "attn": {"wq": (L, D, H, hd), "wk": (L, D, 1, hd), "wv": (L, D, 1, hd),
                 "wo": (L, H, hd, D)},
        "ffn": {"w1": (L, D, F), "b1": (L, F), "w2": (L, F, D), "b2": (L, D)},
    }
    return {"embed": {"table": (V, D), "head": (D, V)}, "layers": [layer],
            "final_norm": {"scale": (D,)}}


def std(path: str, shape) -> float:
    name = path.rsplit("/", 1)[-1]
    if name in ("table", "b1", "b2"):
        return 0.02
    if name == "head":
        return shape[0] ** -0.5
    if name == "wo":
        return (shape[1] * shape[2]) ** -0.5
    return shape[1] ** -0.5


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _layer(x, lp, *, d: Sizes, fmt):
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    S, H, hd = x.shape[0], d.n_heads, d.head_dim
    pos = jnp.arange(S, dtype=jnp.int32)
    h = rms(x, w["norm1"]["scale"], d.norm_eps)
    a = w["attn"]
    q = rope(mm("sd,dhe->she", h, a["wq"], fmt), pos, d.rope_theta)
    k = rope(mm("sd,dke->ske", h, a["wk"], fmt), pos, d.rope_theta)[:, 0]
    v = mm("sd,dke->ske", h, a["wv"], fmt)[:, 0]

    def block(c):
        qc = jax.lax.dynamic_slice_in_dim(q, c * CHUNK, CHUNK, axis=0)
        s = mm("qhe,se->hqs", qc, k, fmt) / np.sqrt(hd)
        qpos = c * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32)
        s = jnp.where((pos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return mm("hqs,se->qhe", jax.nn.softmax(s, axis=-1), v, fmt)

    o = jax.lax.map(block, jnp.arange(S // CHUNK)).reshape(S, H, hd)
    x = x + mm("she,hed->sd", o, a["wo"], fmt)
    f = w["ffn"]
    g = jax.nn.gelu(mm("sd,df->sf", rms(x, w["norm2"]["scale"], d.norm_eps), f["w1"], fmt)
                    + f["b1"])
    return x + mm("sf,fd->sd", g, f["w2"], fmt) + f["b2"]


def logits(weights: dict, d: Sizes, tokens: Sequence[int], rows: Sequence[int],
           fmt: Optional[Tuple[int, int]] = None) -> np.ndarray:
    x = reference.embed(weights["embed"]["table"], jnp.asarray(reference.padded(tokens)))
    stack = weights["layers"][0]
    for i in range(d.n_layers):
        x = _layer(x, jax.tree_util.tree_map(lambda a, i=i: a[i], stack), d=d, fmt=fmt)
    h = rms(x[jnp.asarray(np.asarray(rows, np.int32))],
            weights["final_norm"]["scale"].astype(jnp.float32), d.norm_eps)
    out = mm("sd,dv->sv", h, weights["embed"]["head"].astype(jnp.float32), fmt)
    return np.asarray(jax.device_get(out))


def _matmul_params(d: Sizes) -> int:
    return d.d_model * d.head_dim * (2 * d.n_heads + 2) + 2 * d.d_model * d.d_ff


def _pair_flops(d: Sizes) -> int:
    return 4 * d.n_heads * d.head_dim


def prefill_segment_flops(d: Sizes, matched: int, n_new: int) -> int:
    return (2 * n_new * d.n_layers * _matmul_params(d)
            + d.n_layers * _pair_flops(d) * causal_pairs(matched, n_new)
            + 2 * d.d_model * d.vocab)


def decode_token_flops(d: Sizes, live: int) -> int:
    return prefill_segment_flops(d, live - 1, 1)


def _packed_call(d: Sizes, segments) -> Tuple[int, int]:
    f = sum(_pair_flops(d) * causal_pairs(m, n) for m, n in segments)
    b = sum(BYTES * (2 * (m + n) * d.head_dim + 2 * n * d.n_heads * d.head_dim)
            for m, n in segments)
    return f, b


def _decode_call(d: Sizes, lives) -> Tuple[int, int]:
    return _packed_call(d, [(n - 1, 1) for n in lives])


KERNELS = {
    "packed_flash_attention": Kernel(step="admit", calls=lambda d: d.n_layers, call=_packed_call),
    "decode_attention": Kernel(step="decode", calls=lambda d: d.n_layers, call=_decode_call),
}
