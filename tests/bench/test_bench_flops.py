"""The benchmark's operation and byte counts against the cost model's
(``PerfModel.prefill_flops``, ``decode_bytes_per_token``) at the same shapes,
with each convention that differs written out."""
import json

import pytest

from bench import flops, harness

M = harness.model("dense_gqa")
Dims = M.Dims

CONFIGS = ["qwen2-1.5b", "mistral-nemo-12b-8l"]


def _both(name):
    from repro.core.perf_model import PerfModel, tpu_v5e

    conf = harness.load_config(name)
    return M.sizes(conf["config"]), harness.program_config(conf), PerfModel(tpu_v5e(1))


def _non_matmul_params(d: Dims) -> int:
    """Parameters the cost model counts as 2 FLOPs per token that are no
    matmul: norm scales, q/k/v biases and, where untied, the embedding table
    (a lookup; its head is the matmul)."""
    n = d.n_layers * 2 * d.d_model + d.d_model
    if d.qkv_bias:
        n += d.n_layers * d.head_dim * (d.n_heads + 2 * d.n_kv_heads)
    if not d.tied:
        n += d.vocab * d.d_model
    return n


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("n", [1, 128, 4096, 16896])
def test_prefill_flops_match_cost_model(name, n):
    d, cfg, pm = _both(name)
    ours = M.prefill_flops_all_logits(d, n) + 2.0 * _non_matmul_params(d) * n
    assert ours == pytest.approx(pm.prefill_flops(cfg, n), rel=1e-12)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("ctx", [1, 1000, 17280])
def test_decode_bytes_match_cost_model(name, ctx):
    d, cfg, pm = _both(name)
    untied_table = 0 if d.tied else flops.BYTES * d.vocab * d.d_model
    assert M.decode_bytes_per_token(d, ctx) + untied_table == pm.decode_bytes_per_token(cfg, ctx)


def test_segment_flops_count_the_causal_pairs():
    d, _, _ = _both("qwen2-1.5b")
    # one token behind 10 reused positions attends 11; a 3-token prefill attends 1+2+3
    assert flops.causal_pairs(10, 1) == 11 and flops.causal_pairs(0, 3) == 6
    full = M.prefill_segment_flops(d, 0, 4096)
    split = M.prefill_segment_flops(d, 4000, 96)
    layer = 2 * d.n_layers * M.layer_matmul_params(d)
    assert full - split == layer * 4000 + d.n_layers * M.attn_pair_flops(d) * (
        flops.causal_pairs(0, 4096) - flops.causal_pairs(4000, 96))
    # a decoded token is a one-token prefill behind the rest
    assert M.decode_token_flops(d, 501) == M.prefill_segment_flops(d, 500, 1)


def test_kernel_calls_and_roofline():
    d, _, _ = _both("qwen2-1.5b")
    peak = flops.peaks("TPU v5 lite")
    f, b = M.decode_attention_call(d, [1000, 2000])
    assert f == M.attn_pair_flops(d) * 3000
    assert b == 2 * (2 * 3000 * d.n_kv_heads * d.head_dim + 2 * 2 * d.n_heads * d.head_dim)
    assert flops.least_time(f, b, peak)[1] == "memory"
    f, b = M.packed_prefill_call(d, [(0, 8192)])
    assert flops.least_time(f, b, peak)[1] == "compute"


def test_peaks_table_refuses_unknown_devices():
    table = json.loads(flops.PEAKS.read_text())
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in table["source"]
    with pytest.raises(KeyError):
        flops.peaks("cpu")
