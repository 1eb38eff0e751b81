"""The seam between the harness and an architecture: the model modules
(``bench/models/<m>.py``) and the admission program the engine uses.

- A second model module, in a directory of its own that nothing under
  ``bench/`` names, is built, warmed, served, judged and reduced by the
  harness as it stands.
- ``dense_gqa`` computes what the benchmark computed before the move: the
  same weights, reference logits and counts, bit for bit.
- On an engine that admits one request at a time (an SSM model, which the
  packed path cannot carry), the warm-up covers every admission shape, so
  nothing compiles in the window, and each admission step pairs with the
  executions of the per-request prefill program.
"""
import dataclasses
import importlib.util
import json
import shutil
import sys
import time

import jax
import numpy as np
import pytest

from bench import correct, harness, match, reference, runner, trace
from bench import traffic as T
from bench import weights as W

DATA = harness.REPO / "tests" / "bench" / "data"
BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
SEED = 2**33 + 21


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def fake_trace(window, programs, kernels, launches=lambda step: 1):
    """A trace reduction of ``window``: each traced step's host span, and
    inside it ``launches(step)`` executions of its kind's program, each
    holding one op of every kernel its kind runs."""
    host, modules, ops = [], [], []
    for s in window.steps:
        if not s.in_window:
            continue
        a, b = int(s.t0 * 1e9), int(s.t1 * 1e9)
        host.append(trace.Event("bench.step", a, b))
        if s.kind not in programs:
            continue
        n = launches(s)
        width = (b - a) // n
        for k in range(n):
            lo = a + k * width
            modules.append(trace.Event(f"{programs[s.kind].name}(1234)", lo, lo + width - 1))
            for j, (name, kern) in enumerate(kernels.items()):
                if kern.step == s.kind:
                    ops.append(trace.Event(f"%{name}.{j} = custom-call()", lo + 1, lo + width // 2))
    busy = trace.union([(e.start, e.end) for e in modules + ops], host[0].start, host[-1].end)
    return trace.Reduction(lo=host[0].start, hi=host[-1].end, modules=modules, ops=ops,
                           busy=busy, host=host,
                           step_kinds=[s.kind for s in window.steps if s.in_window])


# --------------------------------------------------------------------------- #
# a second model module, from a directory of its own
# --------------------------------------------------------------------------- #
GELU = {
    "name": "gelu", "source": "the GELU MLP of the program's granite-34b, reduced",
    "model": "dense_gelu",
    "config": {"hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
               "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
               "rope_theta": 10000.0, "rms_norm_eps": 1e-05},
    "reduced": [],
    "program": {"arch": "granite-34b", "replace": {
        "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 1, "d_ff": 256,
        "vocab": 512, "head_dim": 32}},
    "engine": {"platform": "tpu", "policy": "always", "slots": 4,
               "overrides": {"max_len": 384, "admit_batch": 3}},
}


@pytest.fixture(scope="module")
def gelu(tmp_path_factory):
    """A checkout-like directory with the second model's module, its
    configuration file, the tiny traffic and its cell's limit."""
    d = tmp_path_factory.mktemp("second_model")
    for sub in ("models", "configs", "limits"):
        (d / sub).mkdir()
    shutil.copy(DATA / "models" / "dense_gelu.py", d / "models" / "dense_gelu.py")
    (d / "configs" / "gelu.json").write_text(json.dumps(GELU))
    (d / "limits" / "gelu.docqa.json").write_text(json.dumps({"limits": {"max_logit_gap": 0.05}}))
    return d


def test_a_second_model_is_built_served_and_judged(gelu):
    conf = harness.load_config("gelu", gelu / "configs")
    cfg = harness.program_config(conf, gelu / "models")
    assert cfg.mlp_type == "gelu" and cfg.n_kv_heads == 1
    model = harness.model_of(conf, gelu / "models")
    d = model.sizes(conf["config"])
    harness.check_tree(cfg, W.make(model, d, 3))
    cell = {"name": "gelu.docqa", "config": "gelu", "traffic": "docqa_tiny", "chips": 1}
    metrics = [dict(m, workloads=[cell["name"]]) for m in BENCH["end_to_end"]]
    result, checked = runner.run_cell(
        BENCH, cell, seed=SEED, seconds=2.0, device=jax.devices()[0], n_devices=1,
        metrics=metrics, configs_dir=gelu / "configs", traffic_dir=DATA / "traffic",
        limits_dir=gelu / "limits", models_dir=gelu / "models")
    assert result["correct"] is True and result["failed"] == 0
    assert checked["max_logit_gap"]["value"] <= 0.05
    assert set(result["metrics"]) == {"token_gap_p50_ms", "setup_s"}


def test_a_second_models_reference_is_the_programs_forward(gelu):
    """Its plain reference, through the shared precision and padding, and
    the program's float32 forward compute the same model."""
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import common, registry

    conf = harness.load_config("gelu", gelu / "configs")
    model = harness.model_of(conf, gelu / "models")
    d = model.sizes(conf["config"])
    cfg = harness.program_config(conf, gelu / "models")
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    wts = W.make(model, d, 4)
    tokens = np.random.default_rng(1).integers(0, d.vocab, 300).tolist()
    rows = list(range(0, 300, 37))
    ours = model.logits(wts, d, tokens, rows)
    ops.set_kernel_mode("ref")
    try:
        with jax.default_matmul_precision("highest"):
            theirs, _ = registry.get_model(cfg32).forward(
                common.cast_tree(wts, jnp.float32), cfg32, jnp.asarray([tokens], jnp.int32))
    finally:
        ops.set_kernel_mode(None)
    np.testing.assert_allclose(ours, np.asarray(theirs[0])[rows], rtol=2e-4, atol=2e-4)
    # the control and the readings go through the module too
    fin = [correct.Served(req=0, prompt=tokens[:200], tokens=tokens[200:260])]
    read = correct.readings(model, wts, d, fin, control=True)
    assert read["served_tokens"] == 60 and read["control_max_logit_gap"] >= 0


def test_a_second_model_is_reduced(gelu):
    """Its counts feed the trace readers: every share of a peak reads, and
    the roofline bounds name its kernels."""
    conf = harness.load_config("gelu", gelu / "configs")
    model = harness.model_of(conf, gelu / "models")
    engine, d = harness.build(conf, 5, jax.devices()[0], models_dir=gelu / "models")
    tr = T.generate(T.load("docqa_tiny", "gelu", DATA / "traffic"), 5, 2.0, d.vocab)
    harness.warm(engine, tr, harness.fill(engine, tr))
    window = harness.serve(engine, tr, 2.0)
    progs = harness.programs(engine)
    view = runner.RunView(window=window, dims=d, peak={"bf16_flops_per_s": 1e9,
                                                      "hbm_bytes_per_s": 1e8},
                          setup_s=1.0, trace=fake_trace(window, progs, model.KERNELS),
                          model=model, programs=progs)
    for name in ("prefill_mfu", "decode_mfu", "packed_prefill_roofline",
                 "decode_attention_roofline"):
        v = runner.reader(name)(view)
        assert v is not None and v > 0, name
    bounds = runner.roofline_bounds(view)
    assert set(bounds) == set(model.KERNELS)
    assert sum(bounds["decode_attention"].values()) == \
        sum(s.in_window and s.kind == "decode" for s in window.steps)


# --------------------------------------------------------------------------- #
# dense_gqa against the code before the move
# --------------------------------------------------------------------------- #
PRE = {name: _load(DATA / "premove" / f"{name}.py", f"premove_{name}")
       for name in ("flops", "weights", "reference")}
DENSE = harness.model("dense_gqa")


def test_dense_gqa_weights_and_logits_are_the_premove_ones():
    conf = harness.load_config("tiny", DATA / "configs")
    d = DENSE.sizes(conf["config"])
    assert DENSE.shapes(d) == PRE["weights"].shapes(d)
    new, old = W.make(DENSE, d, SEED), PRE["weights"].make(d, SEED)
    same = jax.tree_util.tree_map(lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes(),
                                  new, old)
    assert all(jax.tree_util.tree_leaves(same))
    tokens = np.random.default_rng(2).integers(0, d.vocab, 600).tolist()
    rows = reference.served_rows(550, 50)
    for fmt in (None, reference.FP8):
        assert np.array_equal(DENSE.logits(new, d, tokens, rows, fmt),
                              PRE["reference"].logits(old, d, tokens, rows, fmt))


@pytest.mark.parametrize("name", ["qwen2-1.5b", "mistral-nemo-12b-8l"])
def test_dense_gqa_counts_are_the_premove_ones(name):
    c = harness.load_config(name)["config"]
    d, f = DENSE.sizes(c), PRE["flops"]
    assert d == DENSE.Dims(**vars(f.Dims.from_config(c)))
    for fn in ("layer_matmul_params", "head_params", "weight_bytes", "kv_bytes_per_token",
               "attn_pair_flops"):
        assert getattr(DENSE, fn)(d) == getattr(f, fn)(d), fn
    for m, n in ((0, 1), (4000, 96), (0, 16896), (17000, 1)):
        assert DENSE.prefill_segment_flops(d, m, n) == f.prefill_segment_flops(d, m, n)
        assert DENSE.decode_token_flops(d, m + n) == f.decode_token_flops(d, m + n)
        assert DENSE.prefill_flops_all_logits(d, n) == f.prefill_flops_all_logits(d, n)
        assert DENSE.decode_bytes_per_token(d, m) == f.decode_bytes_per_token(d, m)
    segs, lives = [(4000, 96), (0, 5000), (123, 456)], [1000, 2000, 17280]
    packed, decode = DENSE.KERNELS["packed_flash_attention"], DENSE.KERNELS["decode_attention"]
    assert (packed.step, decode.step) == ("admit", "decode")
    assert packed.calls(d) == decode.calls(d) == d.n_layers
    assert packed.call(d, segs) == f.packed_prefill_call(d, segs)
    assert decode.call(d, lives) == f.decode_attention_call(d, lives)


# --------------------------------------------------------------------------- #
# an engine that admits one request at a time
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def single():
    """A reduced mamba2-1.3b engine (SSM state: not packable), filled,
    warmed and served on the tiny traffic, with the compilations logged."""
    from repro.launch import serve as launch
    from repro.models import registry
    from repro.serving import ServingEngine

    s = launch.setup(launch.parse_args(["--arch", "mamba2-1.3b", "--platform", "tpu",
                                        "--slots", "4", "--policy", "always"]),
                     max_len=384, admit_batch=3)
    dev = jax.devices()[0]
    weights = jax.device_put(registry.get_model(s.cfg).init(jax.random.PRNGKey(0), s.cfg), dev)
    engine = ServingEngine(s.cfg, weights, engine_cfg=s.engine_cfg, planner=s.planner_factory(),
                           pricing=s.pricing, perf=s.perf, device=dev)
    tr = T.generate(T.load("docqa_tiny", "tiny", DATA / "traffic"), 9, 2.0, s.cfg.vocab)
    clog = runner.CompileLog()
    matched = harness.fill(engine, tr)
    warmed = harness.warm(engine, tr, matched)
    t_serve = time.perf_counter()
    window = harness.serve(engine, tr, 2.0)
    return engine, window, warmed, matched, clog, t_serve


def test_per_request_warm_up_covers_the_window(single):
    engine, window, warmed, matched, clog, t_serve = single
    assert not engine._packable and all(m > 0 for m in matched)
    assert warmed["lengths"] > 0 and warmed["inserted"] > 0
    assert clog.between(t_serve, window.end) == {"compiles": 0, "cache_loads": 0}
    admits = [s for s in window.steps if s.kind == "admit"]
    assert admits and all(len(s.batch) == 1 for s in admits)
    assert sum(len(s.batch) for s in admits) == len(window.served)


def test_per_request_admissions_pair_with_their_program(single):
    engine, window, *_ = single
    progs = harness.programs(engine)
    assert progs["admit"] == harness.Program("jit__prefill_impl", per_step=False)
    assert progs["decode"] == harness.Program("jit__decode_impl", per_step=True)
    admits = [s for s in window.steps if s.in_window and s.kind == "admit"]
    twice = {id(admits[0])}  # a launch for the context, one for the question
    red = fake_trace(window, progs, {}, launches=lambda s: 2 if id(s) in twice else 1)
    view = runner.RunView(window=window, dims=None, peak=None, setup_s=0.0, trace=red,
                          programs=progs)
    got = match.pairs(view, "admit")
    assert [s for s, _ in got] == admits
    assert [len(ex) for _, ex in got] == [2] + [1] * (len(admits) - 1)
    # an execution outside every admission step cannot be paired
    stray = trace.Event(f"{progs['admit'].name}(1)", red.hi + 10**7, red.hi + 2 * 10**7)
    red.modules.append(stray)
    red.hi = stray.end + 1
    with pytest.raises(match.Unpaired):
        match.pairs(view, "admit")
