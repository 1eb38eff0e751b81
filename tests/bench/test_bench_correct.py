"""What decides ``correct``, on a tiny dense GQA decoder on the CPU: a sound
run passes, a run whose served tokens are altered where they are produced
fails, the float8 control fails the limit, and the plain reference agrees
with the program's own float32 forward."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness, runner
from bench import traffic as T
from bench import weights as W

DATA = harness.REPO / "tests" / "bench" / "data"
CELL = {"name": "tiny.docqa", "config": "tiny", "traffic": "docqa_tiny", "chips": 1}
# every document seen once: the fill stores nothing, every request is fresh
FRESH = {"name": "tiny.docqa_fresh", "config": "tiny", "traffic": "docqa_tiny_fresh", "chips": 1}
BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
CONF = harness.load_config("tiny", DATA / "configs")
MODEL = harness.model_of(CONF)
DIMS = MODEL.sizes(CONF["config"])
LIMIT = correct.limits(CELL["name"], DATA / "limits")["max_logit_gap"]


def _run(seed, hook=None, cell=CELL):
    metrics = [dict(m, workloads=[cell["name"]]) for m in BENCH["end_to_end"]]
    return runner.run_cell(BENCH, cell, seed=seed, seconds=2.0, device=jax.devices()[0],
                           n_devices=1, metrics=metrics, configs_dir=DATA / "configs",
                           traffic_dir=DATA / "traffic", limits_dir=DATA / "limits",
                           engine_hook=hook)


def _second_best(engine):
    """Every decode step serves the runner-up token instead of the best."""
    orig = engine._jit_decode

    def altered(params, toks, state, active):
        logits, state = orig(params, toks, state, active)
        top = jnp.argmax(logits, axis=-1)
        return logits.at[jnp.arange(logits.shape[0]), top].set(-jnp.inf), state

    engine._jit_decode = altered


def test_sound_run_is_correct_and_reports_every_metric(capsys):
    result, checked = _run(2**33 + 3)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert list(result)[-1] == "checked"
    assert checked["max_logit_gap"]["value"] <= LIMIT
    out = capsys.readouterr().out
    assert '"line": "compilations_in_window"' in out and '"line": "generator"' in out
    lines = {d["line"]: d for d in map(json.loads, out.splitlines()) if "line" in d}
    assert lines["requests"]["lead_in"] > 0 and lines["compilations_in_window"]["compiles"] == 0
    assert lines["backlog"]["owed_at_close"] >= 0 and lines["latency"]["ttft_p50_s"] > 0


def test_altered_token_is_not_correct():
    result, checked = _run(2**33 + 3, hook=_second_best)
    assert result["correct"] is False
    assert checked["max_logit_gap"]["value"] > LIMIT


def test_fresh_documents_bypass_the_store_and_compile_nothing_in_the_window(capsys):
    """Every document asked once: the fill stores none, every admission is
    fresh (matched 0) and its shape was warmed, and the run is correct."""
    result, checked = _run(2**33 + 7, cell=FRESH)
    assert result["correct"] is True and result["failed"] == 0
    lines = {d["line"]: d for d in map(json.loads, capsys.readouterr().out.splitlines())
             if "line" in d}
    assert lines["setup"]["fill_s"] < 0.01
    for phase in ("compilations_in_window", "compilations_in_lead_in"):
        assert (lines[phase]["compiles"], lines[phase]["cache_loads"]) == (0, 0)
    assert lines["store"]["reused_context_share"] == 0.0
    assert lines["store"]["written_back"] > 0  # the tiny configuration's planner always stores


def test_fresh_altered_token_is_not_correct():
    result, checked = _run(2**33 + 7, hook=_second_best, cell=FRESH)
    assert result["correct"] is False
    assert checked["max_logit_gap"]["value"] > LIMIT


@pytest.fixture(scope="module")
def served():
    engine, _ = harness.build(CONF, 11, jax.devices()[0])
    spec = T.load("docqa_tiny", "tiny", DATA / "traffic")
    tr = T.generate(spec, 11, 2.0, DIMS.vocab)
    harness.warm(engine, tr, harness.fill(engine, tr))
    window = harness.serve(engine, tr, 2.0)
    return correct.sample(runner.served(window, tr), 11)


def test_float8_control_fails_the_limit(served):
    read = correct.readings(MODEL, W.make(MODEL, DIMS, 11), DIMS, served, control=True)
    assert read["served_tokens"] >= 50
    assert read["max_logit_gap"] <= LIMIT
    assert read["control_max_logit_gap"] > LIMIT
    assert read["control_max_logit_gap"] >= 3 * read["max_logit_gap"]
    assert read["runner_up_max_logit_gap"] > LIMIT


def test_runner_up_gap_is_the_top_two_margin():
    ref = np.array([[0.0, 3.0, 2.5, -1.0], [4.0, 1.0, 0.0, 3.9]])
    np.testing.assert_allclose(correct.runner_up_gaps(ref), [0.5, 0.1])
    np.testing.assert_allclose(correct.served_gaps(ref, [2, 3]), [0.5, 0.1])


def test_fill_stores_only_documents_asked_more_than_once():
    engine, dims = harness.build(CONF, 13, jax.devices()[0])
    tr = T.generate(T.load("docqa_tiny_fresh", "tiny", DATA / "traffic"), 13, 2.0, dims.vocab)
    assert harness.fill(engine, tr) == [0] * len(tr.docs)
    assert not engine.store.entries
    assert harness.programs(engine) == {
        "admit": harness.Program("jit__packed_prefill_impl", per_step=True),
        "decode": harness.Program("jit__decode_impl", per_step=True)}
    sets = harness.admission_sets(engine, tr, [0] * len(tr.docs))
    assert sets and all(m == 0 for run in sets for m, _ in run)


def test_an_engine_without_its_admission_calls_is_not_warmed():
    class Bare:
        _state = None
    with pytest.raises(RuntimeError, match="cannot be warmed"):
        harness.warm(Bare(), None, [])


def test_a_compile_inside_the_window_fails_the_run():
    def compiles_in_decode(engine):
        orig = engine._jit_decode

        def fresh(params, toks, state, active):
            jax.jit(lambda x: x + len(engine.slots) + fresh.n)(jnp.zeros(())).block_until_ready()
            fresh.n += 1
            return orig(params, toks, state, active)
        fresh.n = 0
        engine._jit_decode = fresh

    with pytest.raises(runner.CompiledInWindow):
        _run(2**33 + 5, hook=compiles_in_decode)


def test_sample_holds_the_longest_and_enough_tokens():
    fin = [correct.Served(req=i, prompt=[0] * (10 + i), tokens=[1] * 40) for i in range(20)]
    s = correct.sample(fin, 5, min_served=200)
    assert s[0].req == 19 and sum(len(x.tokens) for x in s) >= 200 and len(s) == 5
    assert [x.req for x in s] == [x.req for x in correct.sample(fin, 5, min_served=200)]


def test_reference_matches_the_program_forward_in_float32():
    """The reference is written apart from the program; at float32 and the
    highest matmul precision both compute the same model."""
    from repro.kernels import ops
    from repro.models import common, registry

    cfg = harness.program_config(CONF)
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    wts = W.make(MODEL, DIMS, 4)
    tokens = np.random.default_rng(0).integers(0, DIMS.vocab, 300).tolist()
    rows = list(range(0, 300, 37))
    ours = MODEL.logits(wts, DIMS, tokens, rows)
    ops.set_kernel_mode("ref")
    try:
        with jax.default_matmul_precision("highest"):
            theirs, _ = registry.get_model(cfg32).forward(
                common.cast_tree(wts, jnp.float32), cfg32, jnp.asarray([tokens], jnp.int32))
    finally:
        ops.set_kernel_mode(None)
    np.testing.assert_allclose(ours, np.asarray(theirs[0])[rows], rtol=2e-4, atol=2e-4)
