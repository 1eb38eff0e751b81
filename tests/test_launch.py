"""Launch-layer units: mesh factory, collective-bytes parser, dry-run cell
builders (without the 512-device env), artifact schema."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED
from repro.configs.base import SHAPES, cell_is_runnable, ShapeSpec
from repro.configs.shapes import input_specs


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %all-reduce.1 = f32[16,128]{1,0} all-reduce(%x), replica_groups={}
  %ag = bf16[4,256]{1,0} all-gather(%y), dimensions={0}
  %p = (f32[8]{0}, f32[8]{0}) all-to-all(%a, %b)
  %cp = f32[32]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %ar-start = f32[10]{0} all-reduce-start(%w)
  %other = f32[999]{0} add(%x, %x)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 16 * 128 * 4 + 10 * 4  # includes -start
    assert got["all-gather"] == 4 * 256 * 2
    assert got["all-to-all"] == 2 * 8 * 4
    assert got["collective-permute"] == 32 * 4
    assert got["total"] == sum(v for k, v in got.items() if k != "total")


def test_mesh_factory_shapes():
    # Only shape/axis metadata is checked — this host has 1 device, so the
    # factory itself must be exercised by the dry-run (512 host devices).
    from repro.launch import mesh as mesh_mod

    src = Path(mesh_mod.__file__).read_text()
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '("pod", "data", "model")' in src


def test_dryrun_module_sets_xla_flags_first():
    """The spec mandates XLA_FLAGS before ANY other import in dryrun.py."""
    src = Path(__file__).resolve().parents[1] / "src/repro/launch/dryrun.py"
    text = src.read_text()
    first_import = text.index("import os")
    flags = text.index("xla_force_host_platform_device_count=512")
    other_imports = re.search(r"^import (?!os)\w+", text, re.M).start()
    assert first_import < flags < other_imports


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_input_specs_cover_all_runnable_cells(arch):
    cfg = ASSIGNED[arch]
    for shape in SHAPES.values():
        ok, why = cell_is_runnable(cfg, shape)
        if not ok:
            assert shape.name == "long_500k" and not cfg.supports_long_context
            continue
        cell = input_specs(cfg, shape)
        leaves = jax.tree_util.tree_leaves(cell.batch)
        assert leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        # batch dim is the assigned global batch everywhere it appears
        if "tokens" in cell.batch:
            assert cell.batch["tokens"].shape[0] == shape.global_batch


def test_artifact_schema_if_present():
    art = Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
    files = sorted(art.glob("*__pod16x16.json")) if art.exists() else []
    if not files:
        pytest.skip("no dry-run artifacts in this checkout")
    checked = 0
    for f in files:
        rec = json.loads(f.read_text())
        if not rec.get("runnable", True):
            assert "skip_reason" in rec
            continue
        assert rec.get("ok"), f"{f.name}: recorded failure {rec.get('error')}"
        assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
        assert "total" in rec["collectives"]
        checked += 1
    assert checked >= 30  # 33 runnable single-pod cells


def test_long500k_skips_are_exactly_the_full_attention_archs():
    skipped = {
        a for a, c in ASSIGNED.items()
        if not cell_is_runnable(c, SHAPES["long_500k"])[0]
    }
    assert skipped == {
        "granite-34b", "mistral-nemo-12b", "qwen2-1.5b", "qwen2-0.5b",
        "whisper-tiny", "internvl2-1b", "olmoe-1b-7b",
    }


# --------------------------------------------------------------------------- #
# Serving launcher (repro.launch.serve) and the compile-cache helper
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("reduced", [True, False])
def test_serve_launcher_full_width_setup(reduced):
    """``--no-reduced`` serves the published config itself (its widths and
    dtype) with no separate cost arch; the default computes on the reduced
    config and models economics at the full arch."""
    from repro.configs import get_config, reduced_config
    from repro.launch import serve as launch

    argv = ["--arch", "qwen2-1.5b"] + ([] if reduced else ["--no-reduced"])
    s = launch.setup(launch.parse_args(argv), paged_decode=True)
    full = get_config("qwen2-1.5b")
    assert s.cfg == (reduced_config(full) if reduced else full)
    assert s.engine_cfg.cost_arch == ("qwen2-1.5b" if reduced else None)
    assert s.engine_cfg.paged_decode
    # default max_len: the request's room (96+16+8+32) in whole kv blocks
    assert s.engine_cfg.max_len == 256


def test_serve_launcher_reused_logits_match_recompute():
    """The launcher serves its workload with reuse, and a reused request's
    first-token logits from its stored KV + suffix match a full-recompute
    prefill (f32 reduced config: only summation order differs)."""
    from repro.launch import serve as launch

    args = launch.parse_args([
        "--arch", "qwen2-1.5b", "--requests", "4", "--contexts", "1",
        "--context-len", "64", "--prompt-len", "16", "--output-len", "2",
        "--slots", "2", "--policy", "always",
    ])
    engine, summary = launch.serve(args)
    assert summary.n_requests == 4 and summary.reuse_hits == 3
    req = launch.workload(engine.cfg, args)[-1]
    _, entry = engine.store.lookup(list(req.context_tokens))
    artifact, _ = engine.store.fetch(entry.entry_id)
    ctx, prompt = list(req.context_tokens), list(req.prompt_tokens)
    reuse = np.asarray(engine.prefill_logits(ctx, prompt, artifact))
    full = np.asarray(engine.prefill_logits(ctx, prompt))
    assert reuse.shape == (engine.cfg.vocab,)
    assert np.linalg.norm(reuse - full) <= 1e-5 * np.linalg.norm(full)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed .jax_cache/ at the repository root."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
