"""Checkpointing: atomic roundtrip, auto-resume, preemption survival with
bit-exact continuation, elastic reshape."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.data.synthetic import token_batches
from repro.models import registry
from repro.training import checkpoint as ckpt
from repro.training.fault import LoopConfig, Preempted, ResilientLoop
from repro.training.optimizer import AdamW
from repro.training.train_step import make_train_step


def _tiny():
    cfg = reduced_config(get_config("qwen2-0.5b"), n_layers=2, vocab=64)
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    opt = AdamW(lr=1e-3)
    return cfg, params, opt


def test_roundtrip_and_latest(tmp_path):
    _, params, opt = _tiny()
    tree = (params, opt.init(params))
    ckpt.save(tmp_path, 7, tree)
    ckpt.save(tmp_path, 13, tree)
    assert ckpt.latest_step(tmp_path) == 13
    restored, step = ckpt.restore(tmp_path, tree)
    assert step == 13
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention_gc(tmp_path):
    _, params, opt = _tiny()
    for s in range(6):
        ckpt.save(tmp_path, s, params, keep=2)
    steps = sorted(p.name for p in Path(tmp_path).glob("step_*"))
    assert len(steps) == 2 and steps[-1] == "step_00000005"


def test_incomplete_checkpoint_ignored(tmp_path):
    _, params, opt = _tiny()
    ckpt.save(tmp_path, 3, params)
    # simulate a crash mid-write at a later step: directory without manifest
    (tmp_path / "step_00000009").mkdir()
    assert ckpt.latest_step(tmp_path) == 3
    _, step = ckpt.restore(tmp_path, params)
    assert step == 3


def test_shape_mismatch_rejected(tmp_path):
    _, params, opt = _tiny()
    ckpt.save(tmp_path, 1, params)
    bad = jax.tree_util.tree_map(lambda x: np.zeros(x.shape + (2,), x.dtype), params)
    with pytest.raises(AssertionError):
        ckpt.restore(tmp_path, bad)


def test_preemption_resume_bit_exact(tmp_path):
    """Kill training mid-run; re-invoking the loop restores and the final
    params are IDENTICAL to an uninterrupted run (same data order)."""
    cfg, params0, opt = _tiny()
    step_fn = jax.jit(make_train_step(cfg, opt))
    batches = [
        {k: jnp.asarray(v) for k, v in b.items()}
        for _, b in zip(range(12), token_batches(cfg, batch=4, seq_len=16, seed=1))
    ]
    batch_fn = lambda i: batches[i]

    # uninterrupted reference
    ref_dir = tmp_path / "ref"
    loop = ResilientLoop(step_fn, batch_fn,
                         LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=str(ref_dir)))
    ref = loop.run(params0, opt.init(params0))

    # preempted run: dies at step 6 (after the step-4 checkpoint)
    pre_dir = tmp_path / "pre"

    def bomb(step):
        if step == 6:  # after the async step-4 checkpoint was initiated
            raise Preempted("simulated preemption")

    loop1 = ResilientLoop(step_fn, batch_fn,
                          LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=str(pre_dir)),
                          failure_hook=bomb)
    with pytest.raises(Preempted):
        loop1.run(params0, opt.init(params0))
    loop1.ckpt.wait()
    assert ckpt.latest_step(pre_dir) == 4

    # plain re-invocation resumes from step 4 and finishes
    loop2 = ResilientLoop(step_fn, batch_fn,
                          LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=str(pre_dir)))
    out = loop2.run(params0, opt.init(params0))
    for a, b in zip(
        jax.tree_util.tree_leaves(ref["params"]),
        jax.tree_util.tree_leaves(out["params"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_reshape_restore(tmp_path):
    """Checkpoints are mesh-agnostic: save under one (data, model) layout,
    restore under another and shard explicitly — values identical."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    _, params, _ = _tiny()
    ckpt.save(tmp_path, 1, params)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    restored, _ = ckpt.restore(tmp_path, params)
    sharded = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), restored
    )
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
