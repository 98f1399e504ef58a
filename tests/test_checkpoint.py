"""Checkpoint manager: roundtrip, atomicity, integrity, resume, GC."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step, restore_pytree,
                              save_pytree)


def make_tree(key):
    return {
        "a": jax.random.normal(key, (8, 16)),
        "nested": {"b": jnp.arange(12, dtype=jnp.int32),
                   "c": jnp.float32(3.5)},
    }


def assert_trees_equal(a, b):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    tree = make_tree(jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    save_pytree(tree, d)
    got = restore_pytree(tree, d)
    assert_trees_equal(tree, got)


def test_atomic_no_tmp_left(tmp_path):
    tree = make_tree(jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    save_pytree(tree, d)
    assert not os.path.exists(d + ".tmp")
    assert os.path.exists(os.path.join(d, "manifest.json"))


def test_overwrite_is_atomic(tmp_path):
    t1 = make_tree(jax.random.PRNGKey(0))
    t2 = make_tree(jax.random.PRNGKey(1))
    d = str(tmp_path / "ck")
    save_pytree(t1, d)
    save_pytree(t2, d)
    assert_trees_equal(t2, restore_pytree(t1, d))


def test_corruption_detected(tmp_path):
    tree = make_tree(jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    save_pytree(tree, d)
    with open(os.path.join(d, "manifest.json")) as f:
        first = json.load(f)["leaves"]["a"]["shards"][0]["file"]
    with open(os.path.join(d, first), "r+b") as f:
        f.seek(200)
        f.write(b"\xff\xff\xff")
    with pytest.raises(IOError, match="checksum"):
        restore_pytree(tree, d)


def test_manager_async_save_restore_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    tree = make_tree(jax.random.PRNGKey(0))
    for step in (10, 20, 30):
        t = jax.tree.map(lambda x: x + step, tree)
        mgr.save(step, t)
    mgr.wait()
    assert latest_step(str(tmp_path)) == 30
    got, step = mgr.restore(tree)
    assert step == 30
    assert_trees_equal(got, jax.tree.map(lambda x: x + 30, tree))
    kept = sorted(os.listdir(str(tmp_path)))
    assert kept == ["step_20", "step_30"]       # GC kept last 2


def test_restore_with_shardings_elastic(tmp_path):
    """Restore onto an explicit sharding (single-device 'new mesh')."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    tree = make_tree(jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    save_pytree(tree, d)
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    sh = jax.tree.map(lambda x: NamedSharding(mesh, P()), tree)
    got = restore_pytree(tree, d, shardings=sh)
    assert_trees_equal(tree, got)
    assert all(l.sharding == NamedSharding(mesh, P())
               for l in jax.tree.leaves(got))


def test_train_resume_bit_identical(tmp_path):
    """Crash + restore ⇒ identical continuation (fault-tolerance contract)."""
    from repro.configs import get_reduced
    from repro.train import TrainLoop, TrainSettings, init_state
    from repro.train.step import make_train_step

    cfg = get_reduced("qwen3-4b")
    s = TrainSettings(learning_rate=1e-3)
    key = jax.random.PRNGKey(0)
    state = init_state(key, cfg, s)
    step = jax.jit(make_train_step(cfg, s))

    def batches():
        k = jax.random.PRNGKey(42)
        while True:
            k, sub = jax.random.split(k)
            toks = jax.random.randint(sub, (2, 17), 0, cfg.vocab_size)
            yield {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    mgr = CheckpointManager(str(tmp_path / "run"))
    loop = TrainLoop(step, state, ckpt_manager=mgr, ckpt_every=2)
    with pytest.raises(RuntimeError, match="injected failure"):
        loop.run(batches(), 10, fail_at_step=4)
    mgr.wait()

    # uninterrupted reference: 6 steps straight
    ref_state = init_state(key, cfg, s)
    gen = batches()
    for _ in range(6):
        ref_state, _ = step(ref_state, next(gen))

    # resume from step-4 checkpoint, replay the stream from step 4
    restored, at = mgr.restore(state)
    assert at == 4
    gen2 = batches()
    for _ in range(4):
        next(gen2)                      # data pipeline skips replayed steps
    loop2 = TrainLoop(step, restored)
    final = loop2.run(gen2, 2)
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(final.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
