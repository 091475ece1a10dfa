"""Fault tolerance: checkpoint round-trip, preemption + bit-exact resume,
elastic re-shard across mesh changes, atomic manifest commit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import object_store_ckpt as ckpt
from repro.configs.registry import ARCHS
from repro.core.storage_service import ObjectStore
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_mesh
from repro.train.trainer import Preempted, Trainer, TrainerConfig


@pytest.fixture
def small_cfg():
    return dataclasses.replace(ARCHS["internlm2-1.8b"].reduced(),
                               microbatches=2)


def _mesh(data=1, model=1):
    return make_mesh(data, model)


def test_checkpoint_roundtrip():
    store = ObjectStore()
    tree = {"a": jnp.arange(100, dtype=jnp.float32).reshape(10, 10),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    ckpt.save_checkpoint(store, "t", 7, tree)
    restored, step = ckpt.restore_checkpoint(store, "t", tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_chunking_respects_beas():
    store = ObjectStore()
    big = {"w": jnp.zeros((1024, 1024), jnp.float32)}   # 4 MiB
    ckpt.save_checkpoint(store, "big", 1, big)
    chunk_keys = [k for k in store.list("big/") if "chunk" in k]
    sizes = [store.size(k) for k in chunk_keys]
    # every chunk except the last is >= the minimum economical object size
    assert all(s >= 1024 ** 2 for s in sizes[:-1])


def test_manifest_is_commit_point():
    store = ObjectStore()
    tree = {"a": jnp.ones((4,), jnp.float32)}
    ckpt.save_checkpoint(store, "c", 1, tree)
    # simulate a crash mid-write of step 2: leaves written, no manifest
    store.put("c/step-00000002/a/chunk-0000", b"\x00" * 16)
    assert ckpt.latest_step(store, "c") == 1


def test_checkpoint_gc_keeps_latest():
    store = ObjectStore()
    tree = {"a": jnp.ones((4,), jnp.float32)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save_checkpoint(store, "g", s, tree, keep=2)
    assert ckpt.latest_step(store, "g") == 5
    assert not [k for k in store.list("g/step-00000001/")]
    restored, _ = ckpt.restore_checkpoint(store, "g", tree, step=5)


def test_preemption_and_bitexact_resume(small_cfg):
    """Kill training at step 7; a fresh Trainer resumes from step 5's
    manifest and reaches the same final loss as an uninterrupted run."""
    mesh = _mesh()
    data_cfg = DataConfig(seq_len=16, global_batch=4, seed=1)
    tcfg = TrainerConfig(total_steps=10, checkpoint_every=5, log_every=1)

    # Uninterrupted run.
    t_ref = Trainer(small_cfg, mesh, ObjectStore(), data_cfg, tcfg=tcfg)
    ref = t_ref.run()
    assert ref["status"] == "done"

    # Preempted at step 7, then resumed.
    store = ObjectStore()

    def bomb(step):
        if step == 7:
            raise Preempted()

    t1 = Trainer(small_cfg, mesh, store, data_cfg, tcfg=tcfg,
                 preemption_hook=bomb)
    out1 = t1.run()
    assert out1["status"] == "preempted"
    assert out1["resumable_from"] == 5

    t2 = Trainer(small_cfg, mesh, store, data_cfg, tcfg=tcfg)
    out2 = t2.run()
    assert out2["status"] == "done"
    assert out2["metrics"][-1]["loss"] == pytest.approx(
        ref["metrics"][-1]["loss"], rel=1e-5)


def test_elastic_reshard_restore(small_cfg):
    """Save under mesh (1,1), restore under (2,1) [mesh topology change] —
    the paper's elasticity applied to training state."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    store = ObjectStore()
    data_cfg = DataConfig(seq_len=16, global_batch=4, seed=1)
    t1 = Trainer(small_cfg, _mesh(1, 1), store, data_cfg,
                 tcfg=TrainerConfig(total_steps=5, checkpoint_every=5))
    t1.run()
    t2 = Trainer(small_cfg, _mesh(2, 1), store, data_cfg,
                 tcfg=TrainerConfig(total_steps=10, checkpoint_every=5))
    out = t2.run()
    assert out["status"] == "done"


def test_cost_report(small_cfg):
    store = ObjectStore()
    t = Trainer(small_cfg, _mesh(), store,
                DataConfig(seq_len=16, global_batch=4),
                tcfg=TrainerConfig(total_steps=2, checkpoint_every=2))
    out = t.run()
    cost = out["cost"]
    assert cost["elastic_usd"] > 0
    assert 0 < cost["utilization_breakeven"] < 1
    assert cost["storage"]["writes"] > 0
