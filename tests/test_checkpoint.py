"""Checkpoint subsystem: atomicity, resharding, async, incremental, multilevel."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, CheckpointPolicy,
                              CheckpointStore, IncrementalCheckpointer,
                              MultiLevelCheckpointer)
from repro.utils.trees import tree_allclose


def _state(seed=0, n=1000):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w1": rng.standard_normal((n, 8)).astype(np.float32),
                   "w2": rng.standard_normal((n,)).astype(np.float32)},
        "opt": {"m": {"w1": rng.standard_normal((n, 8)).astype(np.float32),
                      "w2": np.zeros((n,), np.float32)}},
        "step": np.int32(7),
    }


def test_store_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=3)
    s = _state()
    store.save(7, s, timestamp=1.0, extra={"cursor": 42})
    restored, extra = store.restore(s)
    assert tree_allclose(s, restored)
    assert extra["cursor"] == 42


@pytest.mark.parametrize("replicated", [False, True])
def test_store_roundtrip_bf16_leaves_bitwise(tmp_path, replicated):
    """bf16 leaves (bf16 Adam state) restore bit for bit through the plain
    and the peer-replicated store: NumPy writes ml_dtypes to .npz as raw
    void bytes, which restore must reinterpret, not cast."""
    from repro.checkpoint import PeerReplicatedStore
    rng = np.random.default_rng(3)
    s = {"p": rng.standard_normal((64, 8)).astype(np.float32),
         "m": jnp.asarray(rng.standard_normal((64, 8)), jnp.bfloat16),
         "step": np.int32(2)}
    store = (PeerReplicatedStore(str(tmp_path), num_shards=2,
                                 replication_factor=1) if replicated
             else CheckpointStore(str(tmp_path), num_shards=2))
    store.save(2, s)
    restored, _ = store.restore(s)
    assert restored["m"].dtype == jnp.bfloat16
    for name in s:
        a, b = np.asarray(s[name]), np.asarray(restored[name])
        assert a.tobytes() == b.tobytes(), name


def test_store_reshard_restore_across_host_counts(tmp_path):
    """Save with 8 shards, restore through a store configured for 2 —
    manifest-driven restore is shard-count agnostic (elastic rescale)."""
    s = _state(1)
    CheckpointStore(str(tmp_path), num_shards=8).save(3, s)
    restored, _ = CheckpointStore(str(tmp_path), num_shards=2).restore(s)
    assert tree_allclose(s, restored)


def test_store_atomicity_corrupt_shard_falls_back(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=2, keep=5)
    s1, s2 = _state(1), _state(2)
    store.save(1, s1)
    store.save(2, s2)
    # corrupt the newest checkpoint's shard
    p = os.path.join(str(tmp_path), "step_0000000002", "shard_00000.npz")
    with open(p, "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x00\x00\x00")
    assert store.newest() == 1          # checksum mismatch hides step 2
    restored, _ = store.restore(s1)
    assert tree_allclose(s1, restored)


def test_store_missing_manifest_invisible(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=1)
    store.save(5, _state())
    os.remove(os.path.join(str(tmp_path), "step_0000000005", "manifest.json"))
    assert store.newest() is None


def test_store_gc_keeps_newest(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=1, keep=2)
    for step in [1, 2, 3, 4]:
        store.save(step, _state(step))
    assert store.list_steps() == [3, 4]


def test_async_checkpointer_writes_and_skips(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=1)
    ac = AsyncCheckpointer(store, busy_policy="skip")
    s = _state()
    assert ac.save(1, s)
    ac.wait()
    assert store.newest() == 1
    assert not ac.errors


def test_async_snapshot_isolation(tmp_path):
    """Mutating the live state after save() must not affect the snapshot."""
    store = CheckpointStore(str(tmp_path), num_shards=1)
    ac = AsyncCheckpointer(store)
    s = {"w": np.ones(10, np.float32)}
    ac.save(1, s)
    s["w"][:] = 999.0
    ac.wait()
    restored, _ = store.restore({"w": np.zeros(10, np.float32)})
    assert np.allclose(restored["w"], 1.0)


@pytest.mark.parametrize("mode", ["lossless", "int8"])
def test_incremental_roundtrip(tmp_path, mode):
    store = CheckpointStore(str(tmp_path), num_shards=2)
    inc = IncrementalCheckpointer(store, full_every=4, mode=mode)
    s = _state(3)
    inc.save(0, s)
    s2 = jax.tree_util.tree_map(
        lambda x: x + np.float32(0.01) if x.dtype == np.float32 else x, s)
    inc.save(1, s2)
    restored, step = inc.restore(s)
    assert step == 1
    if mode == "lossless":
        assert tree_allclose(s2, restored, rtol=1e-6, atol=1e-6)
    else:
        for a, b in zip(jax.tree_util.tree_leaves(s2),
                        jax.tree_util.tree_leaves(restored)):
            if a.dtype == np.float32:
                assert np.max(np.abs(a - b)) < 1e-3


def test_incremental_delta_smaller_than_full(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=1)
    inc = IncrementalCheckpointer(store, full_every=4, mode="lossless")
    s = _state(4, n=20_000)
    inc.save(0, s)
    s2 = jax.tree_util.tree_map(
        lambda x: x + np.float32(1e-4) if x.dtype == np.float32 else x, s)
    inc.save(1, s2)
    assert inc.bytes_written_delta < 0.5 * inc.bytes_written_full


def test_multilevel_coverage(tmp_path):
    ml = MultiLevelCheckpointer(
        local_store=CheckpointStore(str(tmp_path / "local"), num_shards=1),
        remote_store=CheckpointStore(str(tmp_path / "remote"), num_shards=1),
        local_every=2, remote_every=4)
    s = _state(5)
    for i in range(5):
        si = jax.tree_util.tree_map(
            lambda x: x + np.float32(i) if x.dtype == np.float32 else x, s)
        ml.save(i, si)
    # task failure: memory level has the newest (step 4)
    _, step, level = ml.restore(s, "task")
    assert (step, level) == (4, "memory")
    # node failure: memory lost, local has step 4 (saved at i=4, 4%2==0)
    ml.on_node_failure()
    _, step, level = ml.restore(s, "node")
    assert level == "local" and step == 4
    # cluster failure: only remote survives (step 4: 4%4==0)
    _, step, level = ml.restore(s, "cluster")
    assert level == "remote" and step == 4


def test_policy_hot_swap():
    p = CheckpointPolicy(60.0)
    p.reset(0.0)
    assert not p.due(30.0)
    assert p.due(61.0)
    p.set_interval(10.0, t=61.0)
    p.mark(61.0)
    assert p.due(71.5)
    assert p.history[-1] == (61.0, 10.0)
