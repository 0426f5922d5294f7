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


def _manager(path, levels=("local",)):
    from repro.checkpoint import CheckpointManager, CheckpointPlan
    return CheckpointManager(str(path), CheckpointPlan(
        mode="full", levels=levels, num_shards=2, keep=5, remote_every=1,
        replication_factor=0))


def _shards(directory, step):
    d = os.path.join(str(directory), f"step_{step:010d}")
    return sorted(os.path.join(d, f) for f in os.listdir(d)
                  if f.startswith("shard_"))


def _below(spans, root):
    """``root``'s spans and every span below it."""
    ids, out = {root.id}, [root]
    for s in spans:                 # oldest first: parents open first
        if s.parent in ids:
            ids.add(s.id)
            out.append(s)
    return out


def _corrupt_byte(path):
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))


def test_restore_reads_each_shard_byte_once(tmp_path):
    import time
    from repro.utils.timing import spans
    mgr = _manager(tmp_path)
    mgr.save(1, _state(1))
    mgr.save(2, _state(2))
    t0 = time.perf_counter()
    rep = mgr.restore(_state(), "task")
    got, dropped = spans(t0, time.perf_counter())
    assert dropped == 0 and rep.step == 2 and rep.fallbacks == 0
    [root] = [s for s in got if s.name == "khaos.ckpt.restore"]
    below = _below(got, root)
    read = sum(s.counts.get("bytes_read", 0) for s in below)
    assert read == sum(os.path.getsize(p)
                       for p in _shards(tmp_path / "local", 2))
    # the CRC runs over the bytes already read and counts none again
    assert {s.name for s in below if s.counts.get("bytes_read")} == {
        "khaos.ckpt.read"}
    assert root.counts["fallbacks"] == 0
    assert tree_allclose(rep.state, _state(2))


@pytest.mark.parametrize("fault", ["corrupt_byte", "deleted_shard"])
def test_manager_restore_falls_back_past_a_faulty_newest_full(tmp_path,
                                                              fault):
    import time
    from repro.utils.timing import spans
    mgr = _manager(tmp_path)
    mgr.save(1, _state(1), extra={"i": 1})
    mgr.save(2, _state(2), extra={"i": 2})
    shard = _shards(tmp_path / "local", 2)[1]
    if fault == "corrupt_byte":
        _corrupt_byte(shard)
    else:
        os.remove(shard)
    t0 = time.perf_counter()
    rep = mgr.restore(_state(), "task")
    got, _ = spans(t0, time.perf_counter())
    assert (rep.step, rep.level, rep.kind) == (1, "local", "full")
    assert rep.extra["i"] == 1 and rep.fallbacks == 1
    assert tree_allclose(rep.state, _state(1))
    [root] = [s for s in got if s.name == "khaos.ckpt.restore"]
    assert root.counts["fallbacks"] == 1 and root.step == 1
    # what the validated listing names is what the restore chose
    assert mgr.restore_candidates()[0] == (1, "local")


def test_restore_of_a_corrupt_step_by_number_raises(tmp_path):
    store = CheckpointStore(str(tmp_path), num_shards=2, keep=5)
    store.save(1, _state(1))
    store.save(2, _state(2))
    _corrupt_byte(_shards(tmp_path, 2)[0])
    with pytest.raises(FileNotFoundError, match="corrupt or missing"):
        store.restore(_state(), step=2)
    restored, _ = store.restore(_state(), step=1)
    assert tree_allclose(restored, _state(1))


def test_a_rejected_full_hands_the_restore_to_the_next_level(tmp_path):
    """The choice made again after a failed load is the one the validated
    listing makes: local's newest full is corrupt, and remote's newer than
    local's older one wins."""
    mgr = _manager(tmp_path, levels=("local", "remote"))
    for i in (1, 2):
        mgr.save(i, _state(i))
    mgr.stores["remote"].save(3, _state(3))
    mgr.stores["local"].save(4, _state(4))
    _corrupt_byte(_shards(tmp_path / "local", 4)[0])
    expect = mgr.restore_candidates()[0]
    rep = mgr.restore(_state(), "task")
    assert (rep.step, rep.level) == expect == (3, "remote")
    assert rep.fallbacks == 1 and tree_allclose(rep.state, _state(3))


def test_a_rejected_full_leaves_the_delta_on_its_base(tmp_path):
    """A local full written after a newer delta's base (a rolled-back
    stream) fails its load: the restore takes that base and its delta over
    the remote full between them, as the validated listing does."""
    from repro.checkpoint import CheckpointManager, CheckpointPlan
    mgr = CheckpointManager(str(tmp_path), CheckpointPlan(
        mode="incremental", full_every=8, delta_codec="lossless",
        levels=("local", "remote"), num_shards=2, keep=5,
        replication_factor=0))
    mgr.save(0, _state(0))
    assert mgr.save(5, _state(5)).kind == "delta"
    mgr.stores["remote"].save(4, _state(4))
    mgr.stores["local"].save(3, _state(3))
    rep = mgr.restore(_state(), "task")
    assert (rep.step, rep.level, rep.fallbacks) == (4, "remote", 0)
    _corrupt_byte(_shards(tmp_path / "local", 3)[0])
    expect = mgr.restore_candidates()[0]
    rep = mgr.restore(_state(), "task")
    assert (rep.step, rep.level) == expect == (5, "local")
    assert rep.kind == "full+delta" and rep.fallbacks == 1
    assert tree_allclose(rep.state, _state(5))


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
