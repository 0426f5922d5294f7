"""Device-resident delta plane: the f32 subtree packed into ONE
GROUP-aligned mega-buffer, ONE fused encode kernel in front of D2H
(``pipeline.DeltaLeafSource``), placement/codec as plan dimensions, and
the batched controller evaluation that rides along.

All kernel work runs in Pallas interpret mode on the CPU backend
(``ckpt_delta.ops.default_interpret``), so every test here is tier-1.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, CheckpointPlan,
                              DeltaLeafSource, DeviceDeltaBase, FlatLayout)
from repro.checkpoint.incremental import (apply_delta, read_delta_manifest,
                                          write_delta)
from repro.kernels.ckpt_delta.ref import (GROUP, decode_ref,
                                          flat_int8_encode_ref,
                                          flat_lossless_encode_ref,
                                          lossless_encode_ref, pack_flat_ref)
from repro.utils.trees import tree_flatten_with_names

jax.config.update("jax_platform_name", "cpu")


def _state(seed=0, n=3000):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.standard_normal((n,))
                                    .astype(np.float32)),
                   "frozen": jnp.asarray(rng.standard_normal((256,))
                                         .astype(np.float32))},
        "host": rng.standard_normal((128,)).astype(np.float32),
        "ids": np.arange(64, dtype=np.int64),
        "step": jnp.asarray(np.int32(seed)),
    }


def _bump(state, eps=np.float32(1e-4)):
    out = dict(state)
    out["params"] = {"w": state["params"]["w"] + eps,
                     "frozen": state["params"]["frozen"]}     # unchanged
    out["host"] = state["host"] + np.float32(0.5)
    return out


def _bit_exact(a, b) -> bool:
    la = [np.asarray(x) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x) for x in jax.tree_util.tree_leaves(b)]
    return all(np.array_equal(x, y) for x, y in zip(la, lb))


def _flat_ref(layout: FlatLayout, state) -> np.ndarray:
    """Host-oracle packed mega-buffer of ``state`` in layout order."""
    leaves = {n: np.asarray(l) for n, l in tree_flatten_with_names(state)}
    return pack_flat_ref([leaves[n] for n in layout.names])


# ---------------------------------------------------------------------------
# DeltaLeafSource flat payload == ref.py host oracle (kernel parity, tier-1)
# ---------------------------------------------------------------------------

def test_delta_leaf_source_matches_host_oracle_lossless():
    s0 = _state(0)
    s1 = _bump(s0)
    src = DeltaLeafSource(s1, DeviceDeltaBase(s0), codec="lossless")
    src.wait()
    layout = src.layout
    # only the f32 jax leaves pack into the mega-buffer; host and non-f32
    # leaves stay outside the layout and remain raw-readable
    assert sorted(layout.names) == ["params/frozen", "params/w"]
    for name in ("host", "ids", "step"):
        assert name not in layout.by_name
    d_ref, r_ref, changed_ref, _ = flat_lossless_encode_ref(
        _flat_ref(layout, s1), _flat_ref(layout, s0),
        layout.group_leaf, len(layout.names))
    payload = src.flat_payload()
    assert payload["d"].dtype == np.float32
    assert np.array_equal(payload["d"], d_ref)
    # the tiny bump keeps every element within 2x of its base, so the
    # residual plane is all-zero: its D2H is skipped, marker recorded
    assert not r_ref.any() and payload["r"] == "zero"
    # unchanged packed leaf -> fused change count 0 -> skip-zero marker
    assert src.zero_names == tuple(
        e.name for e, c in zip(layout.entries, changed_ref) if not c) \
        == ("params/frozen",)
    assert np.array_equal(src.get("host"), s1["host"])
    # link accounting, exactly: the d plane (residual skipped) + the
    # eager host-leaf copies; the lazy jax scalar "step" is not pulled
    assert src.bytes_on_link() == 4 * layout.total \
        + s1["host"].nbytes + s1["ids"].nbytes


def test_delta_leaf_source_matches_host_oracle_int8():
    s0 = _state(1)
    s1 = _bump(s0, eps=np.float32(3e-3))
    src = DeltaLeafSource(s1, DeviceDeltaBase(s0), codec="int8")
    src.wait()
    layout = src.layout
    q_ref, s_ref, _ = flat_int8_encode_ref(
        _flat_ref(layout, s1), _flat_ref(layout, s0),
        layout.group_leaf, len(layout.names))
    payload = src.flat_payload()
    assert np.array_equal(payload["q"], q_ref)
    assert np.array_equal(payload["s"], s_ref)
    # int8 payload is ~1.004 B/elem vs 4 B/elem f32 for the packed subtree
    assert payload["q"].nbytes + payload["s"].nbytes < 0.5 * 4 * layout.total


def test_delta_leaf_source_residual_transferred_when_nonzero():
    """Elements whose base and new values are far apart (ratio > 2) make
    base + delta round away from new — the residual must cross the link
    and restore must stay bit-exact."""
    base_w = np.array([1.0, 1e-8, -3.0, 1e20] * 256, np.float32)
    new_w = np.array([1.0 + 1e-7, 7.25, 3e-8, -1.5] * 256, np.float32)
    s0 = {"w": jnp.asarray(base_w)}
    s1 = {"w": jnp.asarray(new_w)}
    src = DeltaLeafSource(s1, DeviceDeltaBase(s0), codec="lossless")
    src.wait()
    d_ref, r_ref = lossless_encode_ref(new_w, base_w)
    assert r_ref.any(), "fixture must produce a nonzero residual"
    payload = src.flat_payload()
    assert np.array_equal(payload["r"], r_ref)
    assert np.array_equal(payload["d"], d_ref)


# ---------------------------------------------------------------------------
# fused flat kernels == host oracles on an awkward layout (tier-1)
# ---------------------------------------------------------------------------

def test_flat_ops_match_ref_on_awkward_leaf_sizes():
    """Parity of the fused pallas ops (interpret mode) with the numpy
    oracles on a layout mixing odd, tiny and exactly-one-group leaves —
    padded extents, the group->leaf scatter-add and the per-leaf change
    stats included."""
    from repro.kernels.ckpt_delta.ops import (flat_int8_encode,
                                              flat_lossless_encode,
                                              pack_flat)

    rng = np.random.default_rng(7)
    sizes = [3000, 256, 1, 5000, GROUP]
    base = [rng.standard_normal((s,)).astype(np.float32) for s in sizes]
    new = [b + rng.uniform(-1e-2, 1e-2, b.shape).astype(np.float32)
           for b in base]
    new[1] = base[1].copy()                      # one unchanged leaf
    layout = FlatLayout([(f"l{i}", (s,)) for i, s in enumerate(sizes)])
    nl = len(sizes)
    nf = pack_flat([jnp.asarray(x) for x in new])
    bf = pack_flat([jnp.asarray(x) for x in base])
    assert nf.shape == (layout.total // GROUP, GROUP)   # packed as rows
    assert np.array_equal(np.asarray(nf).reshape(-1), pack_flat_ref(new))
    gl = layout.group_leaf_device()
    d, r, lc, lz = flat_lossless_encode(nf, bf, gl, num_leaves=nl,
                                        interpret=True)
    d_ref, r_ref, lc_ref, lz_ref = flat_lossless_encode_ref(
        pack_flat_ref(new), pack_flat_ref(base), layout.group_leaf, nl)
    assert d.shape == r.shape == nf.shape
    assert np.array_equal(np.asarray(d).reshape(-1), d_ref)
    assert np.array_equal(np.asarray(r).reshape(-1), r_ref)
    assert np.array_equal(np.asarray(lc), lc_ref)
    assert np.array_equal(np.asarray(lz), lz_ref)
    assert int(lc_ref[1]) == 0 and lc_ref[[0, 2, 3, 4]].all()
    q, s, lc2 = flat_int8_encode(nf, bf, gl, num_leaves=nl, interpret=True)
    q_ref, s_ref, _ = flat_int8_encode_ref(
        pack_flat_ref(new), pack_flat_ref(base), layout.group_leaf, nl)
    assert np.array_equal(np.asarray(q).reshape(-1), q_ref)
    assert np.array_equal(np.asarray(s), s_ref)
    assert np.array_equal(np.asarray(lc2), lc_ref)


def test_flat_blocks_pads_to_block_multiple():
    """Compiled-mode blocking: an 11-group buffer at block_groups=8 runs a
    ragged final block (3 of 8 rows in range) instead of padding the
    state-sized planes to a block multiple; its out-of-range rows (the
    interpreter fills them with NaN) must change neither the payload nor
    the per-group statistics.  Interpret mode collapses to ONE
    whole-buffer block — the per-grid-step cost structure documented on
    ``_flat_block_groups``."""
    from repro.kernels.ckpt_delta.kernel import (flat_delta_encode_fwd,
                                                 flat_lossless_encode_fwd)
    from repro.kernels.ckpt_delta.ops import _flat_block_groups

    rng = np.random.default_rng(11)
    base = rng.standard_normal((11, GROUP)).astype(np.float32)
    new = base + rng.uniform(-1e-2, 1e-2, base.shape).astype(np.float32)
    new[4] = base[4]                             # one unchanged group
    d, r, gc, gz = flat_lossless_encode_fwd(
        jnp.asarray(new), jnp.asarray(base), block_groups=8, interpret=True)
    d_ref, r_ref = lossless_encode_ref(new, base)
    assert d.shape == (11, GROUP) and gc.shape == gz.shape == (11,)
    assert np.array_equal(np.asarray(d).reshape(-1), d_ref)
    assert np.array_equal(np.asarray(r).reshape(-1), r_ref)
    changed = (new.view(np.uint32) != base.view(np.uint32)).sum(axis=1)
    assert np.array_equal(np.asarray(gc), changed) and int(gc[4]) == 0
    assert np.array_equal(np.asarray(gz),
                          (r_ref.reshape(11, GROUP) != 0).sum(axis=1))
    q, s, gc8 = flat_delta_encode_fwd(
        jnp.asarray(new), jnp.asarray(base), block_groups=8, interpret=True)
    q_ref, s_ref, _ = flat_int8_encode_ref(
        new.reshape(-1), base.reshape(-1), np.zeros(11, np.int32), 1)
    assert np.array_equal(np.asarray(q).reshape(-1), q_ref)
    assert np.array_equal(np.asarray(s), s_ref)
    assert np.array_equal(np.asarray(gc8), changed)
    assert _flat_block_groups(11 * GROUP, 8, interpret=False) == 8
    assert _flat_block_groups(11 * GROUP, 8, interpret=True) == 11


# ---------------------------------------------------------------------------
# int8 round trip obeys the documented group-quantization bound
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_within_group_bound():
    """|err| <= max|delta_group| / 254 per element (scale = amax/127,
    round-to-nearest) — the bound documented on the int8 encode ops."""
    rng = np.random.default_rng(5)
    base = rng.standard_normal((4 * GROUP,)).astype(np.float32)
    new = (base + rng.uniform(-0.01, 0.01, base.shape)
           .astype(np.float32)).astype(np.float32)
    src = DeltaLeafSource({"w": jnp.asarray(new)},
                          DeviceDeltaBase({"w": jnp.asarray(base)}),
                          codec="int8")
    payload = src.flat_payload()
    got = decode_ref(payload["q"], payload["s"])[:new.size]
    delta = new - base
    amax = np.abs(delta.reshape(-1, GROUP)).max(axis=1)
    bound = np.repeat(np.maximum(amax, 1e-12) / 254.0, GROUP)
    err = np.abs(got - delta)
    assert (err <= bound + 1e-9).all(), float((err - bound).max())


# ---------------------------------------------------------------------------
# skip paths: all-zero residual, fully-unchanged state
# ---------------------------------------------------------------------------

def test_all_zero_residual_skips_transfer_and_roundtrips(tmp_path):
    """When the fused per-leaf residual counts sum to zero the residual
    plane never crosses the link — the manifest carries a ``"zero"``
    marker, no ``flat@r.bin`` exists, the decoder reconstructs zeros, and
    restore is bit-exact."""
    rng = np.random.default_rng(11)
    base_w = rng.standard_normal((8 * GROUP,)).astype(np.float32)
    s0 = {"w": jnp.asarray(base_w), "t": np.arange(4, dtype=np.int64)}
    s1 = {"w": jnp.asarray(base_w + np.float32(1e-4)),
          "t": np.arange(4, dtype=np.int64)}
    src = DeltaLeafSource(s1, DeviceDeltaBase(s0), codec="lossless")
    payload = src.flat_payload()
    assert payload["r"] == "zero"
    # link = the d plane + the eager host-leaf copy; NO residual bytes
    assert src.bytes_on_link() == 8 * GROUP * 4 + s1["t"].nbytes
    base_np = jax.tree_util.tree_map(np.asarray, s0)
    d = str(tmp_path)
    write_delta(d, 1, src, base_np, 0, 1.0, mode="lossless", codec="zlib")
    meta = read_delta_manifest(d, 1)
    assert meta["flat"]["arrays"]["r"] == "zero"
    assert not os.path.exists(os.path.join(d, "delta_0000000001",
                                           "flat@r.bin"))
    got = apply_delta(d, 1, base_np, placement="device")
    assert _bit_exact(got, s1)


def test_all_unchanged_state_moves_no_payload(tmp_path):
    """Every packed leaf unchanged: no payload arrays cross the link at
    all, every packed leaf gets a skip-zero marker, and the delta still
    applies (to the base itself)."""
    s0 = _state(2)
    src = DeltaLeafSource(s0, DeviceDeltaBase(s0), codec="int8")
    assert src.flat_payload() == {}
    assert src.zero_names == tuple(src.layout.names)
    # only the eager host-leaf copies count as link traffic
    assert src.bytes_on_link() == s0["host"].nbytes + s0["ids"].nbytes
    base_np = jax.tree_util.tree_map(np.asarray, s0)
    write_delta(str(tmp_path), 1, src, base_np, 0, 1.0, mode="int8",
                codec="zlib")
    meta = read_delta_manifest(str(tmp_path), 1)
    assert meta["flat"]["arrays"] == {}
    got = apply_delta(str(tmp_path), 1, base_np)
    assert _bit_exact(got, s0)


# ---------------------------------------------------------------------------
# cross-version and mixed-dtype restores
# ---------------------------------------------------------------------------

def test_v2_per_leaf_manifest_restores_through_current_reader(tmp_path):
    """Cross-version: a per-leaf (pre-flat) delta manifest — no ``flat``
    section, the layout PR-5 wrote — still restores bit-exactly through
    the current reader under both decode placements."""
    s0 = _state(6)
    s1 = _bump(_state(6))
    base_np = jax.tree_util.tree_map(np.asarray, s0)
    new_np = jax.tree_util.tree_map(np.asarray, s1)
    d = str(tmp_path)
    write_delta(d, 1, new_np, base_np, 0, 1.0, mode="lossless",
                codec="zlib")
    meta = read_delta_manifest(d, 1)
    assert "flat" not in meta        # host pytree source: per-leaf blobs
    for placement in ("host", "device"):
        got = apply_delta(d, 1, base_np, placement=placement)
        assert _bit_exact(got, s1)


def test_mixed_dtype_state_roundtrips_bit_exact_under_device_plan(tmp_path):
    """Non-f32, odd-sized, zero-size and host-resident leaves fall back
    to the per-leaf host path while the f32 subtree rides the flat
    payload — one device-placement delta must restore the whole mixed
    state bit-exactly."""
    rng = np.random.default_rng(9)
    state = {
        "w": jnp.asarray(rng.standard_normal((3001,)).astype(np.float32)),
        "half": jnp.asarray(rng.standard_normal((513,))
                            .astype(np.float16)),
        "empty": jnp.zeros((0,), jnp.float32),
        "host": rng.standard_normal((65,)).astype(np.float32),
        "ids": np.arange(7, dtype=np.int64),
        "step": jnp.asarray(np.int32(0)),
    }
    bumped = dict(state)
    bumped["w"] = state["w"] + np.float32(1e-3)
    bumped["half"] = (state["half"].astype(jnp.float32)
                      + 0.25).astype(jnp.float16)
    bumped["host"] = state["host"] + np.float32(0.5)
    bumped["step"] = jnp.asarray(np.int32(1))
    plan = CheckpointPlan(mode="incremental", full_every=4,
                          encode_placement="device")
    mgr = CheckpointManager(str(tmp_path), plan)
    mgr.save(0, state, 0.0)
    rep = mgr.save(1, bumped, 1.0)
    assert rep.kind == "delta"
    meta = read_delta_manifest(str(tmp_path / "local"), 1)
    # only the non-empty f32 device leaf packs into the flat section
    assert [row[0] for row in meta["flat"]["layout"]] == ["w"]
    got = mgr.restore(state, "node")
    assert got.step == 1 and _bit_exact(got.state, bumped)


# ---------------------------------------------------------------------------
# cross-placement restore: blobs are byte-compatible both ways
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("save_placement,restore_placement",
                         [("device", "host"), ("host", "device")])
def test_cross_placement_restore_bit_exact(tmp_path, save_placement,
                                           restore_placement):
    plan_save = CheckpointPlan(mode="incremental", full_every=4,
                               encode_placement=save_placement)
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, plan_save)
    s0, s1 = _state(0), _bump(_state(0))
    mgr.save(0, s0, 0.0)
    rep = mgr.save(1, s1, 1.0)
    assert rep.kind == "delta"
    meta = read_delta_manifest(os.path.join(d, "local"), 1)
    assert meta["placement"] == save_placement
    # restore through a manager configured for the OTHER placement
    mgr2 = CheckpointManager(d, CheckpointPlan(
        mode="incremental", full_every=4,
        encode_placement=restore_placement))
    got = mgr2.restore(_state(0), "node")
    assert got.step == 1 and got.kind == "full+delta"
    assert _bit_exact(got.state, s1)


@pytest.mark.parametrize("codec", ["lossless", "int8"])
def test_device_and_host_deltas_restore_identically(tmp_path, codec):
    """Acceptance: a fixed-seed device-encoded delta (flat mega-buffer
    blobs) and the host-encoded per-leaf delta of the same transition
    decode to the SAME state.  The blob layouts differ — ``flat@*.bin``
    planes under a ``flat`` manifest section subsume the packed leaves'
    per-leaf blobs — but GROUP alignment keeps every flat extent's
    payload bit-identical to the per-leaf encoder's, so the decoded
    states agree to the bit."""
    s0, s1 = _state(3), _bump(_state(3), eps=np.float32(2e-3))
    base = jax.tree_util.tree_map(np.asarray, s0)
    for placement in ("host", "device"):
        d = str(tmp_path / placement)
        os.makedirs(d)
        if placement == "device":
            src = DeltaLeafSource(s1, DeviceDeltaBase(s0), codec=codec)
        else:
            src = jax.tree_util.tree_map(np.asarray, s1)
        write_delta(d, 1, src, base, 0, 1.0, mode=codec, codec="zlib")
    mh = read_delta_manifest(str(tmp_path / "host"), 1)
    md = read_delta_manifest(str(tmp_path / "device"), 1)
    assert "flat" not in mh and md["flat"]
    # the packed subtree writes NO per-leaf blobs on the device path —
    # the flat planes replace them; fallback leaves still get their own
    dev_files = sorted(os.listdir(os.path.join(str(tmp_path / "device"),
                                               "delta_0000000001")))
    assert not any(f.startswith("params@") for f in dev_files)
    assert any(f.startswith("flat@") for f in dev_files)
    # skip-zero markers agree across placements (fused per-leaf change
    # counts == host byte-equality checks)
    assert set(mh["zero"]) == set(md["zero"])
    a = apply_delta(str(tmp_path / "host"), 1, base)
    b = apply_delta(str(tmp_path / "device"), 1, base, placement="device")
    assert _bit_exact(a, b)
    if codec == "lossless":
        assert _bit_exact(a, s1)


# ---------------------------------------------------------------------------
# device base lifecycle: plan-switch carry-over, failure wipe, savepoint
# ---------------------------------------------------------------------------

def test_plan_switch_carries_device_base_over(tmp_path):
    plan = CheckpointPlan(mode="incremental", full_every=8,
                          encode_placement="device")
    mgr = CheckpointManager(str(tmp_path), plan)
    s0 = _state(0)
    mgr.savepoint(0, s0, 0.0)
    assert mgr._device_base is not None
    # the rebuild (set_plan semantics): a fresh manager adopting runtime
    # state must keep device-encoding deltas against the drained full
    mgr2 = CheckpointManager(str(tmp_path), CheckpointPlan(
        mode="incremental", full_every=8, encode_placement="device",
        interval_s=10.0))
    mgr2.adopt_runtime_state(mgr)
    # the drained device base rides the rebuild (no re-upload)
    assert mgr2._device_base is mgr._device_base
    s1 = _bump(s0)
    rep = mgr2.save(1, s1, 1.0)      # trigger 0 of the new cadence: full
    assert rep.kind == "full"
    s2 = _bump(s1)
    rep = mgr2.save(2, s2, 2.0)
    assert rep.kind == "delta"
    meta = read_delta_manifest(str(tmp_path / "local"), 2)
    assert meta["placement"] == "device"
    got = mgr2.restore(_state(0), "node")
    assert got.step == 2 and _bit_exact(got.state, s2)
    # a node failure wipes the device base with the rest of runtime state
    mgr2.on_failure("node")
    assert mgr2._device_base is None
    rep2 = mgr2.save(3, s2, 3.0)
    assert rep2.kind == "full"          # chain restarts


@pytest.mark.parametrize("codec", ["lossless", "int8"])
def test_prepare_compiles_device_triggers_ahead(tmp_path, codec):
    """After ``prepare`` the full trigger's pack and the delta trigger's
    pack and encode find their executables compiled: no trigger compiles."""
    from repro.checkpoint import pipeline
    rng = np.random.default_rng(7)
    # leaf sizes no other test packs, so the compile caches start cold
    state = {"a": jnp.asarray(rng.standard_normal((3, 1111), np.float32)),
             "b": jnp.asarray(rng.standard_normal((77,), np.float32)),
             "step": jnp.asarray(np.int32(0))}
    mgr = CheckpointManager(str(tmp_path), CheckpointPlan(
        mode="incremental", full_every=4, encode_placement="device",
        delta_codec=codec))
    mgr.prepare(state)
    misses = (pipeline._compiled_pack.cache_info().misses,
              pipeline._compiled_encoder.cache_info().misses)
    assert mgr.save(0, state, 0.0).kind == "full"
    nxt = {**state, "a": state["a"] + np.float32(1e-3)}
    assert mgr.save(1, nxt, 1.0).kind == "delta"
    assert (pipeline._compiled_pack.cache_info().misses,
            pipeline._compiled_encoder.cache_info().misses) == misses
    got = mgr.restore(state, "node")
    assert got.step == 1
    if codec == "lossless":
        assert _bit_exact(got.state, nxt)


def test_save_report_bytes_on_link_distinguishes_link_from_disk(tmp_path):
    """Satellite: bytes_on_link (pre-compression, post-encode) vs
    bytes_written (post-compression).  Host deltas move the raw state;
    device int8 deltas move ~0.3x of it."""
    s0 = _state(0, n=8192)
    s1 = _bump(s0)
    raw = sum(np.asarray(l).nbytes for l in jax.tree_util.tree_leaves(s0))
    host = CheckpointManager(str(tmp_path / "h"), CheckpointPlan(
        mode="incremental", full_every=4))
    host.save(0, s0, 0.0)
    rep = host.save(1, s1, 1.0)
    assert rep.kind == "delta" and rep.bytes_on_link == raw
    dev = CheckpointManager(str(tmp_path / "d"), CheckpointPlan(
        mode="incremental", full_every=4, encode_placement="device",
        delta_codec="int8"))
    full_rep = dev.save(0, s0, 0.0)
    assert full_rep.bytes_on_link == raw      # fulls always move the state
    drep = dev.save(1, s1, 1.0)
    assert drep.kind == "delta"
    assert 0 < drep.bytes_on_link < 0.5 * raw
    st = dev.stats()
    assert st["bytes_on_link"] == full_rep.bytes_on_link + drep.bytes_on_link
    # a device delta trigger that ALSO takes a remote full pulls the raw
    # state for that write — the raw D2H must be accounted, not just the
    # encoded payload
    ml = CheckpointManager(str(tmp_path / "ml"), CheckpointPlan(
        mode="incremental", full_every=4, levels=("local", "remote"),
        remote_every=2, encode_placement="device", delta_codec="int8"))
    ml.save(0, s0, 0.0)                       # full everywhere
    ml.save(1, _bump(s0), 1.0)                # delta, local only
    rep2 = ml.save(2, _bump(_bump(s0)), 2.0)  # delta local + remote FULL
    assert rep2.kind == "delta" and "remote" in rep2.levels
    assert rep2.bytes_on_link > raw           # payload + raw full pull
    # legacy incremental checkpointer reports the link quantity too
    from repro.checkpoint import CheckpointStore, IncrementalCheckpointer
    inc = IncrementalCheckpointer(CheckpointStore(str(tmp_path / "l"),
                                                  num_shards=2))
    inc.save(0, jax.tree_util.tree_map(np.asarray, s0))
    assert inc.stats()["bytes_on_link"] == raw


# ---------------------------------------------------------------------------
# cost model: placement pricing, v2 calibration, coverage assertions
# ---------------------------------------------------------------------------

def _v2_calibration():
    return {
        "schema": "bench_ckpt/2",
        "state_bytes": 32 * 2**20,
        "full_write_s": 2.0,
        "restore_s": 1.5,
        "delta_fraction": 0.05,
        "delta_int8_fraction": 0.01,
        "delta_encode_s_per_byte": 3.0 / (32 * 2**20),
        "device": {
            "lossless": {"bytes_on_link": 33 * 2**20 // 32,
                         "link_fraction": 1.01, "encode_s": 0.02},
            "int8": {"bytes_on_link": 8 * 2**20,
                     "link_fraction": 0.25, "encode_s": 0.01},
        },
        "plans": {"incr8-sync": {"bytes_per_trigger": 1.0, "write_s": 0.1,
                                 "blocking_s": 0.1, "encode_cpu_s": 0.5}},
    }


def test_from_calibration_v2_prices_device_placement():
    from repro.sim import SimCostModel

    cost = SimCostModel.from_calibration(_v2_calibration())
    assert cost.device_link_fraction_int8 == 0.25
    assert cost.device_encode_s == 0.02
    # device delta drops the per-trigger host encode (3 s) for the
    # measured device encode (0.01-0.02 s)
    host_d = cost.write_duration("delta", encoding="int8")
    dev_d = cost.write_duration("delta", encoding="int8",
                                placement="device")
    assert dev_d < host_d
    assert np.isclose(host_d - dev_d, 3.0 - 0.01)
    # plan-level: the device-int8 plan has the cheapest trigger average
    incr = CheckpointPlan(mode="incremental", full_every=8)
    dev8 = CheckpointPlan(mode="incremental", full_every=8,
                          encode_placement="device", delta_codec="int8")
    assert cost.avg_write_duration(dev8) < cost.avg_write_duration(incr)
    # link-bytes accounting: host plans move the raw state every trigger;
    # the device-int8 plan averages fulls at 1.0x with deltas at 0.25x
    assert cost.avg_link_bytes(incr) == cost.state_bytes
    want = (cost.state_bytes + 7 * 0.25 * cost.state_bytes) / 8
    assert np.isclose(cost.avg_link_bytes(dev8), want)
    # a delta trigger that also takes a remote full pays payload + raw
    dev_ml = CheckpointPlan(mode="incremental", full_every=8,
                            levels=("local", "remote"), remote_every=4,
                            encode_placement="device", delta_codec="int8")
    assert np.isclose(cost.trigger_link_bytes(dev_ml, 4),
                      1.25 * cost.state_bytes)


def _v3_calibration():
    cal = _v2_calibration()
    cal["schema"] = "bench_ckpt/3"
    for codec in ("lossless", "int8"):
        cal["device"][codec].update(pack_s=0.004, per_leaf_encode_s=0.5)
    return cal


def test_from_calibration_v3_prices_pack_and_rejects_missing_keys():
    from repro.sim import SimCostModel

    cost = SimCostModel.from_calibration(_v3_calibration())
    assert cost.device_pack_s == 0.004 == cost.device_pack_s_int8
    # the device delta price is pack + fused encode, replacing the host
    # per-byte encode term — exactly that swap, nothing double-charged
    host_d = cost.write_duration("delta", encoding="int8")
    dev_d = cost.write_duration("delta", encoding="int8",
                                placement="device")
    assert np.isclose(host_d - dev_d, 3.0 - (0.004 + 0.01))
    # a v2 artifact keeps pack_s at 0 (the per-leaf path packed nothing)
    assert SimCostModel.from_calibration(_v2_calibration()).device_pack_s \
        == 0.0
    # a v3 artifact missing the new per-codec keys is rejected
    bad = _v3_calibration()
    del bad["device"]["lossless"]["per_leaf_encode_s"]
    with pytest.raises(ValueError, match="device"):
        SimCostModel.from_calibration(bad)


def test_from_calibration_v1_fallback_and_v2_rejects_bad_device():
    from repro.sim import SimCostModel

    v1 = {k: v for k, v in _v2_calibration().items() if k != "device"}
    v1["schema"] = "bench_ckpt/1"
    cost = SimCostModel.from_calibration(v1)
    assert cost.device_link_fraction_int8 == \
        SimCostModel.device_link_fraction_int8   # modeled default
    bad = _v2_calibration()
    del bad["device"]["int8"]["encode_s"]
    with pytest.raises(ValueError, match="device"):
        SimCostModel.from_calibration(bad)
    bad2 = _v2_calibration()
    del bad2["device"]
    with pytest.raises(ValueError, match="device"):
        SimCostModel.from_calibration(bad2)


def test_surviving_levels_rejects_unknown_failure_kind():
    from repro.checkpoint.multilevel import allowed_levels
    from repro.sim import SimCostModel

    cost = SimCostModel()
    plan = CheckpointPlan(levels=("memory", "local", "remote"))
    assert cost.surviving_levels(plan, "node") == ("local", "remote")
    with pytest.raises(ValueError, match="unknown failure kind"):
        cost.surviving_levels(plan, "rack")
    with pytest.raises(ValueError, match="known kinds"):
        allowed_levels("typo")


# ---------------------------------------------------------------------------
# optimizer: (placement x codec) variants, campaign-verified
# ---------------------------------------------------------------------------

def test_optimize_plan_surfaces_campaign_verified_device_int8():
    """Acceptance: with a bench_ckpt/3-calibrated cost model (the device
    delta priced as pack + fused flat encode), the default variant grid
    contains (placement=device, codec=int8) candidates — single- and
    multi-level — and the campaign verifier scores at least one of them
    end-to-end."""
    from repro.core import QoSModel, optimize_plan
    from repro.core.ci_optimizer import default_plan_variants
    from repro.data.stream import constant_rate
    from repro.sim import SimCostModel
    from repro.sim.batched import make_plan_verifier

    cost = SimCostModel.from_calibration(
        _v3_calibration(), capacity_eps=4600.0, ckpt_sync_penalty=0.6)
    variants = default_plan_variants(cost, ci_ref=60.0)
    dev_int8 = [p for p in variants if p.encode_placement == "device"
                and p.delta_codec == "int8"]
    assert dev_int8, "variant grid lost the device-int8 dimension"
    assert any(tuple(p.levels) != ("local",) for p in dev_int8), \
        "variant grid lost the multi-level device-int8 plan"
    # each scored device candidate's modeled write price reflects the
    # fused cost: pack + one fused encode per delta trigger
    for p in dev_int8:
        assert np.isclose(
            cost.write_duration("delta", encoding="int8",
                                placement="device"),
            cost.ckpt_duration_s * cost.delta_int8_fraction
            + cost.device_pack_s_int8 + cost.device_encode_s_int8)
    rng = np.random.default_rng(0)
    ci = rng.uniform(10, 120, 200)
    tr = rng.uniform(1000, 4000, 200)
    m_l = QoSModel().fit(ci, tr, cost.base_latency_s + 40.0 / ci + tr * 1e-5)
    m_r = QoSModel().fit(ci, tr, 80.0 + 1.2 * ci + 0.01 * tr)
    verifier = make_plan_verifier(cost, schedule=constant_rate(2500.0),
                                  max_recovery_s=900.0)
    res = optimize_plan(m_l, m_r, tr_avg=2500.0, l_const=1.0, r_const=240.0,
                        p=1.0, ci_min=10, ci_max=120, cost=cost,
                        verifier=verifier, verify_top_k=4)
    assert res.feasible and res.verified
    scored = [c for c in res.candidates
              if c.plan.encode_placement == "device"
              and c.plan.delta_codec == "int8" and c.sim is not None]
    assert scored, "no device-int8 candidate was campaign-verified"
    assert {"latency_s", "recovery_s"} <= set(scored[0].sim)


# ---------------------------------------------------------------------------
# drive_campaign: shared QoS evaluation, Decisions bit-identical
# ---------------------------------------------------------------------------

def test_drive_campaign_batched_predictions_bit_identical_decisions():
    """Satellite: the per-period QoS-model reads are batched (one
    ``QoSModel.predict`` over all lanes), and the per-lane Decisions are
    BIT-identical to the per-lane evaluation loop."""
    from repro.config import KhaosConfig
    from repro.core import KhaosRuntime
    from repro.data.stream import constant_rate, dense_rates
    from repro.sim import BatchedCampaign, LaneSpec, SimCostModel
    from repro.sim.batched import BatchedLaneHandle

    cost = SimCostModel(capacity_eps=2600.0, ckpt_duration_s=1.0)
    kcfg = KhaosConfig(latency_constraint=1.2, recovery_constraint=240.0,
                       optimization_period=30.0, ci_min=10, ci_max=120,
                       reconfig_cooldown=60.0)
    sched = constant_rate(1800.0)

    def make_campaign():
        lanes = [LaneSpec(rates=dense_rates(0.0, 400, schedule=sched),
                          ci_s=float(ci),
                          failures=((120.0, "node"),) if i % 2 else ())
                 for i, ci in enumerate((15, 40, 80, 115))]
        return BatchedCampaign(cost, lanes)

    def fresh_runtime():
        rt = KhaosRuntime(kcfg, cost=cost)
        from repro.core.qos_models import QoSModel
        rng = np.random.default_rng(0)
        ci = rng.uniform(10, 120, 150)
        tr = rng.uniform(1000, 2400, 150)
        m_l = QoSModel().fit(ci, tr, cost.base_latency_s + 30.0 / ci)
        m_r = QoSModel().fit(ci, tr, 60.0 + 1.1 * ci + 0.02 * tr)
        rt.install_models(m_l, m_r)
        return rt

    # batched path: drive_campaign (shared predictions)
    rt = fresh_runtime()
    sup = rt.drive_campaign(make_campaign())

    # oracle: the pre-batching per-lane loop, scalar predict per lane
    rt2 = fresh_runtime()
    camp = make_campaign()
    handles = [BatchedLaneHandle(camp, i) for i in range(camp.n_lanes)]
    controllers = [rt2._make_controller() for _ in handles]
    period = max(1, int(round(kcfg.optimization_period)))
    while not camp.done:
        camp.run(n_ticks=period)
        for ctl, h in zip(controllers, handles):
            if h.alive():
                ctl.maybe_optimize(h)
    for ctl, h in zip(controllers, handles):
        ctl.maybe_optimize(h)

    for lane, (ctl, got) in enumerate(zip(controllers, sup.controllers)):
        want = ctl.decisions
        have = got.decisions
        assert len(want) == len(have), (lane, len(want), len(have))
        for dw, dh in zip(want, have):
            assert (dw.t, dw.kind) == (dh.t, dh.kind), lane
            for f in ("latency", "tr_avg", "predicted_recovery", "new_ci"):
                a, b = getattr(dw, f), getattr(dh, f)
                assert (a is None and b is None) or \
                    np.array_equal(np.float64(a), np.float64(b),
                                   equal_nan=True), (lane, f, a, b)
            assert (dw.new_plan is None) == (dh.new_plan is None)
            if dw.new_plan is not None:
                assert dw.new_plan == dh.new_plan
