"""Sharding rules (pure logic on an abstract mesh) + one real multi-device
compile in a subprocess (so the 1-device default of this test process is
preserved, per the dry-run isolation rule)."""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import MeshConfig, ShardingConfig
from repro.configs import get_config
from repro.launch.mesh import make_abstract_mesh
from repro.sharding import ShardingRules


def _rules(arch="yi-6b", multi=False, **scfg):
    mesh = make_abstract_mesh(
        (2, 16, 16) if multi else (16, 16),
        ("pod", "data", "model") if multi else ("data", "model"))
    return ShardingRules(get_config(arch), mesh,
                         ShardingConfig(**scfg))


def test_meshes_have_auto_axes():
    """The sharding rules pin layouts with with_sharding_constraint, which
    takes only Auto axes; jax.make_mesh defaults to Explicit ones."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh
    host = make_host_mesh()
    assert host.axis_names == ("data", "model")
    assert host.devices.size == len(jax.devices())
    for mesh in (host, _rules().mesh):
        assert set(mesh.axis_types) == {AxisType.Auto}
    with pytest.raises(ValueError):
        make_host_mesh(model=len(jax.devices()) + 1)


def test_tp_sharding_of_core_weights():
    r = _rules(fsdp=False)
    assert r.param_spec("layers/attn/wq", (32, 4096, 32, 128)) == P(None, None, "model", None)
    assert r.param_spec("layers/ffn/w_up", (32, 4096, 11008)) == P(None, None, "model")
    assert r.param_spec("layers/ffn/w_down", (32, 11008, 4096)) == P(None, "model", None)
    assert r.param_spec("emb/embed", (64000, 4096)) == P("model", None)


def test_kv_heads_replicated_when_not_divisible():
    r = _rules(fsdp=False)
    # yi-6b: 4 kv heads % 16 != 0 -> replicated (no head-dim sharding)
    assert r.param_spec("layers/attn/wk", (32, 4096, 4, 128)) == P(None, None, None, None)


def test_fsdp_adds_data_axis():
    r = _rules(fsdp=True, fsdp_min_params=0)
    spec = r.param_spec("layers/ffn/w_up", (32, 4096, 11008))
    assert spec == P(None, "data", "model")


def test_fsdp_spans_pod_axis_on_multipod():
    r = _rules(arch="grok-1-314b", multi=True, fsdp=True, fsdp_min_params=0)
    spec = r.param_spec("layers/moe/w_up", (64, 8, 6144, 32768))
    # experts (8) not divisible by tp: d over (pod,data), f over model
    assert spec == P(None, None, ("pod", "data"), "model")


def test_moe_expert_axis_when_divisible():
    r = _rules(arch="olmoe-1b-7b", fsdp=False)
    spec = r.param_spec("layers/moe/w_up", (16, 64, 2048, 1024))
    assert spec == P(None, "model", None, None)   # 64 experts / 16


def test_norms_replicated():
    r = _rules()
    assert r.param_spec("layers/ln1/scale", (32, 4096)) == P()


def test_kv_cache_seq_sharding_fallback():
    r = _rules()
    # yi decode: kv heads 4 %16 -> shard the 32k seq dim instead
    spec = r.cache_spec("k", (32, 128, 32768, 4, 128))
    assert spec == P(None, "data", "model", None, None)
    # codeqwen: 32 kv heads divisible -> heads shard
    r2 = _rules("codeqwen1.5-7b")
    spec2 = r2.cache_spec("k", (32, 128, 32768, 32, 128))
    assert spec2 == P(None, "data", None, "model", None)


def test_batch_replicates_when_not_divisible():
    r = _rules()
    assert r.input_spec("tokens", (1, 524288)) == P(None, None)   # long_500k B=1
    assert r.input_spec("tokens", (256, 4096)) == P("data", None)


def test_act_specs():
    r = _rules()
    assert r.act_spec("hidden", (256, 4096, 4096)) == P("data", None, None)
    assert r.act_spec("wide", (256, 4096, 11008)) == P("data", None, "model")


@pytest.mark.slow
def test_real_compile_on_8_fake_devices():
    """End-to-end lower+compile of a tiny sharded train step in a subprocess
    with 8 placeholder devices (never pollutes this process's jax)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json
        from repro.config import ModelConfig, ShapeConfig, OptimizerConfig, ShardingConfig
        from repro.models import zoo
        from repro.optim import make_optimizer
        from repro.sharding import ShardingRules
        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=128,
                          num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512)
        shape = ShapeConfig("t", "train", 64, 8)
        opt_cfg = OptimizerConfig(); opt = make_optimizer(opt_cfg)
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(4, 2)
        rules = ShardingRules(cfg, mesh, ShardingConfig(fsdp_min_params=0))
        ann = rules.annotator()
        state = zoo.state_specs(cfg, opt)
        batch = zoo.input_specs(cfg, shape)
        fn = zoo.make_train_step(cfg, opt, opt_cfg, accum=2, ann=ann)
        out = jax.eval_shape(fn, state, batch)
        jt = jax.jit(fn,
                     in_shardings=(rules.state_shardings(state), rules.batch_shardings(batch)),
                     out_shardings=(rules.state_shardings(out[0]),
                                    jax.tree_util.tree_map(lambda _: rules.replicated(), out[1])))
        compiled = jt.lower(state, batch).compile()
        ma = compiled.memory_analysis()
        print(json.dumps({"ok": True, "temp": ma.temp_size_in_bytes}))
    """)
    try:
        res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=300,
                             env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                  "JAX_PLATFORMS": "cpu"})
    except (subprocess.TimeoutExpired, OSError) as e:
        pytest.skip(f"8-device compile subprocess did not finish here: {e!r:.200}")
    if res.returncode != 0 and ("ImportError" in res.stderr
                                or "ModuleNotFoundError" in res.stderr):
        pytest.skip("8-device compile subprocess env is missing deps: "
                    + res.stderr[-500:])
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"]


def test_host_run_restores_into_the_step_layout(tmp_path):
    """The launcher's host run: sharded steps, a full-sync checkpoint, a
    node failure, and a restore whose leaves take the layout the compiled
    step declares; the next step from it equals the live one's."""
    from repro.config import ModelConfig
    from repro.launch.train import host_run
    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256)
    out = host_run(cfg, jax.devices(), str(tmp_path), batch=2, seq_len=32,
                   steps=2)
    assert np.isfinite(out["losses"]).all() and len(out["losses"]) == 2
    assert out["save"].kind == "full" and out["save"].step == 2
    assert out["restore_kind"] == "full" and out["restore_step"] == 2
    assert out["bit_exact"]
    declared = jax.tree_util.tree_leaves(out["step"].fn.input_shardings[0][0])
    restored = jax.tree_util.tree_leaves(out["restored_shardings"])
    specs = jax.tree_util.tree_leaves(out["step"].state_specs)
    assert len(restored) == len(declared) == len(specs)
    assert all(a.is_equivalent_to(d, s.ndim)
               for a, d, s in zip(restored, declared, specs))
    assert out["next_loss"] == out["next_loss_restored"]
