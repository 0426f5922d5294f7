"""Elastic recovery: lose hosts mid-training, plan a smaller mesh, restore
the checkpoint with a different shard count, and keep training — the
manifest-driven reshard path (DESIGN.md §5), expressed through the unified
control-plane API: one ``CheckpointManager`` executes the plan on the big
mesh, and the surviving cluster rebuilds the manager from a NEW
``CheckpointPlan`` (different ``num_shards``) — exactly the drain+rebuild
primitive ``ResilientTrainer.set_plan``/``TrainerJobHandle.
reconfigure_plan`` actuate when the Khaos controller switches mechanisms.

    PYTHONPATH=src python examples/elastic_recovery.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import CheckpointPlan, MeshConfig, OptimizerConfig
from repro.configs import get_smoke_config
from repro.ft import HeartbeatDetector, plan_rescale
from repro.models import zoo
from repro.optim import make_optimizer
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    cfg = get_smoke_config("qwen2.5-14b")
    opt = make_optimizer(OptimizerConfig())
    state = zoo.init_state(cfg, opt, jax.random.PRNGKey(0))
    state["step"] = jnp.asarray(120, jnp.int32)

    # 1. production cluster: 64 hosts, one manager executing the 64-shard plan
    plan64 = CheckpointPlan(levels=("local",), num_shards=64)
    mgr64 = CheckpointManager("/tmp/repro_elastic", plan64)
    mgr64.save(120, state, extra={"pipeline": {"cursor": {"offset": 960},
                                               "stream": {"consumed": 960}}})
    mgr64.wait()
    print(f"saved step-120 checkpoint under plan [{plan64.name}] as 64 shards")

    # 2. three hosts miss heartbeats
    det = HeartbeatDetector(num_hosts=64, timeout_s=50.0)
    det.heartbeat_all(0.0)
    for h in range(61):
        det.heartbeat(h, 60.0)
    dead = det.failed_hosts(61.0)
    print(f"heartbeat detector: hosts {dead} failed")

    # 3. plan the new mesh (TP pinned, data axis shrinks, batch stays divisible)
    mesh = MeshConfig(data=16, model=16)
    plan = plan_rescale(mesh, hosts_alive=64 - len(dead), chips_per_host=4,
                        global_batch=256)
    print(f"rescale plan: {plan.old.shape} -> {plan.new.shape} "
          f"({plan.hosts_used} hosts used, {plan.standby} standby, "
          f"batch_ok={plan.batch_ok})")

    # 4. the surviving cluster REBUILDS the manager from a new plan (shard
    #    count follows the smaller mesh) and restores THE SAME checkpoint —
    #    the manifest makes shard count a restore-time choice, and the
    #    rebuild is the same primitive a controller plan-switch uses
    plan61 = CheckpointPlan(levels=("local",), num_shards=plan.hosts_used)
    mgr61 = CheckpointManager("/tmp/repro_elastic", plan61)
    report = mgr61.restore(state, failure_kind="node")
    restored, extra = report.state, report.extra
    same = all(np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
               for a, b in zip(jax.tree_util.tree_leaves(state),
                               jax.tree_util.tree_leaves(restored)))
    print(f"restored at step {report.step} from level {report.level!r} with "
          f"cursor {extra['pipeline']['cursor']} — bitwise identical: {same}")
    assert same and plan.new.model == 16
    print("elastic recovery complete: resume training on the smaller mesh")


if __name__ == "__main__":
    main()
