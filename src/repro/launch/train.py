"""Training launcher: the sharded train step on the devices of this host,
or the CPU-scale resilient trainer for local runs.

    # local (CPU, reduced config, real checkpoints/failures):
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --local \
        --duration 60

    # the devices of this host (one v5e chip, or the four of a v5e host):
    # --arch at its published widths cut as HOST_CUT says, a few sharded
    # steps, a full-sync checkpoint, a node failure and a restore
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b
"""
from __future__ import annotations

import argparse
import math
import os
import tempfile
import time
from typing import Any, NamedTuple, Optional

import jax
import numpy as np

from repro.config import (CheckpointPlan, ModelConfig, OptimizerConfig,
                          ShapeConfig, ShardingConfig)
from repro.data.pipeline import StreamingBatcher
from repro.models import zoo
from repro.optim import make_optimizer
from repro.sharding import ShardingRules

# The host run's workload: the architecture at its published widths with
# only its depth and vocabulary cut.  yi-6b so cut (32 -> 1 layers, 64000
# -> 8000 tokens) fits one v5e chip's 16 GB with f32 parameters and f32
# Adam state at 4 sequences of 2048 tokens.
HOST_CUT = {"num_layers": 1, "vocab_size": 8000}
HOST_BATCH, HOST_SEQ_LEN, HOST_STEPS = 4, 2048, 3
HOST_MODEL_PARALLEL = 2      # the mesh's 'model' axis, where devices allow


class ShardedStep(NamedTuple):
    fn: Any                  # compiled train step; donates its state
    state_shardings: Any
    batch_shardings: Any
    state_specs: Any         # ShapeDtypeStructs of the state


def build_sharded_step(cfg: ModelConfig, mesh, opt_cfg: OptimizerConfig, *,
                       batch: int, seq_len: int, accum: Optional[int] = None,
                       seed: int = 0) -> tuple[ShardedStep, dict]:
    """Compile the train step for ``mesh`` (layouts from ``ShardingRules``)
    and initialize the state directly in its sharded layout.  ``accum``
    defaults to one sequence per data replica per micro-step."""
    rules = ShardingRules(cfg, mesh, ShardingConfig())
    opt = make_optimizer(opt_cfg)
    accum = accum or max(1, batch // rules.dp_size)
    step = zoo.make_train_step(cfg, opt, opt_cfg, accum=accum,
                               ann=rules.annotator())
    state_specs = zoo.state_specs(cfg, opt)
    batch_specs = zoo.input_specs(cfg, ShapeConfig("run", "train", seq_len,
                                                   batch))
    state_sh = rules.state_shardings(state_specs)
    batch_sh = rules.batch_shardings(batch_specs)
    out = jax.eval_shape(step, state_specs, batch_specs)
    jitted = jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(rules.state_shardings(out[0]),
                       jax.tree_util.tree_map(lambda _: rules.replicated(),
                                              out[1])),
        donate_argnums=0)
    compiled = jitted.lower(state_specs, batch_specs).compile()
    init = jax.jit(lambda key: zoo.init_state(cfg, opt, key),
                   out_shardings=state_sh)
    state = init(jax.random.PRNGKey(seed))
    return ShardedStep(compiled, state_sh, batch_sh, state_specs), state


def next_batch(batcher: StreamingBatcher, step: ShardedStep) -> dict:
    """The stream's next batch, placed as the step was compiled for."""
    b = batcher.next_batch()
    if b is None:
        raise RuntimeError("the stream underran: produce more events "
                           "before training")
    return jax.device_put({"tokens": b["tokens"], "labels": b["labels"]},
                          step.batch_shardings)


def train_steps(step: ShardedStep, state: dict, batcher: StreamingBatcher,
                steps: int) -> tuple[dict, list]:
    """Take ``steps`` steps on the stream's batches; returns (state, losses)."""
    losses = []
    for _ in range(steps):
        state, metrics = step.fn(state, next_batch(batcher, step))
        losses.append(float(metrics["loss"]))
    return state, losses


def full_sync_plan() -> CheckpointPlan:
    """Full, synchronous, node-local checkpoints.  The sharded step donates
    its state, so every leaf is copied off the device before save()
    returns (``eager_snapshot``)."""
    return CheckpointPlan(mode="full", sync=True, levels=("local",),
                          eager_snapshot=True, keep=1)


def restore_sharded(mgr, step: ShardedStep, failure_kind: str = "node"):
    """Restore the newest checkpoint that survives ``failure_kind`` into
    the layout ``step`` was compiled for: every leaf goes back to its own
    sharding, none to the default device alone.  Returns (report, state);
    the report's ``state`` is the host copy that was read."""
    report = mgr.restore(step.state_specs, failure_kind)
    return report, jax.device_put(report.state, step.state_shardings)


def host_run(cfg: ModelConfig, devices: list, ckpt_dir: str, *,
             batch: int = HOST_BATCH, seq_len: int = HOST_SEQ_LEN,
             steps: int = HOST_STEPS, seed: int = 0) -> dict:
    """The launcher's run on ``devices``: ``steps`` sharded steps on the
    stream, a full-sync checkpoint, a node failure, a restore into the
    step's layout, and the next step taken from the live and from the
    restored state on the same batch.  Returns what a caller checks: the
    losses, the save's report, the restore's kind and step, whether what
    the restore read equals the live state bit for bit, the restored
    leaves' shardings and both next losses."""
    from repro.checkpoint import CheckpointManager
    from repro.data.stream import EventStream, constant_rate
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(devices,
                          model=math.gcd(len(devices), HOST_MODEL_PARALLEL))
    t0 = time.perf_counter()
    step, state = build_sharded_step(cfg, mesh, OptimizerConfig(),
                                     batch=batch, seq_len=seq_len, seed=seed)
    build_s = time.perf_counter() - t0
    stream = EventStream(schedule=constant_rate(1e6))
    stream.produce_until(1.0)
    batcher = StreamingBatcher(stream, batch, seq_len, cfg.vocab_size,
                               seed=seed)
    t0 = time.perf_counter()
    state, losses = train_steps(step, state, batcher, steps)
    steps_s = time.perf_counter() - t0

    mgr = CheckpointManager(ckpt_dir, full_sync_plan())
    saved = mgr.save(int(state["step"]), state, 0.0,
                     {"pipeline": batcher.state_dict()})
    mgr.on_failure("node")
    report, restored = restore_sharded(mgr, step, "node")
    read = jax.tree_util.tree_leaves(report.state)
    bit_exact = all(
        a.dtype == b.dtype and a.tobytes() == np.asarray(b).tobytes()
        for a, b in zip(read, jax.tree_util.tree_leaves(state)))
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, restored)
    b = next_batch(batcher, step)
    _, live = step.fn(state, b)
    _, resumed = step.fn(restored, b)
    return {"mesh": dict(mesh.shape), "step": step, "build_s": build_s,
            "steps_s": steps_s,
            "losses": losses, "save": saved, "restore_kind": report.kind,
            "restore_step": report.step, "bit_exact": bit_exact,
            "restored_shardings": shardings,
            "next_loss": float(live["loss"]),
            "next_loss_restored": float(resumed["loss"])}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--local", action="store_true",
                    help="CPU-scale run with the reduced config")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--ckpt-dir", default=os.path.join(tempfile.gettempdir(),
                                                       "repro_launch_train"))
    ap.add_argument("--ci", type=float, default=30.0)
    ap.add_argument("--khaos", action="store_true",
                    help="local runs: supervise with a KhaosRuntime "
                         "(prior-fitted QoS models) through TrainerJobHandle")
    args = ap.parse_args()

    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()

    if args.local:
        from repro.config import KhaosConfig
        from repro.configs import get_smoke_config
        from repro.core import KhaosRuntime, demo_prior_models
        from repro.data.stream import EventStream, diurnal_rate
        from repro.runtime import (ResilientTrainer, TrainerConfig,
                                   TrainerJobHandle)

        cfg = get_smoke_config(args.arch)
        stream = EventStream(schedule=diurnal_rate(base=400.0, period=600.0))
        tcfg = TrainerConfig(batch=8, seq_len=32, ckpt_dir=args.ckpt_dir,
                             ckpt_interval_s=args.ci, ckpt_async=True,
                             time_scale=8.0)
        trainer = ResilientTrainer(cfg, tcfg, stream,
                                   OptimizerConfig(total_steps=10_000))
        on_second = None
        if args.khaos:
            rt = KhaosRuntime(KhaosConfig(latency_constraint=1.0,
                                          recovery_constraint=30.0,
                                          optimization_period=10.0,
                                          ci_min=5, ci_max=60))
            rt.install_models(*demo_prior_models())
            rt.attach(TrainerJobHandle(trainer))
            on_second = lambda sample: rt.step()
        summary = trainer.run(args.duration, on_second=on_second)
        print(summary)
        return

    from repro.config import replace
    from repro.configs import get_config

    cfg = replace(get_config(args.arch), **HOST_CUT)
    devices = jax.devices()
    print(f"{args.arch} cut {HOST_CUT}, batch {HOST_BATCH} x {HOST_SEQ_LEN}"
          f" on {len(devices)} {devices[0].device_kind} devices")
    out = host_run(cfg, devices, args.ckpt_dir)
    print(f"mesh {out['mesh']}: compile+init {out['build_s']:.3f}s, "
          f"{HOST_STEPS} steps {out['steps_s']:.3f}s, losses {out['losses']}")
    print(f"checkpoint step {out['save'].step}: {out['save'].bytes_written} "
          f"bytes in {out['save'].duration_s:.3f}s; restore "
          f"{out['restore_kind']} of step {out['restore_step']}, bit-exact "
          f"{out['bit_exact']}; next loss live {out['next_loss']}, restored "
          f"{out['next_loss_restored']}")
    if not (np.isfinite(out["losses"]).all() and out["bit_exact"]
            and out["next_loss"] == out["next_loss_restored"]):
        raise SystemExit("launcher: the run or its restore failed")


if __name__ == "__main__":
    main()
