"""One run of the system's main path on a TPU, with every result checked.

    python chip_smoke.py              # one chip: kernels, trainer, campaign
    python chip_smoke.py --chips 4    # the sharded train step: four chips
                                      # against one, and a sharded restore

The trainer is yi-6b at its published widths (d_model 4096, d_ff 11008,
32 query / 4 kv heads) with only its depth and vocabulary cut (``CUT``),
built as ``examples/train_stream.py`` builds it: a ``ResilientTrainer``
consuming an ``EventStream``, checkpointed by its ``CheckpointManager``
and supervised by a ``KhaosRuntime`` through ``TrainerJobHandle``.
Weights and data are random, made from ``SEED``.

Phases (one chip):

  kernels   the flat ``ckpt_delta`` encoders and decoders, compiled for
            the chip (``tpu_custom_call`` in the compiled text), at the
            size of the packed train state, against the ``ref.py``
            oracles: lossless bit-exact, int8 within the codec's bound.
  trainer   reckons each plan's peak HBM from the step's
            ``memory_analysis()`` and the buffers a trigger holds, then
            per optimizer-state dtype: steps under a full-sync host plan,
            checkpoint, kill (``node``), restore bit-exact; ``set_plan``
            onto incremental device-encode plans, delta triggers, kill,
            restore (lossless bit-exact, int8 within the bound).
  campaign  ``DeviceCampaign`` with 1e5 lanes for a few chunks, and the
            60-lane plan x crash x degradation matrix against the NumPy
            ``BatchedCampaign`` (divergent lanes are printed).

``--chips 4`` runs only the launcher's host run (``repro.launch.train.
host_run``) on a 2x2 ('data', 'model') mesh of the four chips: the
sharded steps, a full-sync checkpoint, a node failure and the launcher's
restore into the step's layout, then the next step from the live and the
restored state.  It compares the losses with the same steps on one chip
in the same process.

Every line but the last reports a phase.  The last line is one JSON
object, printed only when every phase passed.  Times printed are single
samples of one run, not metrics.  The script exits non-zero before any
work when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# the launcher's workload: depth and vocabulary cut (published: 32 layers,
# 64000 tokens), sequences per batch and tokens per sequence, and the train
# steps of each trainer phase
from repro.launch.train import (HOST_BATCH as BATCH,  # noqa: E402
                                HOST_CUT as CUT, HOST_SEQ_LEN as SEQ_LEN,
                                HOST_STEPS as STEPS)

ARCH = "yi-6b"
SEED = 0
WINDOW_S = 3600.0      # virtual seconds a trainer phase may take; also the
                       # failure-detection delay, so a phase ends at its
                       # restore
STREAM_EPS = 200.0     # events (sequences) per virtual second: never underruns
CAMPAIGN_LANES, CAMPAIGN_CHUNK, CAMPAIGN_CHUNKS = 100_000, 250, 3
# int8: the half quantization step plus four times the float32 roundings
# of d/scale and q*scale that tests/test_kernels.py allows on the CPU,
# since the chip's f32 division need not be correctly rounded
INT8_ROUNDING = 4 * 254 * 2.0 ** -24


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def hbm(key: str) -> int:
    """One of the chip's memory statistics (-1 where it reports none)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get(key, -1)


def _bits(x):
    import numpy as np
    x = np.ascontiguousarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


def host_copy(tree):
    import jax
    import numpy as np
    return jax.tree_util.tree_map(lambda x: np.array(x, copy=True), tree)


def bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(_bits(np.asarray(x)), _bits(np.asarray(y)))
        for x, y in zip(la, lb))


def opt_config(state_dtype: str):
    from repro.config import OptimizerConfig
    return OptimizerConfig(total_steps=5000, lr=3e-3, state_dtype=state_dtype)


def model_config():
    from repro.config import replace
    from repro.configs import get_config
    return replace(get_config(ARCH), **CUT)


def packed_layout(state):
    """The ``FlatLayout`` a device delta trigger packs: the f32 leaves."""
    import numpy as np
    from repro.checkpoint import FlatLayout
    from repro.utils.trees import tree_flatten_with_names
    return FlatLayout([(n, tuple(l.shape))
                       for n, l in tree_flatten_with_names(state)
                       if np.dtype(l.dtype) == np.float32
                       and int(np.prod(l.shape)) > 0])


# ---------------------------------------------------------------------------
# plan: reckon each plan's peak HBM before running it
# ---------------------------------------------------------------------------

def reckon(cfg, limit: int) -> dict:
    """Compile the train step with f32 and with bf16 Adam state and reckon
    each plan's peak bytes on the device.

    step  = the step program's arguments + outputs + temporaries
    S     = the train state, F = its packed f32 subtree
    full-sync host    step
    device-int8       trigger: live S + base leaves S + packed base F +
                      packed new F + q F/4; between triggers: step + base
                      leaves S + packed base F; restore: old S + new S +
                      q F/4 + decoded F
    device-lossless   as int8 with d and r (2F) for q, and base, d, r and
                      the decoded plane (4F) on restore
    """
    import jax
    from repro.config import ShapeConfig
    from repro.models import zoo
    from repro.optim import make_optimizer
    from repro.utils.trees import tree_bytes

    out = {}
    for sdt in ("float32", "bfloat16"):
        oc = opt_config(sdt)
        opt = make_optimizer(oc)
        specs = zoo.state_specs(cfg, opt)
        batch = zoo.input_specs(cfg, ShapeConfig("smoke", "train", SEQ_LEN,
                                                 BATCH))
        t0 = time.perf_counter()
        compiled = jax.jit(zoo.make_train_step(cfg, opt, oc)).lower(
            specs, batch).compile()
        compile_s = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        step_b = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes)
        S = tree_bytes(specs)
        F = packed_layout(specs).total * 4
        between = step_b + S + F
        peaks = {
            "full-sync-host": step_b,
            "device-int8": max(between, 2 * S + 2 * F + F // 4,
                               2 * S + F // 4 + F),
            "device-lossless": max(between, 2 * S + 4 * F),  # trigger,
        }                                                      # restore
        out[sdt] = {"peaks": peaks, "layout": packed_layout(specs)}
        log("plan", f"{sdt} Adam state: step compile {compile_s:.3f}s, "
                    f"step args {ma.argument_size_in_bytes} out "
                    f"{ma.output_size_in_bytes} temp {ma.temp_size_in_bytes}"
                    f" B; state {S} B, packed f32 {F} B")
        for plan, peak in peaks.items():
            log("plan", f"{sdt} {plan}: reckoned peak {peak} B "
                        f"({peak / limit:.3f} of {limit} B) -> "
                        f"{'fits' if peak <= limit else 'does not fit'}")
    return out


def choose_state_dtypes(reckoning: dict, limit: int) -> dict:
    """Adam state dtype per device plan: f32 where the reckoned peak fits
    the chip, bf16 otherwise (never catch an out-of-memory error)."""
    chosen = {}
    for plan in ("device-int8", "device-lossless"):
        for sdt in ("float32", "bfloat16"):
            if reckoning[sdt]["peaks"][plan] <= limit:
                chosen[plan] = sdt
                break
        else:
            raise SystemExit(f"{plan} fits the chip with neither f32 nor "
                             f"bf16 Adam state at this cut")
        log("plan", f"{plan} runs with {chosen[plan]} Adam state")
    return chosen


# ---------------------------------------------------------------------------
# kernels: compiled ckpt_delta codec vs the ref.py oracles
# ---------------------------------------------------------------------------

def _compiled_text(fn, *args, **kw) -> tuple[str, float]:
    t0 = time.perf_counter()
    text = fn.lower(*args, **kw).compile().as_text()
    return text, time.perf_counter() - t0


ORACLE_ROWS = 1 << 16   # groups per host oracle chunk (bounds host memory)


def kernels_phase(codec: str, layout, interpret: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ckpt_delta import ops, ref

    G = ref.GROUP
    ng = layout.total // G
    nl = len(layout.entries)

    @jax.jit
    def planes(key):
        k1, k2 = jax.random.split(key)
        base = jax.random.normal(k1, (ng, G), jnp.float32)
        new = base + 1e-3 * jax.random.normal(k2, (ng, G), jnp.float32)
        row = jnp.arange(ng)[:, None]
        new = jnp.where(row % 97 == 0, base, new)           # unchanged groups
        # far from the base: base + (new - base) rounds off new
        return jnp.where(row % 89 == 0, 3.7 * base + 100.0, new), base

    new, base = planes(jax.random.PRNGKey(SEED))
    gl = jnp.asarray(layout.group_leaf)
    new_h, base_h = np.asarray(new), np.asarray(base)
    chunks = [slice(a, min(ng, a + ORACLE_ROWS))
              for a in range(0, ng, ORACLE_ROWS)]
    tag = f"{codec} {ng} groups x {G} ({new_h.nbytes} B per plane)"

    def leaf_sums(per_chunk):
        return np.sum(per_chunk, axis=0)

    if codec == "lossless":
        enc = ops.flat_lossless_encode
        text, cs = _compiled_text(enc, new, base, gl, num_leaves=nl,
                                  interpret=interpret)
        t0 = time.perf_counter()
        d, r, lc, lz = jax.block_until_ready(
            enc(new, base, gl, num_leaves=nl, interpret=interpret))
        run_s = time.perf_counter() - t0
        d_h, r_h = np.asarray(d), np.asarray(r)
        ok_d = ok_r = True
        lcs, lzs, nnz = [], [], 0
        for c in chunks:
            dref, rref, lcref, lzref = ref.flat_lossless_encode_ref(
                new_h[c], base_h[c], layout.group_leaf[c], nl)
            ok_d &= np.array_equal(_bits(d_h[c].reshape(-1)), _bits(dref))
            ok_r &= np.array_equal(r_h[c].reshape(-1), rref)
            lcs.append(lcref)
            lzs.append(lzref)
            nnz += int(np.count_nonzero(rref))
        ok_stats = (np.array_equal(np.asarray(lc), leaf_sums(lcs))
                    and np.array_equal(np.asarray(lz), leaf_sums(lzs)))
        del d_h, r_h
        dtext, dcs = _compiled_text(ops.lossless_decode, base, d, r,
                                    interpret=interpret)
        out = np.asarray(ops.lossless_decode(base, d, r, interpret=interpret))
        ok_rt = np.array_equal(_bits(out), _bits(new_h))
        log("kernels", f"{tag}: encode compile {cs:.3f}s run {run_s:.4f}s "
                       f"(one chip-run sample), decode compile {dcs:.3f}s; "
                       f"nonzero residual words {nnz}; delta bit-exact "
                       f"{ok_d}, residual bit-exact {ok_r}, leaf stats equal "
                       f"{ok_stats}, round trip bit-exact {ok_rt}")
        checks = [ok_d, ok_r, ok_stats, ok_rt, nnz > 0]
    else:
        enc = ops.flat_int8_encode
        text, cs = _compiled_text(enc, new, base, gl, num_leaves=nl,
                                  interpret=interpret)
        t0 = time.perf_counter()
        q, s, lc = jax.block_until_ready(
            enc(new, base, gl, num_leaves=nl, interpret=interpret))
        run_s = time.perf_counter() - t0
        dtext, dcs = _compiled_text(ops.delta_decode, q, s,
                                    interpret=interpret)
        dev = np.asarray(ops.delta_decode(q, s, interpret=interpret))
        q_h, s_h = np.asarray(q), np.asarray(s)
        scale_rel = worst = ulps = 0.0
        q_same = exact = 0
        lcs = []
        for c in chunks:
            qref, sref, lcref = ref.flat_int8_encode_ref(
                new_h[c], base_h[c], layout.group_leaf[c], nl)
            lcs.append(lcref)
            sc = s_h[c]
            scale_rel = max(scale_rel, float(np.max(np.abs(sc - sref) / sref)))
            q_same += int(np.sum(q_h[c].reshape(-1) == qref))
            delta = (new_h[c] - base_h[c]).astype(np.float64)
            deq = ref.decode_ref(q_h[c], sc).reshape(delta.shape)
            bound = sc.astype(np.float64)[:, None] * (0.5 + INT8_ROUNDING)
            worst = max(worst, float(np.max(np.abs(deq - delta) / bound)))
            dv = dev[c].reshape(deq.shape)
            exact += int(np.sum(_bits(dv) == _bits(deq)))
            ulps = max(ulps, float(np.max(
                np.abs(dv.astype(np.float64) - deq)
                / np.spacing(np.abs(deq)))))
        ok_stats = np.array_equal(np.asarray(lc), leaf_sums(lcs))
        q_agree = q_same / new_h.size
        log("kernels", f"{tag}: encode compile {cs:.3f}s run {run_s:.4f}s "
                       f"(one chip-run sample), decode compile {dcs:.3f}s; "
                       f"scales max rel diff {scale_rel:.3e}, q equal to "
                       f"oracle {q_agree:.6f}, leaf stats equal {ok_stats}, "
                       f"worst error / bound {worst:.6f}; device decode vs "
                       f"oracle: {exact / new_h.size:.6f} bit-exact, max "
                       f"{ulps:.1f} ulp")
        checks = [scale_rel <= 2.0 ** -21, q_agree >= 0.999, ok_stats,
                  worst <= 1.0, ulps <= 1.0]
    if not interpret:
        checks += ["tpu_custom_call" in t for t in (text, dtext)]
    if not all(checks):
        raise SystemExit(f"kernels {codec}: a check failed: {checks}")


# ---------------------------------------------------------------------------
# trainer: ResilientTrainer + CheckpointManager + KhaosRuntime
# ---------------------------------------------------------------------------

def host_full_plan():
    from repro.config import CheckpointPlan
    # checkpoints come from drain() (checkpoint-now): a phase's few steps
    # take far less than one interval of virtual time
    return CheckpointPlan(interval_s=60.0, mode="full", levels=("local",),
                          sync=True, keep=1, num_shards=4)


def device_plan(codec: str):
    from repro.config import CheckpointPlan
    # a trigger before every step; the first after the switch is a full
    return CheckpointPlan(interval_s=1e-3, mode="incremental",
                          full_every=1000, delta_codec=codec,
                          encode_placement="device", levels=("local",),
                          sync=True, keep=1, num_shards=4)


class Job:
    """A trainer built the way ``examples/train_stream.py`` builds one."""

    def __init__(self, cfg, state_dtype: str, ckpt_dir: str):
        from repro.config import KhaosConfig
        from repro.core import KhaosRuntime, demo_prior_models
        from repro.data.stream import EventStream, constant_rate
        from repro.runtime import (ResilientTrainer, TrainerConfig,
                                   TrainerJobHandle)
        from repro.sim import SimCostModel

        self.state_dtype = state_dtype
        stream = EventStream(schedule=constant_rate(STREAM_EPS))
        tcfg = TrainerConfig(batch=BATCH, seq_len=SEQ_LEN, ckpt_dir=ckpt_dir,
                             time_scale=1.0, detect_s=WINDOW_S, restart_s=2.0,
                             plan=host_full_plan())
        t0 = time.perf_counter()
        self.trainer = ResilientTrainer(cfg, tcfg, stream,
                                        opt_config(state_dtype), seed=SEED)
        self.build_s = time.perf_counter() - t0
        # no constraint can be violated: the runtime supervises and
        # decides, but never actuates a plan of its own during the smoke
        self.rt = KhaosRuntime(
            KhaosConfig(latency_constraint=math.inf,
                        recovery_constraint=math.inf,
                        optimization_period=10.0, ci_min=5, ci_max=60,
                        reconfig_cooldown=20.0),
            cost=SimCostModel(capacity_eps=500.0, ckpt_duration_s=0.5),
            mtbf_s=600.0)
        self.rt.install_models(*demo_prior_models())
        self.handle = TrainerJobHandle(self.trainer)
        self.rt.attach(self.handle)

    def run(self, on_step) -> dict:
        """One trainer phase: ``on_step(count)`` after every step; the
        phase ends at the restore that follows an injected failure."""
        count, in_use = 0, 0

        def on_second(sample):
            nonlocal count, in_use
            self.rt.step()
            count += 1
            in_use = max(in_use, hbm("bytes_in_use"))
            on_step(count)

        n0 = len(self.trainer.events)
        t0 = time.perf_counter()
        summary = self.trainer.run(WINDOW_S, on_second=on_second)
        summary["wall_s"] = time.perf_counter() - t0
        summary["steps"] = count
        summary["sampled_bytes_in_use"] = in_use
        summary["phase_events"] = self.trainer.events[n0:]
        return summary


def _check_phase(name: str, job: Job, summary: dict, saved_step: int,
                 kind: str, ckpt_kinds: list, ok_state: bool,
                 detail: str) -> None:
    import jax
    import numpy as np
    ev = summary["phase_events"]
    restores = [e for e in ev if e["event"] in ("restore", "restore_fresh")]
    got_kinds = [e["kind"] for e in ev if e["event"] == "checkpoint"]
    stats = summary["ckpt_stats"]
    last = job.trainer.ckpt.restores[-1] if job.trainer.ckpt.restores \
        else None
    losses = job.trainer.losses[-summary["steps"]:]
    peak = hbm("peak_bytes_in_use")
    decisions = collections.Counter(
        d.kind for d in job.rt.controller.decisions)
    checks = {
        "steps": summary["steps"] == STEPS,
        "one restore event, not restore_fresh":
            [e["event"] for e in restores] == ["restore"],
        "restored step": bool(restores) and restores[0].get("step")
        == saved_step,
        "restore kind": last == (saved_step, "local", kind),
        "checkpoint kinds": got_kinds == ckpt_kinds,
        "no async errors": not stats["async_errors"],
        "finite losses": bool(np.isfinite(losses).all()),
        "restored state": ok_state,
    }
    log("trainer", f"{name} [{job.state_dtype} Adam]: {summary['steps']} "
                   f"steps, losses {losses}, checkpoints {got_kinds}, "
                   f"restore {last}, {detail}; phase wall "
                   f"{summary['wall_s']:.3f}s, last step "
                   f"{summary['measured_step_s']:.4f}s (one chip-run sample,"
                   f" not a metric); bytes_in_use sampled max "
                   f"{summary['sampled_bytes_in_use']} B, peak so far {peak} B;"
                   f" bytes written {stats['bytes_by_kind']}, on link "
                   f"{stats['bytes_on_link']}; runtime decisions "
                   f"{dict(decisions)}")
    failed = [k for k, v in checks.items() if not v]
    if failed:
        raise SystemExit(f"trainer {name}: failed checks {failed}")


def host_phase(job: Job) -> None:
    """Steps under the full-sync host plan, checkpoint, kill, restore."""
    tr = job.trainer
    saved = {}

    def on_step(count):
        if count == STEPS:
            saved["state"] = host_copy(tr.state)
            saved["step"] = int(tr.state["step"])
            tr.drain()                 # checkpoint-now: a full savepoint
            tr.inject_failure_at(tr.t, "node")

    summary = job.run(on_step)
    ok = bitwise_equal(host_copy(tr.state), saved["state"])
    _check_phase("full-sync host", job, summary, saved["step"], "full",
                 ["savepoint"], ok, f"bit-exact {ok}")


def int8_within_bound(restored, saved, base) -> tuple[bool, float]:
    """Every float leaf within the int8 codec's bound of the saved state:
    per 1024-group, half a quantization step of (saved - base) plus the
    codec's float roundings, plus the rounding of base + delta back to
    the leaf's dtype.  Other leaves exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ckpt_delta.ref import GROUP
    worst, ok = 0.0, True
    for r, s, b in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (restored, saved, base))):
        if not jnp.issubdtype(s.dtype, jnp.floating):
            ok &= np.array_equal(r, s)
            continue
        n = s.size
        d = (s.astype(np.float32) - b.astype(np.float32)).reshape(-1)
        d = np.concatenate([d, np.zeros((-n) % GROUP, np.float32)])
        scale = np.maximum(np.abs(d).reshape(-1, GROUP).max(axis=1),
                           1e-12) / 127.0
        r64 = r.astype(np.float64).reshape(-1)
        bound = (np.repeat(scale * (0.5 + INT8_ROUNDING), GROUP)[:n]
                 + np.abs(r64) * float(jnp.finfo(s.dtype).eps))
        err = np.abs(r64 - s.astype(np.float64).reshape(-1))
        ratio = float(np.max(err / bound)) if n else 0.0
        worst = max(worst, ratio)
        ok &= ratio <= 1.0
    return bool(ok), worst


def device_phase(job: Job, codec: str) -> None:
    """set_plan onto a device-encode plan, delta triggers, kill, restore."""
    tr = job.trainer
    job.handle.reconfigure_plan(device_plan(codec))   # drain + rebuild
    base = host_copy(tr.state)       # what the switch's first trigger saves
    saved = {}

    def on_step(count):
        if count == STEPS - 1:       # the state the last delta trigger saves
            saved["state"] = host_copy(tr.state)
            saved["step"] = int(tr.state["step"])
        if count == STEPS:
            tr.inject_failure_at(tr.t, "node")

    summary = job.run(on_step)
    restored = host_copy(tr.state)
    if codec == "lossless":
        ok = bitwise_equal(restored, saved["state"])
        detail = f"bit-exact {ok}"
    else:
        ok, worst = int8_within_bound(restored, saved["state"], base)
        detail = f"within the int8 bound {ok} (worst error / bound {worst:.6f})"
    _check_phase(f"device-{codec}", job, summary, saved["step"],
                 "full+delta", ["full"] + ["delta"] * (STEPS - 1), ok, detail)


def trainer_phase(cfg, chosen: dict, workdir: str) -> None:
    from repro.kernels.ckpt_delta.ops import default_interpret
    if default_interpret():
        raise SystemExit("the delta codec would run in interpret mode")
    for sdt in ("float32", "bfloat16"):
        codecs = [p.split("-")[1] for p, d in chosen.items() if d == sdt]
        if sdt != "float32" and not codecs:
            continue
        job = Job(cfg, sdt, tempfile.mkdtemp(prefix=f"{sdt}-", dir=workdir))
        log("trainer", f"{sdt} Adam state: trainer built in "
                       f"{job.build_s:.3f}s (step compile included)")
        host_phase(job)
        for codec in codecs:
            device_phase(job, codec)
        del job
        gc.collect()


# ---------------------------------------------------------------------------
# campaign: DeviceCampaign on the chip, parity with the NumPy engine
# ---------------------------------------------------------------------------

def campaign_phase(lanes: int = CAMPAIGN_LANES) -> None:
    import numpy as np
    from benchmarks.bench_campaign import (THR_TICKS, campaign_cost,
                                           parity_check, throughput_lanes)
    from repro.sim.device import DeviceCampaign

    camp = DeviceCampaign(campaign_cost(), throughput_lanes(lanes, THR_TICKS),
                          record_history=False)
    walls = []
    for _ in range(CAMPAIGN_CHUNKS):
        t0 = time.perf_counter()
        camp.run(n_ticks=CAMPAIGN_CHUNK)
        walls.append(time.perf_counter() - t0)
    arrays = {name: np.asarray(getattr(camp, name))
              for name in ("lag", "consumed", "produced", "processed_total",
                           "steady_lag", "t")}
    finite = all(np.isfinite(a).all() for a in arrays.values())
    log("campaign", f"{lanes} lanes x {CAMPAIGN_CHUNKS} chunks of "
                    f"{CAMPAIGN_CHUNK} ticks: chunk walls "
                    f"{[round(w, 4) for w in walls]}s (first includes "
                    f"compile; one chip-run sample, not a metric); "
                    f"checkpoints {int(np.sum(camp.ckpt_count))}, all state "
                    f"finite {finite}")
    par = parity_check()
    log("campaign", f"parity matrix vs NumPy BatchedCampaign: "
                    f"{par['lanes']} lanes x {par['ticks']} ticks, "
                    f"{par['divergent_lanes']} divergent lanes "
                    f"(fma_contraction {par['fma_contraction']})")
    if not finite:
        raise SystemExit("campaign: non-finite lane state")


# ---------------------------------------------------------------------------
# sharded (--chips 4): the launcher's sharded step, four chips vs one
# ---------------------------------------------------------------------------

def sharded_phase(cfg, devices, workdir: str) -> None:
    import jax
    import numpy as np
    from repro.config import OptimizerConfig
    from repro.data.pipeline import StreamingBatcher
    from repro.data.stream import EventStream, constant_rate
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_sharded_step, host_run, train_steps

    # the reference: the same steps on one chip, with the launcher's
    # optimizer
    t0 = time.perf_counter()
    step, state = build_sharded_step(
        cfg, make_host_mesh(devices[:1]), OptimizerConfig(),
        batch=BATCH, seq_len=SEQ_LEN, seed=SEED)
    stream = EventStream(schedule=constant_rate(1e6))
    stream.produce_until(1.0)
    state, ref_losses = train_steps(
        step, state, StreamingBatcher(stream, BATCH, SEQ_LEN, cfg.vocab_size,
                                      seed=SEED), STEPS)
    log("sharded", f"one chip: compile+init+{STEPS} steps "
                   f"{time.perf_counter() - t0:.3f}s (one chip-run sample), "
                   f"losses {ref_losses}")
    del step, state
    gc.collect()

    out = host_run(cfg, devices, workdir, seed=SEED)
    log("sharded", f"mesh {out['mesh']}: compile+init {out['build_s']:.3f}s,"
                   f" {STEPS} steps {out['steps_s']:.3f}s (one chip-run "
                   f"sample), losses {out['losses']}")
    rel = np.abs(np.asarray(out["losses"]) - ref_losses) / np.abs(ref_losses)
    ok_loss = bool((rel <= 2.0 ** -7).all())       # bf16 epsilon
    log("sharded", f"losses vs one chip: max rel diff {float(rel.max()):.3e}"
                   f" (bf16 tolerance {2.0 ** -7:.3e}) -> {ok_loss}")

    # what the launcher's restore produced, against the layout the compiled
    # step declares; the step also refuses a committed leaf laid out
    # otherwise, so the next step from the restored state checks it again
    leaves = jax.tree_util.tree_leaves(out["restored_shardings"])
    declared = jax.tree_util.tree_leaves(
        out["step"].fn.input_shardings[0][0])
    specs = jax.tree_util.tree_leaves(out["step"].state_specs)
    everyone = set(devices)
    same_sharding = all(a.is_equivalent_to(d, s.ndim)
                        for a, d, s in zip(leaves, declared, specs))
    on_all = all(a.device_set == everyone for a in leaves)
    split = sum(1 for a in leaves if not a.is_fully_replicated)
    same_next = out["next_loss"] == out["next_loss_restored"]
    save = out["save"]
    log("sharded", f"full-sync checkpoint of step {save.step}: "
                   f"{save.bytes_written} B in {save.duration_s:.3f}s; node "
                   f"failure; launcher restore ({out['restore_kind']}) "
                   f"bit-exact {out['bit_exact']}, every leaf in the "
                   f"compiled step's sharding {same_sharding}, every leaf on "
                   f"all {len(everyone)} chips {on_all} ({split} of "
                   f"{len(leaves)} leaves split across chips); next loss "
                   f"live {out['next_loss']}, restored "
                   f"{out['next_loss_restored']} -> equal {same_next}")
    if not (ok_loss and out["bit_exact"] and same_sharding and on_all
            and same_next and save.kind == "full"
            and out["restore_kind"] == "full"):
        raise SystemExit("sharded: a check failed")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import use_compile_cache
    cache = use_compile_cache()
    cfg = model_config()
    log("setup", f"{len(devices)} x {dev.device_kind}; model {ARCH} at its "
                 f"published widths (d_model {cfg.d_model}, d_ff {cfg.d_ff},"
                 f" heads {cfg.num_heads}/{cfg.num_kv_heads}); cuts: layers "
                 f"32 -> {cfg.num_layers}, vocab 64000 -> {cfg.vocab_size};"
                 f" batch {BATCH} x {SEQ_LEN}; seed {SEED}; compile cache "
                 f"{cache}")
    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 4:
            sharded_phase(cfg, devices[:4], workdir)
        else:
            from repro.kernels.ckpt_delta.ops import default_interpret
            limit = hbm("bytes_limit")
            reckoning = reckon(cfg, limit)
            chosen = choose_state_dtypes(reckoning, limit)
            for codec in ("lossless", "int8"):
                layout = reckoning[chosen[f"device-{codec}"]]["layout"]
                kernels_phase(codec, layout, default_interpret())
                gc.collect()
            trainer_phase(cfg, chosen, workdir)
            campaign_phase()
    log("done", f"all phases passed in {time.perf_counter() - t_start:.3f}s; "
                f"peak HBM {hbm('peak_bytes_in_use')} B")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
