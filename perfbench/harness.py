"""One run of one benchmark cell: build the job the way the program's
examples build it, warm it up, measure a window on the host's clock, and
decide ``correct`` against the plain references.

The job is a ``ResilientTrainer`` fed by the benchmark's ``EventSource``,
checkpointed by its ``CheckpointManager`` under the mix's
``CheckpointPlan``, and supervised by a ``KhaosRuntime`` attached
through ``TrainerJobHandle`` and stepped after every train step.  The
runtime's constraints cannot be violated, so it decides but never
actuates and the plan stays pinned.  Steps, saves, failures and restores
all go through the trainer's own loop (``ResilientTrainer.run``); the
harness only wraps the program's entry points on this one job's objects
to time and record them, and raises ``StopRun`` from the per-step
callback to end a phase.

What belongs to one configuration, one traffic mix or one per-layer
metric is in files of its own, found by name, so that a new one is added
as files alone:

  * a configuration is ``configs/<config>.json``: its sizes, its job, the
    limits its training numbers are held to (``limits``, over the common
    ones of ``limits.json``) and the name of its architecture (``arch``);
  * an architecture is ``archs/<arch>.py``: everything that depends on the
    model's shape (the program's ``ModelConfig``, the plain reference's
    weights and loss, the parameters' shapes, the FLOPs of a token, and
    the CPU tests' size);
  * a traffic mix is ``traffic/<traffic>.json``;
  * a per-layer metric is ``metrics/<metric>.py``, whose ``read`` takes a
    ``RunView``.
"""
from __future__ import annotations

import copy
import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

import numpy as np

import arith
import reference
import trace as tracing
from traffic import EventSource

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMMON_LIMITS = json.loads((BENCH / "limits.json").read_text())["limits"]


class StopRun(Exception):
    """Raised from the per-step callback to end a phase of the run."""


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(name: str, bench_dir: Path = BENCH) -> dict:
    return json.loads((bench_dir / "configs" / f"{name}.json").read_text())


def load_mix(name: str, bench_dir: Path = BENCH) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    return _load_module(bench_dir / "metrics" / f"{name}.py",
                        f"perfbench_metric_{name}").read


def arch_module(cfg: dict, bench_dir: Path = BENCH):
    """The configuration's architecture module, ``archs/<arch>.py``."""
    arch = cfg["arch"]
    return _load_module(bench_dir / "archs" / f"{arch}.py",
                        f"perfbench_arch_{arch}")


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The cell's ``end_to_end`` or ``per_layer`` metrics."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def program_seed(seed: int) -> int:
    """The seed the program gets: any whole number, folded to 31 bits."""
    return int(seed) % (2 ** 31 - 1)


# ---------------------------------------------------------------------------
# the program's configuration objects, from the benchmark's files
# ---------------------------------------------------------------------------

def model_config(cfg: dict, bench_dir: Path = BENCH, **override):
    return arch_module(cfg, bench_dir).model_config(cfg, **override)


def optimizer_config(cfg: dict, **override):
    from repro.config import OptimizerConfig
    return OptimizerConfig(**{**cfg["job"]["optimizer"], **override})


# ---------------------------------------------------------------------------
# witness: what a state held, read on the device after each save/restore
# ---------------------------------------------------------------------------

def leaf_names(tree) -> list:
    import jax
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def sample_groups(state, n: int, seed: int) -> list:
    """(leaf index, group) pairs drawn from ``seed``: at least one group
    of every f32 leaf, the rest in proportion to the leaves' groups."""
    import jax
    leaves = jax.tree_util.tree_leaves(state)
    f32 = [i for i, x in enumerate(leaves)
           if np.dtype(x.dtype) == np.float32 and x.size]
    g = np.array([arith.groups(leaves[i].size) for i in f32])
    rng = np.random.default_rng(seed)
    extra = rng.multinomial(max(0, n - len(f32)), g / g.sum())
    pairs = []
    for i, k in zip(f32, 1 + extra):
        k = min(int(k), int(arith.groups(leaves[i].size)))
        for grp in sorted(rng.choice(arith.groups(leaves[i].size), k,
                                     replace=False)):
            pairs.append((i, int(grp)))
    return pairs


def make_witness(state, pairs: list):
    """A jitted function of a state: per leaf, two uint32 sums of its
    bits (plain, and weighted by position), and the 1024 elements of each
    sampled group (zero past a leaf's end, as the codec pads)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    by_leaf: dict = {}
    for i, g in pairs:
        by_leaf.setdefault(i, []).append(g)

    def bits(x):
        if x.dtype.itemsize == 4:
            return lax.bitcast_convert_type(x, jnp.uint32)
        if x.dtype.itemsize == 2:
            return lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        return x.astype(jnp.uint32)

    def digest(x):
        b = bits(x)
        pos = jnp.zeros(x.shape, jnp.uint32)
        stride = 1
        for ax in reversed(range(x.ndim)):
            pos = pos + lax.broadcasted_iota(jnp.uint32, x.shape, ax) \
                * jnp.uint32(stride % 2 ** 32)
            stride *= x.shape[ax]
        w = pos * jnp.uint32(2654435761) + jnp.uint32(1)
        return jnp.stack([jnp.sum(b, dtype=jnp.uint32),
                          jnp.sum(b * w, dtype=jnp.uint32)])

    def gather(x, grps):
        idx = (np.asarray(grps)[:, None] * arith.GROUP
               + np.arange(arith.GROUP)[None, :])
        valid = idx < x.size
        multi = np.unravel_index(np.minimum(idx, x.size - 1), x.shape)
        return jnp.where(valid, x[multi], 0.0).astype(jnp.float32)

    def perfbench_witness(state):
        leaves = jax.tree_util.tree_leaves(state)
        digests = jnp.stack([digest(x) for x in leaves])
        samples = [gather(leaves[i], by_leaf[i]) for i in sorted(by_leaf)]
        return digests, jnp.concatenate(samples)

    # compiled once for the state's shapes on its device, so a restored
    # (committed) state runs the same executable as the live one
    dev = jax.tree_util.tree_leaves(state)[0].devices().pop()
    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=jax.sharding.SingleDeviceSharding(dev)),
        state)
    compiled = jax.jit(perfbench_witness).lower(specs).compile()
    order = [(i, g) for i in sorted(by_leaf) for g in by_leaf[i]]
    return compiled, order


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

@dataclass
class Records:
    steps: list = field(default_factory=list)        # (t, first, n)
    log: list = field(default_factory=list)          # exactly-once log
    saves: list = field(default_factory=list)
    restores: list = field(default_factory=list)
    kills: list = field(default_factory=list)        # (t_fail, t_resumed)
    controller_s: list = field(default_factory=list)
    batches: list = field(default_factory=list)      # first set-up batches
    losses: list = field(default_factory=list)       # (first offset, loss)


class Job:
    """The trainer, its checkpoint plane and its supervisor, with the
    harness's records around the program's entry points."""

    def __init__(self, cfg: dict, mix: dict, seed: int, ckpt_dir: str,
                 model_override: Optional[dict] = None,
                 opt_override: Optional[dict] = None,
                 bench_dir: Path = BENCH):
        from repro.config import CheckpointPlan, KhaosConfig
        from repro.core import KhaosRuntime, demo_prior_models
        from repro.runtime import (ResilientTrainer, TrainerConfig,
                                   TrainerJobHandle)
        from repro.sim import SimCostModel

        job = cfg["job"]
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.batch, self.seq_len = job["batch"], job["seq_len"]
        self.source = EventSource(self.batch)
        plan = CheckpointPlan(**mix["plan"])
        tcfg = TrainerConfig(batch=self.batch, seq_len=self.seq_len,
                             ckpt_dir=ckpt_dir, time_scale=1.0,
                             detect_s=0.0, restart_s=0.0, plan=plan)
        self.trainer = ResilientTrainer(
            model_config(cfg, bench_dir, **(model_override or {})), tcfg,
            self.source, optimizer_config(cfg, **(opt_override or {})),
            seed=seed)
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
        self.rec = Records()
        self.witness = None
        self.witness_order: list = []
        self.state_names: list = []
        self.on_step: Optional[Callable] = None
        self.keep_batches = 0
        self._instrument()

    # -- wrappers around the program's entry points -------------------------
    def _instrument(self) -> None:
        import jax
        tr, mgr, rec = self.trainer, self.trainer.ckpt, self.rec
        span = jax.profiler.TraceAnnotation

        def witness_of(state):
            if self.witness is None:
                self.state_names = leaf_names(state)
                pairs = sample_groups(state, self.mix["sample_groups"],
                                      self.seed)
                self.witness, self.witness_order = make_witness(state, pairs)
            return self.witness(state)

        def wrap_save(fn, kind):
            def save(step, state, timestamp=0.0, extra=None):
                t0 = time.perf_counter()
                with span("perfbench.save"):
                    report = fn(step, state, timestamp, extra)
                t1 = time.perf_counter()
                # dispatched after the save returns, so the trigger's
                # blocking time does not include it
                wit = witness_of(state) if report else None
                rec.saves.append({
                    "t0": t0, "t1": t1, "step": int(step), "call": kind,
                    "kind": report.kind, "blocking_s": report.blocking_s,
                    "report": report, "witness": wit,
                    "cursor": copy.deepcopy((extra or {}).get("pipeline"))})
                return report
            return save

        def restore(treedef_like, failure_kind="task"):
            t0 = time.perf_counter()
            with span("perfbench.restore"):
                report = restore_fn(treedef_like, failure_kind)
            rec.restores.append({
                "t0": t0, "t1": time.perf_counter(), "step": report.step,
                "duration_s": report.duration_s, "kind": report.kind,
                "level": report.level,
                "cursor": copy.deepcopy(report.extra.get("pipeline"))})
            return report

        def batcher_restore(state):
            batcher_restore_fn(state)
            # the trainer placed the restored state just before this call
            rec.restores[-1]["witness"] = witness_of(tr.state)
            rec.log.append(("restore", tr.batcher.cursor.offset))

        def next_batch():
            with span("perfbench.batch"):
                b = next_batch_fn()
            if b is not None and len(rec.batches) < self.keep_batches:
                rec.batches.append((b["first_offset"],
                                    np.array(b["tokens"]),
                                    np.array(b["labels"])))
            return b

        def step_fn(state, batch):
            with span("perfbench.step"):
                return compiled(state, batch)

        restore_fn = mgr.restore
        batcher_restore_fn = tr.batcher.restore
        next_batch_fn = tr.batcher.next_batch
        compiled = tr.step_fn
        mgr.save = wrap_save(mgr.save, "save")
        mgr.savepoint = wrap_save(mgr.savepoint, "savepoint")
        mgr.restore = restore
        tr.batcher.restore = batcher_restore
        tr.batcher.next_batch = next_batch
        tr.step_fn = step_fn

    def _on_second(self, sample: dict) -> None:
        import jax
        t = time.perf_counter()
        off = self.trainer.batcher.cursor.offset
        self.rec.steps.append((t, off - self.batch, self.batch))
        self.rec.log.append(("step", off - self.batch, self.batch))
        self.rec.losses.append((off - self.batch, sample["loss"]))
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("perfbench.controller"):
            self.rt.step()
        self.rec.controller_s.append(time.perf_counter() - c0)
        if self.on_step is not None:
            self.on_step(t, off)

    def drive(self, on_step: Callable) -> None:
        """Run the trainer's loop until ``on_step(t, cursor)`` raises
        ``StopRun``."""
        self.on_step = on_step
        try:
            self.trainer.run(1e12, on_second=self._on_second)
        except StopRun:
            pass
        finally:
            self.on_step = None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def host_params(state) -> dict:
    import jax
    return {n: np.asarray(x) for n, x in zip(leaf_names(state["params"]),
                                             jax.tree_util.tree_leaves(
                                                 state["params"]))}


def peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", -1)
               for d in jax.local_devices())


def gap(prog: float, ref: float, floor: float) -> float:
    return abs(prog - ref) / max(abs(ref), floor)


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """The largest gap between the program's and the reference's norm of
    a leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    names = [n for n in ref if keep is None or keep(n)]
    med = float(np.median([ref[n] for n in names]))
    worst = max(names, key=lambda n: gap(prog[n], ref[n], med))
    return gap(prog[worst], ref[worst], med), worst


def training_numbers(prog: dict, ref: dict) -> dict:
    """The three numbers a training cell compares: the worst relative gap
    of the first steps' losses, of the first clipped gradient's leaf norms,
    and of the parameters' change after the last of them.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    loss = max(gap(p, r, 0.0) for p, r in zip(prog["losses"], ref["losses"]))
    g, g_leaf = worst_leaf_gap(prog["grad1"], ref["grad1"])
    med = float(np.median(list(ref["grad1"].values())))
    moved = {n for n, v in ref["grad1"].items() if v >= 1e-3 * med}
    c, c_leaf = worst_leaf_gap(prog["change"], ref["change"],
                               keep=lambda n: n in moved)
    return {"loss_gap": loss, "grad1_gap": g, "grad1_leaf": g_leaf,
            "change_gap": c, "change_leaf": c_leaf,
            "left_out": sorted(set(ref["grad1"]) - moved)}


def program_first_steps(job: Job, n: int) -> dict:
    """Drive the job's first ``n`` steps through its own loop and read
    what the reference is compared on: the losses, the first gradient as
    Adam's first moment holds it after step 1, and the parameters' change
    after step ``n``."""
    import jax
    import jax.numpy as jnp
    tr = job.trainer
    b1 = job.cfg["job"]["optimizer"]["b1"]
    p0 = host_params(tr.state)
    out: dict = {}
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))

    def on_step(t, off):
        k = len(job.rec.steps)
        if k == 1:
            m = tr.state["opt"]["m"]
            out["grad1"] = {nm: float(v) / (1.0 - b1) for nm, v in zip(
                leaf_names(m), jax.tree_util.tree_leaves(norms(m)))}
        if k >= n:
            raise StopRun

    job.keep_batches = n
    job.drive(on_step)
    p3 = host_params(tr.state)
    out["change"] = {nm: float(np.linalg.norm(
        (p3[nm].astype(np.float32) - p0[nm].astype(np.float32))
        .astype(np.float64).ravel())) for nm in p0}
    out["losses"] = list(tr.losses[:n])
    return out


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
             trace_dir: Optional[str], t_start: float,
             model_override: Optional[dict] = None,
             opt_override: Optional[dict] = None,
             first_steps: int = 3, bench_dir: Path = BENCH) -> dict:
    """One run.  Returns the records, the metrics' raw inputs and the
    checks; ``summarize`` turns them into the result line."""
    import jax
    pseed = program_seed(seed)
    ckpt_dir = tempfile.mkdtemp(prefix="perfbench-ckpt-")
    try:
        return _run(cell, cfg, mix, seed, pseed, seconds, trace_dir, t_start,
                    ckpt_dir, model_override, opt_override, first_steps,
                    bench_dir)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _run(cell, cfg, mix, seed, pseed, seconds, trace_dir, t_start, ckpt_dir,
         model_override, opt_override, first_steps, bench_dir) -> dict:
    import jax
    job = Job(cfg, mix, pseed, ckpt_dir, model_override, opt_override,
              bench_dir)
    tr, rec = job.trainer, job.rec

    # ---- set-up: the first steps (checked against the reference), then
    # whatever the mix needs before its window
    prog = program_first_steps(job, first_steps)
    setup = mix["setup_until"]
    if setup == "first_save":
        def until_save(t, off):
            if rec.saves:
                raise StopRun
        job.drive(until_save)
        tr.ckpt.wait()
    elif setup == "due":
        # up to the step boundary after which the next trigger fires
        def until_due(t, off):
            if tr.policy.due(tr.t):
                raise StopRun
        job.drive(until_due)
    elif setup != "steps":
        raise ValueError(f"unknown setup_until {setup!r}")
    if mix["stream"]["kind"] != "saturating":
        raise ValueError(f"unknown stream kind {mix['stream']['kind']!r}")
    # what set-up wrote reaches the disk before the window, so the window's
    # own writes do not queue behind it
    os.sync()
    failure = mix.get("failure")
    n_setup_saves, n_setup_steps = len(rec.saves), len(rec.steps)

    # ---- window
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    # a window anchored at a due trigger starts here, at set-up's cursor,
    # so the trigger it opens with is inside it
    start = [(t_window, *rec.steps[-1][1:])] if setup == "due" else []
    state = {"since_save": 0, "killed": True, "save_done_at": None,
             "resume_pending": None, "first": None, "raised": 0}

    def cycles_done() -> bool:
        """Every run holds the same whole number of cycles (steps, a save,
        and any failure and restore), wherever the triggers fall in time:
        the window ends at the first step boundary of the phase it started
        in, once the mix's cycles are done."""
        if len(rec.saves) - n_setup_saves < mix["cycles"]:
            return False
        if failure is not None and state["raised"] < mix["cycles"]:
            return False
        if setup == "due":
            return tr.policy.due(tr.t)
        return state["since_save"] == 1      # the first step after a save

    def in_window(t, off):
        if state["first"] is None:
            state["first"] = t
        if state["resume_pending"] is not None:
            rec.kills.append((state["resume_pending"], t))
            state["resume_pending"] = None
        last = rec.saves[-1] if rec.saves else None
        if last is not None and last["t1"] != state["save_done_at"]:
            # a save completed before this step: a new cycle begins
            state["save_done_at"] = last["t1"]
            state["since_save"], state["killed"] = 0, False
        state["since_save"] += 1
        if mix["window"] == "cycles":
            if cycles_done():
                raise StopRun
        elif t - state["first"] >= seconds:
            raise StopRun
        if (failure is not None and not state["killed"]
                and state["since_save"] >= failure["steps_after_save"]):
            state["killed"] = True
            state["raised"] += 1
            tr.inject_failure_at(tr.t, failure["kind"])
            state["resume_pending"] = time.perf_counter()

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    win_span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
    win_span.__enter__()
    job.drive(in_window)
    win_span.__exit__(None, None, None)
    t_end = time.perf_counter()
    if trace_dir:
        jax.block_until_ready(tr.state)
        jax.profiler.stop_trace()
    window_steps = rec.steps[n_setup_steps:]

    # ---- after the window: quiesce, read the memory peak and free the
    # program's state
    tr.ckpt.wait()
    memory_peak = peak_bytes()
    losses = list(tr.losses)
    stats = tr.ckpt.stats()
    saves = rec.saves
    for s in saves:
        if s["witness"] is not None:
            s["witness"] = jax.device_get(s["witness"])
    for r in rec.restores:
        r["witness"] = jax.device_get(r.get("witness"))
    pinned = not job.handle.reconfigurations and not job.handle.plan_changes
    order, names = job.witness_order, job.state_names
    final_cursor = tr.batcher.cursor.offset
    del job, tr
    gc.collect()

    # ---- checks
    checks, details = {}, {}
    events = [reference.event_tokens(
        range(off, off + cfg["job"]["batch"]), cfg["job"]["seq_len"],
        cfg["vocab_size"], pseed) for off, _, _ in rec.batches]
    ref = reference.first_steps(cfg, pseed,
                                [(e[:, :-1], e[:, 1:]) for e in events],
                                arch=arch_module(cfg, bench_dir))
    nums = training_numbers(prog, ref)
    details.update(nums)
    checks["loss_gap"] = nums["loss_gap"]
    checks["grad1_gap"] = nums["grad1_gap"]
    checks["change_gap"] = nums["change_gap"]
    checks["input_mismatches"] = sum(
        int(not (np.array_equal(tok, e[:, :-1])
                 and np.array_equal(lab, e[:, 1:])))
        for (_, tok, lab), e in zip(rec.batches, events))
    checks["nonfinite_losses"] = int(np.sum(~np.isfinite(losses)))
    checks["async_errors"] = len(stats["async_errors"])
    checks["plan_changes"] = int(not pinned)
    checks.update(checkpoint_checks(rec, saves, n_setup_saves, order, names,
                                    ckpt_dir, details))
    # every failure raised is followed by a restore
    checks["restore_mismatches"] += max(0, state["raised"]
                                        - len(rec.restores))
    if failure is not None:
        checks["redo_loss_gap"], details["redone_steps"] = redo_loss_gap(
            rec.losses)
    kept = arith.kept_offsets(rec.log)
    contiguous = all(a[0] + a[1] == b[0] for a, b in zip(kept, kept[1:]))
    checks["exactly_once_gaps"] = int(
        not contiguous or not kept or kept[-1][0] + kept[-1][1]
        != final_cursor)
    details["run"] = {
        "window_s": t_end - t_window, "steps": len(window_steps),
        "saves": [[s["kind"], s["blocking_s"], s["t1"] - s["t0"],
                   getattr(s["report"], "bytes_written", 0)]
                  for s in saves[n_setup_saves:]],
        "restores": [[r["duration_s"], r["t1"] - r["t0"]]
                     for r in rec.restores],
        "resumes": [b - a for a, b in rec.kills],
        "bytes_written": sum(getattr(s["report"], "bytes_written", 0)
                             for s in saves)}
    return {
        "setup_s": setup_s, "seconds": seconds, "window": (t_window, t_end),
        "window_steps": window_steps, "boundaries": start + window_steps,
        "records": rec, "window_saves": saves[n_setup_saves:],
        "controller_window": rec.controller_s[
            n_setup_steps:n_setup_steps + len(window_steps)],
        "memory_peak_bytes": memory_peak, "checks": checks,
        "details": details, "losses": losses,
    }


def redo_loss_gap(losses: list) -> tuple[float, int]:
    """Steps re-done after a restore train the same events from the state
    that was saved, so each loss has to equal the one first read for the
    same stream offset: the worst relative gap, and how many were re-done."""
    first: dict = {}
    worst, redone = 0.0, 0
    for off, loss in losses:
        if off in first:
            redone += 1
            worst = max(worst, gap(loss, first[off], 0.0)
                        if math.isfinite(loss) else math.inf)
        else:
            first[off] = loss
    return worst, redone


def checkpoint_checks(rec: Records, saves: list, n_setup: int, order: list,
                      names: list, ckpt_dir: str, details: dict) -> dict:
    """Every save of the window against the state it snapshotted, read
    back from its files by the plain readers, and every restore against
    the save it restored."""
    out = {"save_mismatches": 0, "restore_mismatches": 0}
    window = saves[n_setup:]
    base = None
    for s in saves:
        if s["kind"] == "full":
            base = s
        if s not in window:
            continue
        if s["kind"] == "delta":
            out.setdefault("int8_error_ratio", 0.0)
            ratio, bad = check_delta(s, base, order, names, ckpt_dir)
            out["int8_error_ratio"] = max(out["int8_error_ratio"], ratio)
            out["save_mismatches"] += bad
        elif s["kind"] == "full":
            out["save_mismatches"] += check_full(s, order, names, ckpt_dir)
        else:
            out["save_mismatches"] += 1          # skipped: a save not made
    for r in rec.restores:
        before = [s for s in saves if s["t1"] <= r["t0"]]
        want = before[-1] if before else None
        ok = (want is not None and r["step"] == want["step"]
              and r["cursor"] == want["cursor"]
              and r["witness"] is not None
              and np.array_equal(r["witness"][0], want["witness"][0]))
        out["restore_mismatches"] += int(not ok)
    details["saves_in_window"] = len(window)
    details["restores"] = len(rec.restores)
    return out


def check_full(s: dict, order: list, names: list, ckpt_dir: str) -> int:
    """A full save read back from its files: the manifest's step and
    stream cursor, every leaf's bit sums and the sampled groups, each
    against what the witness read on the device when the save was made."""
    path = os.path.join(ckpt_dir, "local", f"step_{s['step']:010d}")
    try:
        man, leaves = reference.read_full_save(path)
    except (OSError, KeyError, ValueError):
        return 1
    if (man["step"] != s["step"]
            or man["extra"].get("pipeline") != s["cursor"]
            or sorted(leaves) != sorted(names)):
        return 1
    digests = np.stack([reference.bit_digest(leaves[n]) for n in names])
    samples = reference.group_samples([leaves[names[i]] for i, _ in order],
                                      [g for _, g in order])
    return int(not (np.array_equal(digests, s["witness"][0])
                    and np.array_equal(samples.view(np.uint32),
                                       s["witness"][1].view(np.uint32))))


def check_delta(s: dict, base: dict, order: list, names: list,
                ckpt_dir: str) -> tuple[float, int]:
    """A device int8 delta against the witnessed state and base: the
    manifest's step, base and stream cursor, and the sampled groups decoded
    by the reference codec within its stated bound."""
    path = os.path.join(ckpt_dir, "local", f"delta_{s['step']:010d}")
    d = reference.read_flat_delta(path)
    man = d["manifest"]
    bad = int(man["step"] != s["step"] or man["base_step"] != base["step"]
              or man["extra"].get("pipeline") != s["cursor"])
    new, old = s["witness"][1], base["witness"][1]
    rows = np.array([d["layout"][names[i]][0] // arith.GROUP + g
                     for i, g in order])
    delta = new.astype(np.float32) - old.astype(np.float32)
    decoded = reference.decode_groups(d["q"][rows], d["s"][rows])
    return reference.int8_error_ratio(decoded, delta), bad


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

@dataclass
class RunView:
    """What a per-layer metric's reader is given."""
    cfg: dict
    peaks: dict
    run: dict
    trace: Optional[dict]           # trace.summarize of the window
    steps_traced: int


def end_to_end(name: str, run: dict, cfg: dict) -> Optional[float]:
    """The host-clock end-to-end metrics."""
    if name == "setup_s":
        return run["setup_s"]
    if name == "goodput_tokens_per_s":
        return arith.goodput([(t, a + n) for t, a, n in run["boundaries"]],
                             cfg["job"]["seq_len"])
    if name == "resume_s":
        return arith.resume_s(run["records"].kills)
    raise KeyError(f"no end-to-end metric {name!r}")


def limits(cfg: dict) -> dict:
    """The limits a configuration's numbers are held to: its own over the
    common ones."""
    return {**COMMON_LIMITS, **cfg["limits"]}


def compare(checks: dict, cfg: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when none is over.  A
    number whose limit is null is not compared for that configuration."""
    out, ok = {}, True
    lim = limits(cfg)
    for name, value in checks.items():
        limit = lim[name]
        if limit is None:
            continue
        out[name] = {"value": value, "limit": limit}
        ok &= value <= limit
    return ok, out


def result(bench: dict, cell: dict, cfg: dict, run: dict, device: dict,
           trace_dir: Optional[str]) -> dict:
    workload = cell["name"]
    metrics, failed = {}, run["checks"]["nonfinite_losses"]
    attempted = len(run["window_steps"])
    out = {}
    if trace_dir is None:
        for m in cell_metrics(bench, workload, "end_to_end"):
            v = end_to_end(m["name"], run, cfg)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        tr = tracing.load(trace_dir)
        w = tracing.window_of(tr)
        summary = tracing.summarize(tr, *w) if w else None
        t0, t1 = run["window"]
        view = RunView(cfg, arith.peaks(device["kind"]), run, summary,
                       sum(1 for t, _, _ in run["window_steps"]
                           if t0 <= t <= t1))
        for m in cell_metrics(bench, workload, "per_layer"):
            v = metric_reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device = {**device, "busy_s": summary["busy_s"],
                      "window_s": summary["window_s"]}
            out["breakdown"] = {
                "device_ops": [[k, v] for k, v in summary["device_ops"]],
                "idle_gaps": [[k, v] for k, v in summary["idle_gaps"]]}
    correct, checks = compare(run["checks"], cfg)
    return {"correct": bool(correct), "attempted": attempted,
            "failed": int(failed), "metrics": metrics,
            "device": {**device, "memory_peak_bytes": run["memory_peak_bytes"]},
            **out, "checks": checks}
