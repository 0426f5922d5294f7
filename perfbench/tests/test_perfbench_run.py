"""A whole run of the harness on the CPU at a tiny size, with the chip
check skipped: a sound run is correct, and each fault a cell can have,
and the lower-precision control, make ``correct`` false."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

import harness

BENCH = Path(harness.__file__).resolve().parent
ROOT = BENCH.parent
SECONDS = 1.5


def tiny(cfg: dict) -> dict:
    cfg = dict(cfg, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=500)
    cfg["job"] = dict(cfg["job"], seq_len=32, batch=4)
    return cfg


def quick(mix: dict, interval: float) -> dict:
    return {**mix, "plan": {**mix["plan"], "interval_s": interval}}


def run(workload: str, interval: float, bench_dir: Path = BENCH,
        root: Path = ROOT, **kw) -> dict:
    bench = harness.load_benchmark(root)
    cell = harness.cell_spec(bench, workload)
    cfg = tiny(harness.load_config(cell["config"]))
    mix = quick(harness.load_mix(cell["traffic"], bench_dir), interval)
    out = harness.run_cell(cell, cfg, mix, 2 ** 31 + 9, SECONDS, None,
                           time.perf_counter(), **kw)
    correct, checks = harness.compare(out["checks"], cfg["name"])
    return {"correct": correct, "checks": checks, "run": out}


def over(result: dict) -> list:
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


def test_a_new_mix_is_picked_up_by_name(tmp_path):
    """A traffic file and a workloads entry, and no edit to any file the
    benchmark has, make a cell the harness runs."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "yi-6b.fresh", "config": "yi-6b", "traffic": "fresh",
        "chips": 1, "why": "a mix added as data alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "traffic").mkdir()
    mix = harness.load_mix("int8-device")
    (tmp_path / "traffic" / "fresh.json").write_text(json.dumps(mix))
    r = run("yi-6b.fresh", 0.5, bench_dir=tmp_path, root=tmp_path)
    assert r["correct"], over(r)
    details = r["run"]["details"]
    assert details["saves_in_window"] >= 1
    assert r["checks"]["int8_error_ratio"]["value"] <= 1.0


def test_restores_are_checked(monkeypatch):
    r = run("yi-6b.full-host.kill", 0.5)
    assert r["correct"], over(r)
    details = r["run"]["details"]
    # how many steps are re-done before the next due trigger ends the
    # window depends on the host's speed; each of them is compared
    assert details["restores"] >= 1 and details["redone_steps"] >= 1
    # the window's own save is the one restored
    assert details["run"]["saves"] and details["run"]["saves"][0][0] == "full"

    # fault: a restore that returns one element altered
    from repro.checkpoint.manager import CheckpointManager
    orig = CheckpointManager.restore

    def altered(self, treedef_like, failure_kind="task"):
        report = orig(self, treedef_like, failure_kind)
        scale = np.array(report.state["params"]["final_norm"]["scale"])
        scale[0] += 1.0
        report.state["params"]["final_norm"]["scale"] = scale
        return report

    monkeypatch.setattr(CheckpointManager, "restore", altered)
    r = run("yi-6b.full-host.kill", 0.5)
    assert not r["correct"]
    assert {"restore_mismatches", "redo_loss_gap"} <= set(over(r))


def test_a_full_save_written_wrong_fails(monkeypatch):
    """A save whose files hold one element altered: the read-back from
    disk finds it, whatever the restore then does with it."""
    orig = np.savez

    def altered(path, **arrays):
        name = next(k for k, v in arrays.items()
                    if v.dtype == np.float32 and v.size)
        x = np.array(arrays[name])
        x.reshape(-1)[0] += 1.0
        return orig(path, **{**arrays, name: x})

    monkeypatch.setattr(np, "savez", altered)
    r = run("yi-6b.full-host.kill", 0.5)
    assert not r["correct"] and "save_mismatches" in over(r)


def test_an_altered_delta_payload_fails(monkeypatch):
    from repro.checkpoint.pipeline import DeltaLeafSource
    orig = DeltaLeafSource.flat_payload

    def altered(self):
        payload = orig(self)
        if not isinstance(payload.get("q"), np.ndarray):
            return payload
        q = payload["q"].reshape(-1, 1024).copy()
        q[:, 0] = np.where(q[:, 0] >= 0, q[:, 0] - 2, q[:, 0] + 2)
        return {**payload, "q": q.reshape(-1)}

    monkeypatch.setattr(DeltaLeafSource, "flat_payload", altered)
    r = run("yi-6b.int8-device", 0.5)
    assert not r["correct"] and "int8_error_ratio" in over(r)


def faulty_step(monkeypatch, fault):
    from repro.models import zoo
    orig = zoo.make_train_step

    def make(*a, **k):
        step = orig(*a, **k)

        def train_step(state, batch):
            return fault(step, state, batch)
        return train_step

    monkeypatch.setattr(zoo, "make_train_step", make)


def test_a_step_that_leaves_its_state_unchanged_fails(monkeypatch):
    faulty_step(monkeypatch, lambda step, state, batch:
                (state, step(state, batch)[1]))
    r = run("internlm2-20b.train", 600.0)
    assert not r["correct"] and "change_gap" in over(r)


def test_half_the_batch_left_out_fails(monkeypatch):
    faulty_step(monkeypatch, lambda step, state, batch: step(
        state, {k: v[: v.shape[0] // 2] for k, v in batch.items()}))
    r = run("internlm2-20b.train", 600.0)
    assert not r["correct"]
    assert {"grad1_gap", "change_gap"} & set(over(r))


def test_the_bfloat16_control_fails():
    """The program's own lower-precision path: parameters and Adam
    moments in bfloat16 where the configuration states float32."""
    r = run("internlm2-20b.train", 600.0,
            model_override={"param_dtype": "bfloat16"},
            opt_override={"state_dtype": "bfloat16"})
    assert not r["correct"] and "change_gap" in over(r)


def test_run_refuses_a_machine_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "yi-6b.int8-device", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "cpu" in p.stderr
