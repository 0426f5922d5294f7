"""A whole run of the harness on the CPU at a tiny size, with the chip
check skipped: a sound run is correct, and each fault a cell can have,
and the lower-precision control, make ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import harness

BENCH = Path(harness.__file__).resolve().parent
ROOT = BENCH.parent
SECONDS = 1.5


def quick(mix: dict, interval: float) -> dict:
    return {**mix, "plan": {**mix["plan"], "interval_s": interval}}


def run(workload: str, interval: float, bench_dir: Path = BENCH,
        root: Path = ROOT, **kw) -> dict:
    bench = harness.load_benchmark(root)
    cell = harness.cell_spec(bench, workload)
    cfg = harness.load_config(cell["config"], bench_dir)
    cfg = harness.arch_module(cfg, bench_dir).tiny(cfg)
    mix = quick(harness.load_mix(cell["traffic"], bench_dir), interval)
    out = harness.run_cell(cell, cfg, mix, 2 ** 31 + 9, SECONDS, None,
                           time.perf_counter(), bench_dir=bench_dir, **kw)
    correct, checks = harness.compare(out["checks"], cfg)
    return {"correct": correct, "checks": checks, "run": out}


def over(result: dict) -> list:
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]


def files_as_they_are(bench_dir: Path) -> None:
    """The benchmark's configurations, architectures and mixes, copied
    unchanged into ``bench_dir``, beside which a test adds its own."""
    for kind in ("configs", "archs", "traffic"):
        shutil.copytree(BENCH / kind, bench_dir / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_a_new_mix_is_picked_up_by_name(tmp_path):
    """A traffic file and a workloads entry, and no edit to any file the
    benchmark has, make a cell the harness runs."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "yi-6b.fresh", "config": "yi-6b", "traffic": "fresh",
        "chips": 1, "why": "a mix added as data alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    files_as_they_are(tmp_path)
    mix = harness.load_mix("int8-device")
    (tmp_path / "traffic" / "fresh.json").write_text(json.dumps(mix))
    r = run("yi-6b.fresh", 0.5, bench_dir=tmp_path, root=tmp_path)
    assert r["correct"], over(r)
    details = r["run"]["details"]
    assert details["saves_in_window"] >= 1
    assert r["checks"]["int8_error_ratio"]["value"] <= 1.0


def test_a_new_config_is_picked_up_by_name(tmp_path):
    """An architecture module, a configuration that names it and carries
    its own limits, and a workloads entry, and no edit to any file the
    benchmark has, make a cell the harness runs."""
    files_as_they_are(tmp_path)
    (tmp_path / "archs" / "fresh_dense.py").write_text(
        (BENCH / "archs" / "dense.py").read_text())
    cfg = dict(harness.load_config("internlm2-20b"), name="fresh",
               arch="fresh_dense",
               limits={"loss_gap": 1e-3, "grad1_gap": 1e-3,
                       "change_gap": 5e-3})
    (tmp_path / "configs" / "fresh.json").write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "fresh", "source": cfg["source"],
        "file": "perfbench/configs/fresh.json", "reduced": [],
        "why": "an architecture added as files alone"})
    bench["workloads"].append({
        "name": "fresh.train", "config": "fresh", "traffic": "train",
        "chips": 1, "why": "a configuration added as files alone"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    module = harness.arch_module(cfg, tmp_path)
    assert Path(module.__file__) == tmp_path / "archs" / "fresh_dense.py"
    r = run("fresh.train", 600.0, bench_dir=tmp_path, root=tmp_path)
    assert r["correct"], over(r)
    # the configuration's own limits are the ones compared
    assert r["checks"]["grad1_gap"]["limit"] == 1e-3


@pytest.mark.parametrize(
    "config", [c["name"] for c in harness.load_benchmark()["configs"]])
def test_a_sound_run_is_correct(config, tmp_path):
    """Each configuration at its architecture's tiny size, through the
    train mix: its first steps agree with the reference, its steps are
    finite and its stream is trained exactly once."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{
        "name": f"{config}.sound", "config": config, "traffic": "train",
        "chips": 1, "why": "a sound run of the configuration"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run(f"{config}.sound", 600.0, root=tmp_path)
    assert r["correct"], over(r)
    assert r["run"]["details"]["run"]["steps"] >= 1


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
