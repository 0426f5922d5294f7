"""The readers of the program's spans, on whole runs of the harness on the
CPU at a tiny size: each reads a finite value in the cells it lists, the
spans agree with the reports the program returns, and a reader finds
nothing where the spans are missing or were dropped."""
import math
import sys
import types

import pytest

import harness
from spantree import tree_total
from test_perfbench_run import run

NEW = ("loop_host_ms", "ckpt_commit_wait_s", "ckpt_commit_s",
       "ckpt_readback_ratio", "restore_read_ratio")
CELLS = {"yi-6b.int8-device": 0.5, "yi-6b.full-host.kill": 0.5,
         "internlm2-20b.train": 600.0}


@pytest.fixture(scope="module")
def runs():
    out = {}

    def get(workload):
        if workload not in out:
            out[workload] = run(workload, CELLS[workload])
        return out[workload]
    return get


def window_spans(r, t1=None):
    from repro.utils.timing import spans
    t0, end = r["run"]["window"]
    got, dropped = spans(t0, end if t1 is None else t1)
    assert dropped == 0
    return got


def read(name, r):
    view = harness.RunView({}, {}, r["run"], None, 0)
    return harness.metric_reader(name)(view)


LISTED = [(m["name"], w) for m in harness.load_benchmark()["per_layer"]
          if m["name"] in NEW for w in m["workloads"]]


@pytest.mark.parametrize("name,workload", LISTED)
def test_a_reader_reads_a_finite_value_in_the_cells_it_lists(
        runs, name, workload):
    r = runs(workload)
    assert r["correct"]
    v = read(name, r)
    assert v is not None and math.isfinite(v) and v >= 0.0


def test_a_sync_plan_has_no_commit_wait(runs):
    assert read("ckpt_commit_wait_s", runs("yi-6b.full-host.kill")) is None


def test_a_tree_total_sums_a_count_below_its_roots():
    from repro.utils.timing import Recorder
    rec = Recorder()
    with rec.span("save", bytes_read=3) as save:
        with rec.span("commit", bytes_written=12) as commit:
            with rec.span("verify", bytes_read=100):
                pass
    with rec.span("elsewhere", bytes_read=1000):
        pass
    got, _ = rec.spans(-math.inf, math.inf)
    assert tree_total(got, [save], "bytes_read") == 103
    assert tree_total(got, [commit], "bytes_written") == 12
    assert tree_total(got, [commit], "bytes_read") == 100
    assert tree_total(got, [save], "tokens") == 0
    assert tree_total(got, [], "bytes_read") == 0


def test_the_ratios_count_the_reads_the_code_makes(runs):
    """A delta commit CRCs its base full before writing; a full save
    reads every shard back and validates the kept fulls after; a restore
    reads and validates the full once and loads it from the same bytes."""
    kill = runs("yi-6b.full-host.kill")
    assert read("ckpt_readback_ratio", kill) == pytest.approx(2.0, rel=0.02)
    assert read("restore_read_ratio", kill) == pytest.approx(1.0, rel=0.02)
    assert read("ckpt_readback_ratio", runs("yi-6b.int8-device")) > 1.0


def window_save_spans(r):
    """Each window save's record beside its ``khaos.ckpt.save`` span."""
    got = window_spans(r)
    saves = [s for s in got if s.name == "khaos.ckpt.save"]
    pairs = []
    for rec in r["run"]["window_saves"]:
        inside = [s for s in saves
                  if rec["t0"] <= s.start and s.end <= rec["t1"]]
        assert len(inside) == 1
        pairs.append((rec, inside[0]))
    assert pairs
    return pairs


@pytest.mark.parametrize("workload",
                         ["yi-6b.int8-device", "yi-6b.full-host.kill"])
def test_a_save_less_its_commit_wait_is_its_blocking_time(runs, workload):
    r = runs(workload)
    got = window_spans(r, math.inf)
    for rec, sp in window_save_spans(r):
        waits = [s.duration for s in got
                 if s.parent == sp.id and s.name == "khaos.ckpt.commit_wait"]
        assert len(waits) <= 1
        assert abs(sp.duration - sum(waits) - rec["blocking_s"]) < 5e-3


def test_a_restore_less_its_wait_is_its_duration(runs):
    r = runs("yi-6b.full-host.kill")
    got = window_spans(r)
    restores = [s for s in got if s.name == "khaos.ckpt.restore"]
    recs = r["run"]["records"].restores
    assert restores and len(restores) == len(recs)
    for sp, rec in zip(restores, recs):
        wait = [s.duration for s in got
                if s.parent == sp.id and s.name == "khaos.ckpt.restore_wait"]
        assert len(wait) == 1
        assert abs(sp.duration - wait[0] - rec["duration_s"]) < 5e-3
        assert sp.step == rec["step"] and sp.counts["state_bytes"] > 0


def test_a_full_save_reads_back_the_bytes_it_wrote(runs):
    r = runs("yi-6b.full-host.kill")
    got = window_spans(r, math.inf)
    for rec, sp in window_save_spans(r):
        assert rec["kind"] == "full"
        commits = [s for s in got
                   if s.parent == sp.id and s.name == "khaos.ckpt.commit"]
        writes = [s for s in got if s.name == "khaos.ckpt.write"
                  and s.parent in {c.id for c in commits}]
        readback = sum(s.counts["bytes_read"] for s in got
                       if s.name == "khaos.ckpt.verify"
                       and s.parent in {w.id for w in writes})
        wrote = sum(w.counts["bytes_written"] for w in writes)
        assert writes and readback == wrote > 0
        # what the save reports also holds its manifest
        report = rec["report"].bytes_written
        assert commits[0].counts["bytes_written"] == report
        assert wrote <= report < wrote + (64 << 10)


def test_the_readers_find_nothing_when_spans_were_dropped(runs, monkeypatch):
    import repro.utils.timing as timing
    r = runs("yi-6b.full-host.kill")
    real = timing.spans
    monkeypatch.setattr(timing, "spans",
                        lambda t0, t1: (real(t0, t1)[0], 1))
    assert all(read(name, r) is None for name in NEW)


def test_the_readers_find_nothing_without_the_recorder(runs, monkeypatch):
    """A program that predates the recorder: the readers return None and
    raise nothing."""
    r = runs("yi-6b.full-host.kill")
    monkeypatch.setitem(sys.modules, "repro.utils.timing",
                        types.ModuleType("repro.utils.timing"))
    assert all(read(name, r) is None for name in NEW)
