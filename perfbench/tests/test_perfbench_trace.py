"""The reduction of a profiler trace, on a small trace recorded on one v5e
chip (the end of a train step, the host gap, the start of the next)."""
import json
from pathlib import Path

import pytest

import trace as tracing

DATA = Path(__file__).with_name("data") / "v5e_step_gap.json"


def recorded():
    fx = json.loads(DATA.read_text())
    chip = fx["chip"]
    tr = tracing.Trace(
        ops={chip: [tracing.Event(a, b, n) for a, b, n in fx["ops"]]},
        programs={chip: [tracing.Event(a, b, n) for a, b, n in
                         fx["programs"]]},
        spans=[tracing.Event(a, b, n) for a, b, n in fx["spans"]])
    tracing.assign_programs(tr)
    return tr, fx


def test_busy_is_the_union_of_op_intervals():
    tr, fx = recorded()
    t0, t1 = fx["window"]
    s = tracing.summarize(tr, t0, t1)
    merged = tracing.union([(max(e.start, t0), min(e.end, t1))
                            for e in tr.ops[fx["chip"]]])
    assert s["busy_s"] == pytest.approx(sum(b - a for a, b in merged))
    # the host gap between the two steps is idle
    (_, end0, _), (start1, _, _) = fx["programs"]
    assert s["window_s"] - s["busy_s"] >= 0.9 * (start1 - end0)
    assert 0.5 < 1 - s["busy_s"] / s["window_s"] < 0.7


def test_programs_and_ops_are_found_by_name():
    tr, fx = recorded()
    s = tracing.summarize(tr, *fx["window"])
    secs, runs = tracing.program_time(s, "jit_train_step")
    assert runs == 2 and secs > 0
    assert all(name.startswith("jit_train_step:")
               for name, _ in s["device_ops"])


def test_idle_gaps_go_to_the_host_span_they_fall_in():
    tr, fx = recorded()
    s = tracing.summarize(tr, *fx["window"])
    gaps = dict(s["idle_gaps"])
    # building the next batch is the longest part of the gap
    assert max(gaps, key=gaps.get) == "perfbench.batch"
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_union_and_op_names():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracing.op_name("%fusion.12 = (f32[2]) fusion(%p)") == "fusion.12"
    assert tracing.module_name("jit_train_step(123)") == "jit_train_step"
