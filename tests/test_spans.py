"""The span recorder: nesting, parents across threads, window reads,
bounded buffer, counts."""
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from repro.utils.timing import Recorder


def by_name(spans):
    return {s.name: s for s in spans}


def test_nesting_sets_parent_ids():
    rec = Recorder()
    with rec.span("a", step=3) as a:
        assert rec.current() == a.id
        with rec.span("b") as b:
            with rec.span("c") as c:
                pass
        with rec.span("d") as d:
            pass
    assert rec.current() is None
    got, dropped = rec.spans(-math.inf, math.inf)
    assert dropped == 0
    assert [s.name for s in got] == ["a", "b", "c", "d"]
    assert a.parent is None and a.step == 3
    assert b.parent == a.id and d.parent == a.id and c.parent == b.id
    assert len({a.id, b.id, c.id, d.id}) == 4
    assert a.start <= b.start <= c.start <= c.end <= b.end <= d.start \
        <= d.end <= a.end
    assert all(s.thread == threading.get_ident() for s in got)


def test_an_exception_closes_the_span_and_propagates():
    rec = Recorder()
    try:
        with rec.span("outer"):
            with rec.span("inner"):
                raise KeyError("x")
    except KeyError:
        pass
    got, _ = rec.spans(-math.inf, math.inf)
    spans = by_name(got)
    assert spans["inner"].parent == spans["outer"].id
    assert rec.current() is None
    assert all(s.end >= s.start for s in got)


def test_an_explicit_parent_is_carried_to_a_worker_thread():
    rec = Recorder()

    def work(parent):
        # the worker's own stack is empty: without the explicit parent
        # its span would be a root
        assert rec.current() is None
        with rec.span("work", parent=parent) as w:
            with rec.span("inner") as i:
                pass
        return w, i

    with ThreadPoolExecutor(max_workers=2) as pool:
        with rec.span("submit") as s:
            fut = pool.submit(work, rec.current())
            orphan = pool.submit(work, None)
            w, i = fut.result(timeout=30)
            o, _ = orphan.result(timeout=30)
    assert w.parent == s.id and i.parent == w.id
    assert o.parent is None
    assert w.thread != s.thread and i.thread == w.thread


def test_the_window_keeps_spans_that_start_inside_it():
    rec = Recorder()
    with rec.span("before"):
        time.sleep(0.002)
        t0 = time.perf_counter()
        with rec.span("inside") as inside:
            time.sleep(0.002)
        t1 = time.perf_counter()
    with rec.span("after"):
        pass
    got, _ = rec.spans(t0, t1)
    # whole spans, clipped by their start: "before" overlaps the window
    # but starts before it, "after" starts past it
    assert [s.name for s in got] == ["inside"]
    assert got[0].start == inside.start and got[0].end == inside.end
    assert [s.name for s in rec.spans(t0, math.inf)[0]] == ["inside",
                                                              "after"]


def test_a_full_buffer_drops_the_oldest_and_counts_the_drops():
    rec = Recorder(capacity=4)
    for k in range(6):
        with rec.span(f"s{k}"):
            pass
    mid = time.perf_counter()
    for k in range(6, 9):
        with rec.span(f"s{k}"):
            pass
    got, dropped = rec.spans(-math.inf, math.inf)
    assert [s.name for s in got] == ["s5", "s6", "s7", "s8"]
    assert dropped == 5 and rec.dropped == 5
    # every dropped span ended before ``mid``: a window that starts there
    # lost nothing
    assert rec.spans(mid, math.inf) == (got[1:], 0)


def test_counts_are_summed():
    rec = Recorder()
    with rec.span("save", bytes_read=1) as save:
        save.add(bytes_read=2, bytes_written=10)
        with rec.span("commit", bytes_written=5) as commit:
            commit.add(bytes_written=7)
            with rec.span("verify", bytes_read=100):
                pass
    assert save.counts == {"bytes_read": 3, "bytes_written": 10}
    assert commit.counts == {"bytes_written": 12}


def test_the_default_recorder_records_the_trainer_loop(tmp_path):
    """The trainer's loop through the program's own recorder: each
    ``khaos.step`` that trained carries its tokens and has the loop's
    phases as children."""
    from repro.configs import get_smoke_config
    from repro.data.stream import EventStream, constant_rate
    from repro.runtime import ResilientTrainer, TrainerConfig
    from repro.utils.timing import spans

    stream = EventStream(schedule=constant_rate(500.0))
    stream.produce_until(0.0)
    tr = ResilientTrainer(get_smoke_config("yi-6b"), TrainerConfig(
        batch=2, seq_len=16, ckpt_dir=str(tmp_path), ckpt_interval_s=1e9),
        stream)
    t0 = time.perf_counter()
    tr.run(duration_s=0.5)
    got, dropped = spans(t0, math.inf)
    steps = [s for s in got
             if s.name == "khaos.step" and s.counts["tokens"]]
    assert steps and dropped == 0
    assert {s.counts["tokens"] for s in steps} == {2 * 16}
    kids = {s.name for s in got if s.parent == steps[0].id}
    assert {"khaos.trigger", "khaos.batch", "khaos.upload", "khaos.dispatch",
            "khaos.loss_read", "khaos.record"} <= kids
