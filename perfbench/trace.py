"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` into plain events:

  * device ops: each TPU plane's ``XLA Ops`` line (one event per HLO op
    the chip ran, named by its HLO instruction; the program run that
    holds it in time names its program);
  * device programs: each TPU plane's ``XLA Modules`` line (one event per
    program run, named ``jit_<function>(<id>)``);
  * host spans: the ``perfbench.*`` spans the harness places around its
    calls into the program (``jax.profiler.TraceAnnotation``).

``summarize`` keeps the part of each event inside the traced window and
gives busy time (the union of op intervals, averaged over the chips that
ran anything), per-program device time and run counts, the ops that took
most time, and the idle gaps grouped by the host span they fell in.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Optional

SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"


@dataclass
class Event:
    start: float          # seconds on the trace's clock
    end: float
    name: str
    module: str = ""


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)       # chip -> [Event]
    programs: dict = field(default_factory=dict)  # chip -> [Event]
    spans: list = field(default_factory=list)     # host [Event]


def module_name(name: str) -> str:
    """``jit_train_step(12)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def op_name(name: str) -> str:
    """``%fusion.12 = (f32[...]) fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def assign_programs(tr: Trace) -> None:
    """Name each op's program: the program run on its chip whose interval
    holds the op's midpoint."""
    for chip, ops in tr.ops.items():
        progs = sorted(tr.programs.get(chip, []), key=lambda e: e.start)
        starts = [p.start for p in progs]
        for op in ops:
            mid = 0.5 * (op.start + op.end)
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and progs[i].end >= mid:
                op.module = module_name(progs[i].name)


def load(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = ProfileData.from_file(paths[-1])
    tr = Trace()
    for plane in pd.planes:
        chip = plane.name
        is_tpu = chip.startswith("/device:TPU:") and "NON_CORE" not in chip
        for line in plane.lines:
            if is_tpu and line.name in ("XLA Ops", "XLA Modules"):
                out = tr.ops if line.name == "XLA Ops" else tr.programs
                evs = out.setdefault(chip, [])
                for e in line.events:
                    s = e.start_ns * 1e-9
                    evs.append(Event(s, s + e.duration_ns * 1e-9,
                                     op_name(e.name)))
            elif not is_tpu:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        tr.spans.append(Event(s, s + e.duration_ns * 1e-9,
                                              e.name))
    assign_programs(tr)
    return tr


def _clip(evs: list, t0: float, t1: float) -> list:
    return [Event(max(e.start, t0), min(e.end, t1), e.name, e.module)
            for e in evs if e.end > t0 and e.start < t1]


def union(intervals: list) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


UNSPANNED = "trainer loop (no harness span)"


def _segments(spans: list, t0: float, t1: float) -> tuple[list, list]:
    """Cut [t0, t1] at every span boundary; name each piece by the
    shortest (innermost) harness span covering it."""
    spans = [s for s in spans if s.name != WINDOW_SPAN]
    cuts = sorted({t0, t1} | {x for s in spans for x in (s.start, s.end)})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        inside = [s for s in spans if s.start <= mid <= s.end]
        names.append(min(inside, key=lambda s: s.end - s.start).name
                     if inside else UNSPANNED)
    return cuts, names


def window_of(tr: Trace) -> Optional[tuple[float, float]]:
    w = [s for s in tr.spans if s.name == WINDOW_SPAN]
    return (w[0].start, w[0].end) if w else None


def summarize(tr: Trace, t0: float, t1: float, top: int = 10) -> dict:
    """Device numbers of the window [t0, t1] on the trace's clock."""
    window = t1 - t0
    chips = {c: _clip(evs, t0, t1) for c, evs in tr.ops.items()}
    chips = {c: evs for c, evs in chips.items() if evs}
    busy_by_chip, gaps = {}, []
    op_time: dict = defaultdict(float)
    for c, evs in chips.items():
        merged = union([(e.start, e.end) for e in evs])
        busy_by_chip[c] = sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for e in evs:
            op_time[f"{e.module}:{e.name}"] += e.end - e.start
    prog_time: dict = defaultdict(float)
    prog_runs: dict = defaultdict(int)
    for c, evs in tr.programs.items():
        for e in _clip(evs, t0, t1):
            m = module_name(e.name)
            prog_time[m] += e.end - e.start
            prog_runs[m] += 1
    idle_by: dict = defaultdict(float)
    cuts, names = _segments(_clip(tr.spans, t0, t1), t0, t1)
    for g0, g1 in gaps:
        # each piece of a gap goes to the innermost host span covering it;
        # what no span covers is the trainer's own loop
        i = max(0, bisect.bisect_right(cuts, g0) - 1)
        while i < len(names) and cuts[i] < g1:
            a, b = max(cuts[i], g0), min(cuts[i + 1], g1)
            if b > a:
                idle_by[names[i]] += b - a
            i += 1
    n = max(1, len(chips))
    return {
        "chips": len(chips),
        "window_s": window,
        "busy_s": sum(busy_by_chip.values()) / n,
        "program_s": dict(prog_time),
        "program_runs": dict(prog_runs),
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((k, v / n) for k, v in idle_by.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def program_time(summary: dict, module: str) -> tuple[float, int]:
    """(device seconds, runs) of the program ``module`` in the window."""
    return (summary["program_s"].get(module, 0.0),
            summary["program_runs"].get(module, 0))
