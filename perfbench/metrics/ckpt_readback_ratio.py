"""Bytes the window's saves read back, over the bytes they wrote: the
``bytes_read`` of every span under the window's ``khaos.ckpt.save`` spans
(CRC read-backs, the validation of kept checkpoints) over the
``bytes_written`` of their ``khaos.ckpt.commit`` spans."""
import math

from spantree import tree_total


def read(run):
    try:
        from repro.utils.timing import spans
    except ImportError:             # a program without the span recorder
        return None
    t0, t1 = run.run["window"]
    got, dropped = spans(t0, t1)
    # a commit and what it reads are kept only when they end, after the
    # window for the last save of an async plan
    later, _ = spans(t0, math.inf)
    saves = [s for s in got if s.name == "khaos.ckpt.save"]
    ids = {s.id for s in saves}
    written = sum(s.counts.get("bytes_written", 0) for s in later
                  if s.name == "khaos.ckpt.commit" and s.parent in ids)
    if dropped or written <= 0:
        return None
    return tree_total(later, saves, "bytes_read") / written
