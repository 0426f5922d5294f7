"""Bytes the window's restores read, over the bytes of the state they
restored: the ``bytes_read`` of every span under the window's
``khaos.ckpt.restore`` spans (validation reads, the shard loads, the
delta files) over their ``state_bytes``."""

from spantree import tree_total


def read(run):
    try:
        from repro.utils.timing import spans
    except ImportError:             # a program without the span recorder
        return None
    t0, t1 = run.run["window"]
    got, dropped = spans(t0, t1)
    restores = [s for s in got if s.name == "khaos.ckpt.restore"]
    restored = sum(s.counts.get("state_bytes", 0) for s in restores)
    if dropped or not restores or restored <= 0:
        return None
    return tree_total(got, restores, "bytes_read") / restored
