"""Host time of the trainer's own loop per train step: the mean over the
window's ``khaos.step`` spans that trained of their length less their
``khaos.loss_read`` (the wait for the device) and ``khaos.trigger`` (the
checkpoint trigger) children."""


def read(run):
    try:
        from repro.utils.timing import spans
    except ImportError:             # a program without the span recorder
        return None
    t0, t1 = run.run["window"]
    got, dropped = spans(t0, t1)
    steps = [s for s in got
             if s.name == "khaos.step" and s.counts.get("tokens", 0) > 0]
    if dropped or not steps:
        return None
    ids = {s.id for s in steps}
    less = sum(s.duration for s in got
               if s.parent in ids
               and s.name in ("khaos.loss_read", "khaos.trigger"))
    return 1e3 * (sum(s.duration for s in steps) - less) / len(steps)
