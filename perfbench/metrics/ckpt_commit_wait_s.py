"""Mean ``khaos.ckpt.commit_wait`` per window save: how long a trigger
waited for the previous async commit before its snapshot began, which
``ckpt_blocking_s`` leaves out.  A synchronous plan has no such wait."""


def read(run):
    try:
        from repro.utils.timing import spans
    except ImportError:             # a program without the span recorder
        return None
    t0, t1 = run.run["window"]
    got, dropped = spans(t0, t1)
    saves = {s.id for s in got if s.name == "khaos.ckpt.save"}
    waits = [s.duration for s in got
             if s.name == "khaos.ckpt.commit_wait" and s.parent in saves]
    if dropped or not waits:
        return None
    return sum(waits) / len(waits)
