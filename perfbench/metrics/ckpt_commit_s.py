"""Mean length of the ``khaos.ckpt.commit`` of the window's saves: the
D2H pulls, compression, writes and validation reads behind a trigger, on
the committer thread under an async plan."""
import math


def read(run):
    try:
        from repro.utils.timing import spans
    except ImportError:             # a program without the span recorder
        return None
    t0, t1 = run.run["window"]
    got, dropped = spans(t0, t1)
    # a commit starts during its save but is kept only when it ends, after
    # the window for the last save of an async plan
    later, _ = spans(t0, math.inf)
    saves = {s.id for s in got if s.name == "khaos.ckpt.save"}
    commits = [s.duration for s in later
               if s.name == "khaos.ckpt.commit" and s.parent in saves]
    if dropped or not commits:
        return None
    return sum(commits) / len(commits)
