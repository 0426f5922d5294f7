"""Mean ``SaveReport.blocking_s`` over the window's saves: the time a
trigger held the step stream (under async commit, the snapshot or the
device encode and the first payload chunk)."""


def read(run):
    saves = [s for s in run.run["window_saves"] if s["kind"] != "skipped"]
    if not saves:
        return None
    return sum(s["blocking_s"] for s in saves) / len(saves)
