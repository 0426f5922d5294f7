"""Mean ``RestoreReport.duration_s`` of the window's restores."""


def read(run):
    rs = run.run["records"].restores
    if not rs:
        return None
    return sum(r["duration_s"] for r in rs) / len(rs)
