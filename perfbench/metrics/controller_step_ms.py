"""Host time of one ``KhaosRuntime.step()``, run inline after every
train step of the window."""


def read(run):
    xs = run.run["controller_window"]
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
