"""Device time of the jitted train step per run of it, in the window."""
import trace as tracing


def read(run):
    if run.trace is None:
        return None
    s, n = tracing.program_time(run.trace, "jit_train_step")
    return 1e3 * s / n if n else None
