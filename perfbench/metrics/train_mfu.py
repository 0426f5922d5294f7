"""Model FLOPs of the train steps completed in the traced window over the
window's length times the chip's bf16 peak, in percent."""
import arith


def read(run):
    if run.trace is None or not run.trace["chips"] or not run.steps_traced:
        return None
    flops = run.steps_traced * arith.train_flops_per_step(run.cfg)
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["bf16_flops"])
