"""The flat int8 encoder's share of its HBM roofline: the bytes it must
move (new and base planes read, q and the scales written) over the
chip's HBM bandwidth, over its summed device time in the window."""
import arith
import trace as tracing


def read(run):
    if run.trace is None:
        return None
    s, n = tracing.program_time(run.trace, "jit_flat_int8_encode")
    if not n or s <= 0.0:
        return None
    least = n * arith.int8_encode_bytes(arith.packed_groups(run.cfg)) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
