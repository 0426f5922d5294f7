"""The benchmark's arithmetic: operations and bytes from the shapes a
configuration's architecture module gives, the peak table, and the
end-to-end metrics from the host-clock records of a run.  Nothing here
imports the program."""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Optional, Sequence

GROUP = 1024          # elements per scale group of the int8 delta codec
BENCH = Path(__file__).resolve().parent


# ---------------------------------------------------------------------------
# peaks
# ---------------------------------------------------------------------------

def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one token of a train step, as the configuration's
    architecture module counts them."""
    import harness
    return harness.arch_module(cfg).train_flops_per_token(cfg, seq_len)


def train_flops_per_step(cfg: dict) -> float:
    job = cfg["job"]
    return train_flops_per_token(cfg, job["seq_len"]) * job["batch"] \
        * job["seq_len"]


def groups(size: int) -> int:
    return -(-size // GROUP)


def packed_groups(cfg: dict, f32_copies: int = 3) -> int:
    """Scale groups of the packed f32 state a device delta trigger
    encodes: each f32 leaf padded to whole groups; parameters and, with
    f32 Adam, both moments (``f32_copies`` = 3)."""
    import harness
    shapes = harness.arch_module(cfg).param_shapes(cfg)
    per = sum(groups(math.prod(s)) for s in shapes.values())
    return f32_copies * per


def int8_encode_bytes(n_groups: int) -> int:
    """HBM bytes the flat int8 encode must move: the new and the base
    plane read (4 B each per element), q written (1 B per element) and
    one f32 scale per group."""
    return n_groups * GROUP * (4 + 4 + 1) + n_groups * 4


# ---------------------------------------------------------------------------
# end-to-end metrics from host-clock records
# ---------------------------------------------------------------------------

def goodput(boundaries: Sequence[tuple[float, int]], seq_len: int
            ) -> Optional[float]:
    """Tokens kept per second between the first and the last step
    boundary of the window.  ``boundaries`` are (host time, stream cursor
    after the step) in order; a restore moves the cursor back, so steps
    that were rolled back and done again count once."""
    if len(boundaries) < 2:
        return None
    (t0, o0), (t1, o1) = boundaries[0], boundaries[-1]
    if t1 <= t0:
        return None
    return (o1 - o0) * seq_len / (t1 - t0)


def resume_s(kills: Sequence[tuple[float, float]]) -> Optional[float]:
    """Mean over kills of (first step completed after the restore - the
    moment the failure was raised)."""
    if not kills:
        return None
    return sum(b - a for a, b in kills) / len(kills)


def kept_offsets(log: Sequence[tuple]) -> list:
    """The (first offset, events) of every step the final state holds:
    ``log`` interleaves ("step", first, events) and ("restore", cursor)
    in order; a restore drops the steps past its cursor."""
    kept: list = []
    for entry in log:
        if entry[0] == "step":
            kept.append((entry[1], entry[2]))
        else:
            kept = [s for s in kept if s[0] + s[1] <= entry[1]]
    return kept
