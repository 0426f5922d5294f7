"""Readings that set the limits of ``correct``; the benchmark's own runs
never run this.  On the chip, at a cell's own size:

    python3 perfbench/control.py --workload yi-6b.int8-device \
        --seeds 11 12 13 --seconds 12

For each seed it prints one JSON line with

  * ``sound``: the cell's numbers from a short run of the cell itself;
  * ``control_bf16``: the program's own lower-precision path (parameters
    and Adam moments in bfloat16, where the configuration states float32)
    driven through the same first steps, against the float32 reference;
  * ``fault_half_batch``: the reference fed half of each batch (the mean
    taken over the rest) against the reference on the whole batch;
  * ``control_int4`` (int8 cells): the sampled deltas of the run's saves
    quantized with 4-bit codes, against the codec's stated int8 bound.

A state left unchanged reads 1 on ``change_gap`` and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def int4_ratio(run: dict) -> float | None:
    """Worst 4-bit codec error over the int8 bound, on the samples the
    run's witnesses took of each window delta and its base."""
    import numpy as np
    import reference
    saves = run["records"].saves
    base = next((s for s in saves if s["kind"] == "full"), None)
    deltas = [s for s in run["window_saves"] if s["kind"] == "delta"]
    if base is None or not deltas:
        return None
    worst = 0.0
    for s in deltas:
        d = s["witness"][1].astype(np.float32) - base["witness"][1]
        q, sc = reference.encode_groups(d, bits=4)
        worst = max(worst, reference.int8_error_ratio(
            reference.decode_groups(q, sc), d))
    return worst


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import jax
    import harness
    import reference
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    bench = harness.load_benchmark()
    cell = harness.cell_spec(bench, args.workload)
    cfg = harness.load_config(cell["config"])
    mix = harness.load_mix(cell["traffic"])
    for seed in args.seeds:
        out = {"workload": cell["name"], "seed": seed,
               "device": jax.devices()[0].device_kind}
        run = harness.run_cell(cell, cfg, mix, seed, args.seconds, None,
                               time.perf_counter())
        out["sound"] = run["checks"]
        out["int4_ratio"] = int4_ratio(run)
        del run
        gc.collect()

        pseed = harness.program_seed(seed)
        batch, seq = cfg["job"]["batch"], cfg["job"]["seq_len"]
        events = [reference.event_tokens(range(o, o + batch), seq,
                                         cfg["vocab_size"], pseed)
                  for o in range(0, 3 * batch, batch)]
        full = [(e[:, :-1], e[:, 1:]) for e in events]
        arch = harness.arch_module(cfg)
        ref = reference.first_steps(cfg, pseed, full, arch=arch)
        half = reference.first_steps(
            cfg, pseed, [(t[: batch // 2], l[: batch // 2]) for t, l in full],
            arch=arch)
        out["fault_half_batch"] = {
            k: v for k, v in harness.training_numbers(half, ref).items()
            if k.endswith("gap")}

        import tempfile
        with tempfile.TemporaryDirectory(prefix="perfbench-ctl-") as d:
            job = harness.Job(cfg, {**mix, "plan": {"interval_s": 1e9}},
                              pseed, d, model_override={
                                  "param_dtype": "bfloat16"},
                              opt_override={"state_dtype": "bfloat16"})
            prog = harness.program_first_steps(job, 3)
            del job
            gc.collect()
        out["control_bf16"] = {
            k: v for k, v in harness.training_numbers(prog, ref).items()
            if k.endswith("gap") or k.endswith("leaf")}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
