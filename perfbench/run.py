"""Run one benchmark cell once on the chips of this machine.

    python3 perfbench/run.py --workload yi-6b.int8-device --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration and its traffic mix are read by name from
``BENCHMARK.json`` and the files under ``perfbench/``.  With ``--trace 0``
the last line of standard output is one JSON object with the cell's
end-to-end metrics; with ``--trace 1`` it holds the per-layer metrics,
read from a profiler trace of the window, and a breakdown.  Both name the
device and end with the numbers ``correct`` was decided on, each beside
its limit; standard error ends with the same numbers.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((c for c in bench["workloads"]
                 if c["name"] == args.workload), None)
    if cell is None:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2

    import harness
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    cfg = harness.load_config(cell["config"])
    mix = harness.load_mix(cell["traffic"])
    with tempfile.TemporaryDirectory(prefix="perfbench-trace-") as tdir:
        run = harness.run_cell(cell, cfg, mix, args.seed, args.seconds,
                               tdir if args.trace else None, T_START)
        out = harness.result(bench, cell, cfg, run, device,
                             tdir if args.trace else None)
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"
    print(f"{tag} run {json.dumps(run['details']['run'])}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"{tag} check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
