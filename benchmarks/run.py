"""Benchmark driver — one section per paper table/figure (DESIGN.md §6):

  E1  IoT-Vehicles analogue  (paper Table II, Fig. 2a/2c, Fig. 3a)
  E2  YSB analogue           (paper Table III, Fig. 2b/2d, Fig. 3b)
  E4  recovery/latency vs CI (paper §III-C premise; scalar oracle AND the
                              batched campaign — emits BENCH_sim.json with
                              the lane-vs-scalar table, the campaign
                              throughput measurement, and the embedded E10
                              proactive section and the E12 device-engine
                              section, schema "bench_sim/3")
  E5  checkpoint subsystem   (beyond-paper; emits the BENCH_ckpt.json
                              calibration artifact the sim cost model loads)
  E6  kernel validation      (oracle timings + interpret-mode allclose)
  E7  dry-run / roofline     (reads experiments/dryrun.json)
  E10 proactive vs reactive  (forecast-driven plan switching + anomaly-
                              triggered reprofiling vs a reactive twin on
                              ONE gray-failure campaign; its result is the
                              "proactive" section of BENCH_sim.json and the
                              validator gates a STRICT proactive win)
  E12 device mega-campaigns  (jitted DeviceCampaign vs the NumPy lanes:
                              throughput at 1e3/1e4/1e5 lanes, the
                              bit-exact parity matrix, and the exhaustive
                              device plan sweep vs top-k replay — the
                              "device" section of BENCH_sim.json)
  E11 fleet supervisor       (one control plane over 8+ concurrent jobs —
                              emits BENCH_fleet.json, schema "bench_fleet/1",
                              gating the shared-tick wall-clock ratio < 2x
                              one job, >= 5x less profiling lane-time via
                              QoS-model transfer with matched twin QoS, and
                              admission-control rejection of an infeasible
                              firehose job)

Usage: PYTHONPATH=src python -m benchmarks.run [--quick | --smoke]

``--smoke`` is the tier-1-adjacent CI check: it runs the E5 checkpoint
bench on a tiny state (device-placement delta encodes included, plus a
micro trainer on an ``encode_placement="device"`` plan in interpret
mode), the PROACTIVE-CONTROL DRILL (diurnal ramp + backpressure window +
crash on one supervised lane, asserting a forecast-driven plan switch
BEFORE the λ peak and an anomaly-triggered ``reprofile`` re-entry in the
phase log), a tiny 4-lane E4 campaign, a tiny end-to-end ``KhaosRuntime``
(all three phases on a 4-lane controller-in-the-loop campaign + a micro
live trainer with a mid-run plan switch), and the replication RECOVERY
DRILL (save under k=1 ring replication, kill one host, assert the
degraded partial restore is bit-exact and pulls only the failed host's
shard bytes — ``restored_bytes < full_state_bytes`` — plus the peer-loss
worst case through the per-shard remote fallback and the optimizer's
``replication_factor`` dimension), and the FLEET DRILL (a 3-job
supervisor: one cold admit, one fingerprint-matched transfer admit that
skips Phase 2 via the QoS-model registry, and one firehose rejected by
admission control, validating the emitted BENCH_fleet.json against
``bench_fleet.validate_fleet_artifact``), validating that the emitted
BENCH_ckpt.json / BENCH_sim.json artifacts match their schemas
("bench_ckpt/3" via ``SimCostModel.from_calibration`` — placement/codec
fields, int8 link fraction <= 0.26, the fused flat device encode under
the per-leaf dispatch baseline, with "bench_ckpt/1" and "/2" artifacts
still loadable as the versioned fallbacks; "bench_sim/2" via
``bench_recovery.validate_sim_artifact``, which also gates the embedded
proactive drill) and that the phase order /
JobHandle protocol have not regressed — exiting non-zero on any
mismatch.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="single repetition for E1/E2 (default: median of 3)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-state bench_ckpt + BENCH artifact schema "
                         "validation + end-to-end KhaosRuntime phase/"
                         "protocol gate (tier-1-adjacent check)")
    args = ap.parse_args()
    use_compile_cache()

    # Pin the XLA CPU backend to a pre-FMA ISA BEFORE any bench initializes
    # a backend: the device campaign's bit-exact parity gate needs it
    # (importing sim.device appends the flag as a side effect).
    from repro.sim.device import ensure_bitexact_cpu
    ensure_bitexact_cpu()

    t0 = time.monotonic()
    if args.smoke:
        from benchmarks import (bench_campaign, bench_ckpt, bench_fleet,
                                bench_proactive, bench_recovery,
                                bench_replication, bench_runtime)
        try:
            bench_ckpt.smoke()
            # the proactive drill's summary and the device-engine section
            # are embedded (and gated) in the BENCH_sim.json artifact that
            # bench_recovery.smoke() emits — the device parity gate
            # (divergent_lanes == 0) runs on a small CPU campaign here
            proactive = bench_proactive.smoke()
            device = bench_campaign.device_section(smoke=True)
            bench_recovery.smoke(proactive=proactive, device=device)
            bench_replication.smoke()
            bench_runtime.smoke()
            bench_fleet.smoke()
        except (ValueError, AssertionError) as e:
            print(f"SMOKE FAILED: {e}", file=sys.stderr)
            sys.exit(1)
        print(f"smoke done in {time.monotonic() - t0:.0f}s")
        return
    from benchmarks import (bench_campaign, bench_ckpt, bench_dryrun,
                            bench_fleet, bench_kernels,
                            bench_khaos_training, bench_proactive,
                            bench_recovery, bench_replication, bench_tables)

    repeats = 1 if args.quick else 3
    bench_tables.bench_iot_vehicles(repeats=repeats)
    bench_tables.bench_ysb(repeats=repeats)
    proactive = bench_proactive.main()
    device = bench_campaign.main()
    bench_recovery.main(proactive=proactive, device=device)
    bench_replication.main()
    bench_fleet.main()
    bench_khaos_training.main()
    bench_ckpt.main()
    bench_kernels.main()
    bench_dryrun.main()
    print(f"\nall benchmarks done in {time.monotonic() - t0:.0f}s")


if __name__ == "__main__":
    main()
