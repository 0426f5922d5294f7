"""E12: device-resident mega-campaigns — the "device" section of
BENCH_sim.json (schema "bench_sim/3").

Three measurements, one per claim the device engine makes:

  throughput   lane-ticks/s, NumPy vs device, at 1e3/1e4/1e5 lanes of the
               same failure-bearing scenario (record_history=False, warm
               numbers exclude the one-time XLA compile, which is reported
               separately).  On an accelerator the device engine is the
               10x+ story; on the CPU fallback it must merely not lose —
               either way the numbers are measured, not assumed.
  parity       the hard gate: a full (plan x crash kind x degradation
               kind x CI) matrix run through BOTH engines; a lane counts
               as divergent unless its lag history, latency history,
               recovery records, and final counters are ALL bit-identical.
               ``divergent_lanes`` must be 0 for the artifact to validate
               (``fma_contraction`` reports whether the pre-FMA ISA pin
               took — see ``sim.device.ensure_bitexact_cpu``).
  sweep        what the throughput buys: ``optimize_plan`` on the E4
               scenario with the usual top-3 replay (NumPy) vs the
               exhaustive full-variant-grid replay (device).  Because the
               exhaustive replay scores a SUPERSET of the shortlist with
               bit-identical measurements, its pick must match or improve
               the top-k pick's measured Eq.-8 objective — the validator
               gates ``exhaustive_objective <= topk_objective``.
"""
from __future__ import annotations

import time

import numpy as np

from repro.config import CheckpointPlan
from repro.core import QoSModel, optimize_plan
from repro.data.stream import constant_rate, dense_rates
from repro.ft.failures import Degradation
from repro.sim import (BatchedCampaign, LaneSpec, SimCostModel,
                       make_plan_verifier)
from repro.sim.device import DeviceCampaign, fma_contraction_active

THR_TICKS = 2000
THR_LANE_COUNTS = (1_000, 10_000, 100_000)

PARITY_PLANS = (
    ("full-sync", None),
    ("full-async", CheckpointPlan(sync=False)),
    ("incr8-async", CheckpointPlan(mode="incremental", full_every=8,
                                   sync=False)),
    ("incr4-async-mlr", CheckpointPlan(mode="incremental", full_every=4,
                                       levels=("memory", "local", "remote"),
                                       local_every=1, remote_every=8)),
)
PARITY_KINDS = ("task", "node", "cluster")
PARITY_DEGRADATIONS = (
    ("straggler", Degradation(t=300.0, kind="straggler", duration_s=400.0,
                              severity=1.8)),
    ("net_delay_source", Degradation(t=250.0, kind="net_delay",
                                     duration_s=500.0, severity=3.0,
                                     jitter_s=0.8, direction="to_source")),
    ("net_delay_store", Degradation(t=250.0, kind="net_delay",
                                    duration_s=600.0, severity=4.0,
                                    jitter_s=1.0, direction="to_ckpt_store")),
    ("backpressure", Degradation(t=200.0, kind="backpressure",
                                 duration_s=150.0)),
)


def campaign_cost() -> SimCostModel:
    return SimCostModel(capacity_eps=4600.0, base_latency_s=0.5,
                        ckpt_duration_s=3.0, ckpt_sync_penalty=0.6)


def throughput_lanes(n: int, horizon: int = THR_TICKS) -> list[LaneSpec]:
    """n failure-bearing lanes sharing one λ array (the mega-campaign
    shape: many scenarios, one workload upload)."""
    rates = 3000.0 + 800.0 * np.sin(np.arange(horizon) / 40.0)
    return [LaneSpec(rates=rates, ci_s=float(10 + (i % 12) * 10),
                     failures=((300.0 + (i % 700), "task"),))
            for i in range(n)]


def bench_throughput(lane_counts=THR_LANE_COUNTS,
                     horizon: int = THR_TICKS) -> list[dict]:
    cost = campaign_cost()
    rows = []
    for n in lane_counts:
        lanes = throughput_lanes(n, horizon)
        t0 = time.perf_counter()
        BatchedCampaign(cost, lanes, record_history=False).run()
        wall_np = time.perf_counter() - t0
        t0 = time.perf_counter()
        DeviceCampaign(cost, lanes, record_history=False).run()
        wall_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        DeviceCampaign(cost, lanes, record_history=False).run()
        wall_warm = time.perf_counter() - t0
        ticks = n * horizon
        rows.append({
            "lanes": n,
            "lane_ticks": ticks,
            "numpy_lane_ticks_per_s": ticks / wall_np,
            "device_lane_ticks_per_s": ticks / wall_warm,
            "device_cold_wall_s": wall_cold,
            "device_speedup": wall_np / wall_warm,
        })
        print(f"  {n:>7d} lanes: numpy {ticks/wall_np/1e6:6.1f}M t/s, "
              f"device {ticks/wall_warm/1e6:6.1f}M t/s "
              f"({wall_np/wall_warm:.2f}x, cold {wall_cold:.1f}s)")
    return rows


def parity_lanes(horizon: int = 900) -> list[LaneSpec]:
    """The full scenario matrix both engines must agree on bit-for-bit:
    every plan x crash kind x CI with two injections, every plan x
    degradation kind with and without a concurrent crash, and pure
    no-failure lanes (the carry-free fast path)."""
    rates = 3000.0 + 800.0 * np.sin(np.arange(horizon) / 40.0)
    lanes = []
    for pi, (_name, plan) in enumerate(PARITY_PLANS):
        for kind in PARITY_KINDS:
            for ci in (15.0, 45.0):
                lanes.append(LaneSpec(
                    rates=rates, ci_s=ci, plan=plan,
                    failures=((200.0 + 20 * pi, kind), (560.0, "task"))))
    for _name, plan in PARITY_PLANS:
        for _dname, deg in PARITY_DEGRADATIONS:
            for fails in ((), ((400.0, "task"),)):
                lanes.append(LaneSpec(rates=rates, ci_s=20.0, plan=plan,
                                      failures=fails, degradations=[deg]))
    for _name, plan in PARITY_PLANS:
        lanes.append(LaneSpec(rates=rates, ci_s=25.0, plan=plan))
    return lanes


def divergent_lanes(a: BatchedCampaign, b: DeviceCampaign) -> int:
    """Count lanes that differ ANYWHERE: history, latency, recoveries, or
    final counters.  Bit-exact comparison — no tolerance."""
    n = a.n_lanes
    bad = np.zeros(n, dtype=bool)
    bad |= (a.lag_hist != b.lag_hist).any(axis=1)
    bad |= (a.latency_history() != b.latency_history()).any(axis=1)
    for name in ("lag", "consumed", "produced", "processed_total",
                 "ckpt_count", "save_count", "steady_lag", "down", "t"):
        bad |= np.asarray(getattr(a, name)) != np.asarray(getattr(b, name))
    bad |= (a.off_lvl != b.off_lvl).any(axis=1)
    for i in range(n):
        if a.recoveries[i] != b.recoveries[i]:
            bad[i] = True
    return int(bad.sum())


def parity_check(horizon: int = 900) -> dict:
    cost = campaign_cost()
    lanes = parity_lanes(horizon)
    a = BatchedCampaign(cost, lanes).run()
    b = DeviceCampaign(cost, lanes).run()
    div = divergent_lanes(a, b)
    out = {"lanes": len(lanes), "ticks": horizon,
           "divergent_lanes": div,
           "fma_contraction": bool(fma_contraction_active())}
    print(f"  parity: {len(lanes)} lanes x {horizon} ticks, "
          f"{div} divergent (fma_contraction={out['fma_contraction']})")
    return out


# ---------------------------------------------------------------------------
# exhaustive sweep vs top-k replay (E4 scenario)
# ---------------------------------------------------------------------------

def _e4_surfaces(cost: SimCostModel) -> tuple[QoSModel, QoSModel]:
    """Analytic stand-in QoS surfaces on the E4 envelope — the surfaces
    only pick the shortlist; the replay measurements decide the winner,
    which is exactly the top-k-vs-exhaustive comparison under test."""
    rng = np.random.default_rng(0)
    ci = rng.uniform(10, 240, 200)
    tr = rng.uniform(2000, 3600, 200)
    m_l = QoSModel().fit(ci, tr, cost.base_latency_s + 40.0 / ci + tr * 1e-5)
    m_r = QoSModel().fit(ci, tr, 80.0 + 1.2 * ci + 0.01 * tr)
    return m_l, m_r


def _measured_objective(res) -> float:
    objs = [c.sim["objective"] for c in res.candidates
            if c.sim is not None and c.sim["feasible"]]
    return float(min(objs)) if objs else float("nan")


def bench_sweep(rate: float = 3000.0, l_const: float = 2.0,
                r_const: float = 600.0, max_recovery_s: float = 1200.0,
                grid: int = 64) -> dict:
    cost = SimCostModel(capacity_eps=4600.0, base_latency_s=0.5,
                        ckpt_duration_s=3.0, ckpt_sync_penalty=0.6)
    m_l, m_r = _e4_surfaces(cost)
    kw = dict(tr_avg=rate, l_const=l_const, r_const=r_const, p=1.0,
              ci_min=10.0, ci_max=240.0, cost=cost, grid=grid)

    ver = make_plan_verifier(cost, schedule=constant_rate(rate),
                             warmup_s=120.0, max_recovery_s=max_recovery_s)
    t0 = time.perf_counter()
    res_top = optimize_plan(m_l, m_r, verifier=ver, verify_top_k=3, **kw)
    wall_top = time.perf_counter() - t0

    ver = make_plan_verifier(cost, schedule=constant_rate(rate),
                             warmup_s=120.0, max_recovery_s=max_recovery_s)
    t0 = time.perf_counter()
    res_ex = optimize_plan(m_l, m_r, verifier=ver, exhaustive=True,
                           engine="device", **kw)
    wall_ex = time.perf_counter() - t0

    out = {
        "variants": len(res_top.candidates),
        "replayed_topk": sum(1 for c in res_top.candidates
                             if c.sim is not None),
        "replayed_exhaustive": sum(1 for c in res_ex.candidates
                                   if c.sim is not None),
        "topk_wall_s": wall_top,
        "exhaustive_wall_s": wall_ex,
        "topk_objective": _measured_objective(res_top),
        "exhaustive_objective": _measured_objective(res_ex),
        "topk_plan": res_top.plan.name if res_top.plan else None,
        "exhaustive_plan": res_ex.plan.name if res_ex.plan else None,
        "topk_ci": res_top.ci,
        "exhaustive_ci": res_ex.ci,
    }
    print(f"  sweep: top-3 replay {wall_top:.1f}s (obj "
          f"{out['topk_objective']:.4f}, {out['topk_plan']}) vs exhaustive "
          f"{out['replayed_exhaustive']}-candidate device replay "
          f"{wall_ex:.1f}s (obj {out['exhaustive_objective']:.4f}, "
          f"{out['exhaustive_plan']})")
    return out


def device_section(smoke: bool = False) -> dict:
    """The "device" section of the bench_sim/3 artifact."""
    print("\n=== Device campaign engine (E12) ===")
    if smoke:
        # tiny but complete: a real two-engine throughput point, the full
        # parity matrix at a short horizon, no sweep (run.py --smoke must
        # stay accelerator-free and minute-scale; the validator accepts a
        # null sweep)
        throughput = bench_throughput(lane_counts=(256,), horizon=400)
        parity = parity_check(horizon=400)
        sweep = None
    else:
        throughput = bench_throughput()
        parity = parity_check()
        sweep = bench_sweep()
    return {"throughput": throughput, "parity": parity, "sweep": sweep}


def main() -> dict:
    return device_section(smoke=False)


if __name__ == "__main__":
    main()
