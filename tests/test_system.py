"""End-to-end behaviour: the full three-phase Khaos pipeline against the
simulator, and Khaos vs static baselines on a compressed workload — the
miniature of the paper's evaluation (benchmarks/ runs the full-size one)."""
import numpy as np
import pytest

from repro.config import KhaosConfig
from repro.core import (KhaosController, QoSModel, run_profiling,
                        select_failure_points, young_daly_interval)
from repro.data.stream import diurnal_rate, record_workload
from repro.ft.failures import FailureInjector
from repro.sim import (SimCostModel, SimDeployment, SimJobHandle,
                       StreamSimulator)


@pytest.fixture(scope="module")
def pipeline():
    sched = diurnal_rate(base=2000, amplitude=0.5, period=7200, seed=11)
    rec = record_workload(sched, duration=7200, seed=11)
    cost = SimCostModel(capacity_eps=3600.0, ckpt_duration_s=2.0)
    ss = select_failure_points(rec, m=4, smoothing_window=30)
    prof = run_profiling(lambda ci: SimDeployment(ci, rec, cost, warmup_s=200),
                         ss, [15, 45, 90, 180], margin=60)
    ci_f, tr_f, L_f, R_f = prof.flat()
    m_l = QoSModel().fit(ci_f, tr_f, L_f)
    m_r = QoSModel().fit(ci_f, tr_f, R_f)
    return sched, rec, cost, ss, prof, m_l, m_r


def test_phase1_phase2_produce_full_grids(pipeline):
    _, _, _, ss, prof, _, _ = pipeline
    assert prof.latencies.shape == (4, 4)       # m x z
    assert prof.recoveries.shape == (4, 4)
    assert np.all(prof.recoveries > 0)
    assert np.all(np.isfinite(prof.latencies))


def test_phase3_models_in_paper_error_band(pipeline):
    _, _, _, _, prof, m_l, m_r = pipeline
    ci_f, tr_f, L_f, R_f = prof.flat()
    # paper reports 9-12% (latency) and 7-13% (recovery) avg percent error;
    # in-sample fit must be at least that good
    assert m_l.avg_percent_error(ci_f, tr_f, L_f) < 0.15
    assert m_r.avg_percent_error(ci_f, tr_f, R_f) < 0.30


def test_khaos_beats_worst_static_on_recovery_violations(pipeline):
    sched, rec, cost, ss, prof, m_l, m_r = pipeline
    kcfg = KhaosConfig(latency_constraint=1.0, recovery_constraint=400.0,
                       optimization_period=60.0, ci_min=15, ci_max=180,
                       reconfig_cooldown=120.0)
    fail_times = [2400.0, 4800.0]

    def evaluate(ci_static=None):
        sim = StreamSimulator(cost, ci_s=ci_static or 60.0, schedule=sched)
        job = SimJobHandle(sim)
        ctl = None
        if ci_static is None:
            ctl = KhaosController(cfg=kcfg, m_l=m_l, m_r=m_r)
        inj = FailureInjector()
        for ft in fail_times:
            t = inj.worst_case_time(ft, 0.0, sim.policy.interval_s,
                                    cost.ckpt_duration_s)
            sim.inject_failure(t)
        while sim.t < 7200:
            sim.tick()
            if ctl is not None:
                ctl.maybe_optimize(job)
        recs = [r["recovery_s"] for r in sim.recoveries]
        viol = sum(max(0.0, r - kcfg.recovery_constraint) for r in recs)
        return viol, recs

    viol_khaos, recs_khaos = evaluate(None)
    viol_180, _ = evaluate(180.0)
    assert len(recs_khaos) == 2
    # Khaos must not be worse than the most violating static config
    assert viol_khaos <= viol_180 + 1e-9


def test_young_daly_baseline_in_range(pipeline):
    _, _, cost, _, _, _, _ = pipeline
    w = young_daly_interval(cost.ckpt_duration_s, mtbf_s=3600.0)
    assert 60 < w < 240


def test_compile_cache_dir_env_wins_else_checkout(monkeypatch):
    """Entry points keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says and set none of their own; unset, it
    goes to the fixed .jax_cache/ at the checkout root."""
    import os

    import jax

    from repro.utils.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert str(CHECKOUT_CACHE_DIR) == os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

