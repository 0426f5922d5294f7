"""Device-resident mega-campaigns: the jitted/vmapped third engine.

The sim package is a three-engine hierarchy (ROADMAP item 4):

  1. ``StreamSimulator`` — the scalar ORACLE.  One job, one Python tick
     loop, statement-level readability.  Ground truth for semantics.
  2. ``BatchedCampaign`` — the NumPy LANE engine.  N jobs as array lanes,
     one fused NumPy tick, bit-exact against the oracle (~27x scalar).
     Ground truth for the vectorized tick ORDER.
  3. ``DeviceCampaign`` (this module) — the DEVICE engine.  The same fused
     tick traced once into a jitted ``lax.fori_loop`` program and executed
     on the accelerator: struct-of-arrays lane state lives in device
     buffers, λ(t) is the ``dense_rates`` precompute uploaded once
     (deduplicated by shared rate array), plan/cost scalars are gathered
     per lane from the packed ``_PlanTable`` parameter tables, per-lane
     branches become ``lax.while_loop``/masked ``where`` updates, and lag
     history comes back in chunked device→host readbacks instead of
     per-tick row writes.

Each engine is authoritative one level down: the scalar oracle defines
WHAT a tick does, the NumPy engine defines the floating-point ORDER of
the batched tick, and on the CPU backend the device engine reproduces
that order bit-exactly (``tests/test_device_campaign.py`` asserts
``assert_array_equal`` parity across plans, crash kinds, degradation
kinds, and mid-run plan switches).  The claim is for XLA:CPU only: a TPU
has no native f64, XLA emulates it, and on a v5e chip all 60 lanes of
that parity matrix diverge from NumPy while every lane stays finite
(``chip_smoke.py``'s campaign phase prints the count).  Use the scalar
for semantics work,
the NumPy engine for moderate grids and as the parity reference, and the
device engine for mega-campaigns (10^5+ lanes) and exhaustive plan
sweeps (``optimize_plan(..., exhaustive=True, engine="device")``).

``DeviceCampaign`` subclasses ``BatchedCampaign``: construction, lane
actuation (``lane_set_ci``/``lane_set_plan``), compaction, handles, and
every result surface reuse the host-side code; only ``run`` is replaced
by a device-chunk advance that syncs the full lane state host<->device at
chunk boundaries.  Between chunks the host state is exactly what the
NumPy engine would hold, so ``drive_campaign`` controllers actuate lanes
mid-run without knowing which engine is underneath.

Bit-exactness on CPU requires one backend flag.  XLA:CPU keeps f64
multiply-adds as separate HLO ops, but LLVM contracts them into FMAs on
FMA-capable ISAs (AVX2+), producing 1-ULP divergences from NumPy in
chains like ``0.9*s + 0.1*lag`` (neither ``optimization_barrier`` nor
``--xla_cpu_enable_fast_math=false`` prevents the contraction).
``--xla_cpu_max_isa=AVX`` pins codegen to a pre-FMA ISA and restores
bit-exact parity; ``ensure_bitexact_cpu()`` appends it to ``XLA_FLAGS``
(it must run before the first backend initialization — importing this
module is enough when nothing has touched jax yet), and
``fma_contraction_active()`` probes whether contraction is still on so
benchmarks can report parity honestly.
"""
from __future__ import annotations

import os
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

from repro.sim.batched import (_DEG_ID, _DIR_ID, BatchedCampaign, LaneSpec)
from repro.sim.costmodel import SimCostModel

_ISA_FLAG = "--xla_cpu_max_isa=AVX"


def ensure_bitexact_cpu() -> None:
    """Append ``--xla_cpu_max_isa=AVX`` to ``XLA_FLAGS`` if absent.

    Only effective before the first XLA backend initialization (the env
    var is read lazily at first computation); call it as early as the
    process allows — tests do it in conftest, benchmarks at driver start.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_cpu_max_isa" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + _ISA_FLAG).strip()


ensure_bitexact_cpu()

import jax                                                          # noqa: E402
import jax.numpy as jnp                                             # noqa: E402
from jax import lax                                                 # noqa: E402


def fma_contraction_active() -> bool:
    """True when jitted f64 mul-add chains still diverge from NumPy (the
    ISA pin did not take, e.g. a backend was initialized first)."""
    rng = np.random.default_rng(0)
    a = rng.random(256)
    b = rng.random(256)
    with jax.enable_x64(True):
        jv = np.asarray(jax.jit(lambda x, y: 0.9 * x + 0.1 * y)(
            jnp.asarray(a), jnp.asarray(b)))
    return not np.array_equal(0.9 * a + 0.1 * b, jv)


#: per-lane read-only inputs (may change between chunks via actuation)
_LANE_CONST = ("interval", "plan_id", "_period", "_mu_ck", "lane_ticks")
_FAIL_CONST = ("fail_t", "fail_kind")
_DEG_CONST = ("deg_t", "deg_kind", "deg_dur", "deg_sev", "deg_jit",
              "deg_dir")


def _carry_partition(any_deg: bool, has_fail: bool, track_af: bool
                     ) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Split the per-lane state into (carried, read-only-const) names for
    one chunk configuration.  Every array the loop body passes through
    unchanged costs XLA:CPU a per-tick buffer copy, so state a given
    configuration cannot mutate rides as a constant input instead (or is
    dropped entirely when it cannot even be read)."""
    carried = ["t", "lag", "produced", "consumed", "processed_total",
               "pol_last", "off_lvl", "last_off", "ck_active", "ck_end",
               "ck_off", "ck_lvls", "ckpt_count", "save_count", "down",
               "steady_lag"]
    consts: list[str] = []
    if has_fail:
        carried += ["down_until", "pending_ro", "fptr", "_next_fail",
                    "af_t0", "af_kind", "af_ci", "af_level"]
    else:
        consts += ["down_until", "pending_ro"]
        if track_af:
            consts += ["af_t0", "af_kind", "af_ci", "af_level"]
    if track_af:
        carried += ["af_active", "_rec_t_start", "_rec_kind", "_rec_ci",
                    "_rec_level", "_rec_t_end", "_rec_count"]
    if any_deg:
        carried += ["dptr", "_next_deg", "dg_cap_scale", "dg_cap_until",
                    "dg_ck_delay", "dg_ck_jitter", "dg_ck_t0",
                    "dg_ck_until", "dg_lat_delay", "dg_lat_jitter",
                    "dg_lat_t0", "dg_lat_until", "dg_bp_until",
                    "bp_suppressed"]
    return tuple(carried), tuple(consts)

_DEG_STRAGGLER = _DEG_ID["straggler"]
_DEG_NET = _DEG_ID["net_delay"]
_DEG_BP = _DEG_ID["backpressure"]
_DIR_STORE = _DIR_ID["to_ckpt_store"]
_DIR_SOURCE = _DIR_ID["to_source"]


@lru_cache(maxsize=32)
def _chunk_fn(hist_rows: int, any_deg: bool, has_fail: bool,
              lat_extra: bool, track_af: bool):
    """Compile one device-chunk program: ``hist_rows`` (static) rows of lag
    history per call (0 = no recording), tick count ``n`` traced.  The tick
    body mirrors ``BatchedCampaign._step`` statement-for-statement in the
    same floating-point order; every structural `if` below is a STATIC
    configuration switch, never per-lane control flow."""
    carried, ro_consts = _carry_partition(any_deg, has_fail, track_af)
    carried_set = frozenset(carried)

    def phase(t, t0):
        # ft.failures.jitter_phase, traced (np.where on tracers would fail)
        return jnp.where((t - t0) % 2.0 < 1.0, 1.0, -1.0)

    def begin_failure(s, c, mask, kind, ev_t):
        # BatchedCampaign._begin_failure (the early-return on an empty mask
        # is a no-op: every write below is masked by `act`)
        act = mask & ~s["down"]
        ck_active = s["ck_active"] & ~act
        pid = c["plan_id"]
        surv = c["surviving"][pid, kind]
        offs = jnp.where(surv, s["off_lvl"], -jnp.inf)
        best = offs.max(axis=1)
        has = surv.any(axis=1)
        lvl = jnp.argmax(offs == best[:, None], axis=1)
        restore = jnp.where(has, c["restore_dur"][pid, kind, lvl],
                            c["cold_restore"][pid])
        offset = jnp.where(has, best, 0.0)
        wipe = c["wipes"][pid, kind]
        return dict(
            s, ck_active=ck_active,
            off_lvl=jnp.where(act[:, None] & wipe, 0.0, s["off_lvl"]),
            down_until=jnp.where(
                act, ev_t + c["detect_s"] + c["restart_s"] + restore,
                s["down_until"]),
            pending_ro=jnp.where(act, offset, s["pending_ro"]),
            down=s["down"] | act,
            af_active=s["af_active"] | act,
            af_t0=jnp.where(act, ev_t, s["af_t0"]),
            af_kind=jnp.where(act, kind, s["af_kind"]),
            af_ci=jnp.where(act, c["interval"], s["af_ci"]),
            af_level=jnp.where(act, jnp.where(has, lvl, -1), s["af_level"]))

    def begin_degradation(s, c, mask, cur):
        # BatchedCampaign._begin_degradation (kind-specific masked writes)
        ar = jnp.arange(cur.shape[0])
        kind = c["deg_kind"][ar, cur]
        ev_t = c["deg_t"][ar, cur]
        until = ev_t + c["deg_dur"][ar, cur]
        sev = c["deg_sev"][ar, cur]
        jit = c["deg_jit"][ar, cur]
        dirn = c["deg_dir"][ar, cur]
        m = mask & (kind == _DEG_STRAGGLER)
        scale = 1.0 / (1.0 + c["sbf"] * (jnp.maximum(sev, 1.0) - 1.0))
        out = dict(s,
                   dg_cap_scale=jnp.where(m, scale, s["dg_cap_scale"]),
                   dg_cap_until=jnp.where(m, until, s["dg_cap_until"]))
        nd = mask & (kind == _DEG_NET)
        m = nd & (dirn == _DIR_STORE)
        out.update(dg_ck_delay=jnp.where(m, sev, s["dg_ck_delay"]),
                   dg_ck_jitter=jnp.where(m, jit, s["dg_ck_jitter"]),
                   dg_ck_t0=jnp.where(m, ev_t, s["dg_ck_t0"]),
                   dg_ck_until=jnp.where(m, until, s["dg_ck_until"]))
        m = nd & (dirn == _DIR_SOURCE)
        out.update(dg_lat_delay=jnp.where(m, sev, s["dg_lat_delay"]),
                   dg_lat_jitter=jnp.where(m, jit, s["dg_lat_jitter"]),
                   dg_lat_t0=jnp.where(m, ev_t, s["dg_lat_t0"]),
                   dg_lat_until=jnp.where(m, until, s["dg_lat_until"]))
        m = mask & (kind == _DEG_BP)
        out.update(dg_bp_until=jnp.where(m, until, s["dg_bp_until"]))
        return out

    def chunk(s, c, k0, n):
        rates_u, rate_col = c["rates_u"], c["rate_col"]
        lane_ticks = c["lane_ticks"]
        n_act = rate_col.shape[0]
        Kf = c["fail_t"].shape[1] if has_fail else 0
        Kd = c["deg_t"].shape[1] if any_deg else 0
        R = s["_rec_t_start"].shape[1] if track_af else 0
        ar = jnp.arange(n_act)

        def tick(i, carry):
            st, hist, lat = carry

            def get(name):
                # carried state from the loop carry, frozen state from the
                # constant inputs (static per configuration)
                return st[name] if name in carried_set else c[name]

            k = k0 + i
            t = st["t"]
            alive = k < lane_ticks
            lam = jnp.where(alive, rates_u[k][rate_col], 0.0)
            st = dict(st, produced=st["produced"] + lam)

            if has_fail:
                def f_cond(s2):
                    return jnp.any((s2["_next_fail"] <= t) & alive)

                def f_body(s2):
                    pend = (s2["_next_fail"] <= t) & alive
                    cur = jnp.minimum(s2["fptr"], Kf - 1)
                    s2 = begin_failure(s2, c, pend, c["fail_kind"][ar, cur],
                                       s2["_next_fail"])
                    fptr = jnp.where(pend, s2["fptr"] + 1, s2["fptr"])
                    nxt = jnp.minimum(fptr, Kf - 1)
                    nf = jnp.where(fptr < Kf, c["fail_t"][ar, nxt], jnp.inf)
                    return dict(s2, fptr=fptr, _next_fail=nf)

                st = lax.while_loop(f_cond, f_body, st)

            if any_deg:
                def d_cond(s2):
                    return jnp.any((s2["_next_deg"] <= t) & alive)

                def d_body(s2):
                    pend = (s2["_next_deg"] <= t) & alive
                    cur = jnp.minimum(s2["dptr"], Kd - 1)
                    s2 = begin_degradation(s2, c, pend, cur)
                    dptr = jnp.where(pend, s2["dptr"] + 1, s2["dptr"])
                    nxt = jnp.minimum(dptr, Kd - 1)
                    ndg = jnp.where(dptr < Kd, c["deg_t"][ar, nxt], jnp.inf)
                    return dict(s2, dptr=dptr, _next_deg=ndg)

                st = lax.while_loop(d_cond, d_body, st)

            # down lanes accumulate lag; restart rolls back to the offset
            down_pre = st["down"]
            lag = jnp.where(alive & down_pre, st["lag"] + lam, st["lag"])
            restart = alive & down_pre & (t >= get("down_until"))
            rb = restart & (get("pending_ro") < st["consumed"])
            lag = jnp.where(rb, lag + (st["consumed"] - get("pending_ro")),
                            lag)
            consumed = jnp.where(rb, get("pending_ro"), st["consumed"])
            down = down_pre & ~restart
            pol_last = jnp.where(restart, t, st["pol_last"])
            # a lane restarting this tick stays out of processing (the
            # NumPy `up` is taken before the restart clears `down`)
            up = alive & ~down_pre

            # checkpoint completion
            comp = up & st["ck_active"] & (t >= st["ck_end"])
            off = st["ck_off"]
            off_lvl = jnp.where(comp[:, None] & st["ck_lvls"], off[:, None],
                                st["off_lvl"])
            last_off = jnp.where(comp, jnp.maximum(st["last_off"], off),
                                 st["last_off"])
            ckpt_count = st["ckpt_count"] + comp
            ck_active = st["ck_active"] & ~comp

            # checkpoint start
            due = up & (t - pol_last >= c["interval"]) & ~ck_active
            if any_deg:
                bp = due & (t < st["dg_bp_until"])
                bp_suppressed = st["bp_suppressed"] + bp
                due = due & ~bp
            idx = st["save_count"] % c["_period"]
            save_count = st["save_count"] + due
            dur = c["trig_dur"][c["plan_id"], idx]
            if any_deg:
                ckd = t < st["dg_ck_until"]
                pen = c["store_f"] * st["dg_ck_delay"] \
                    + st["dg_ck_jitter"] * phase(t, st["dg_ck_t0"])
                dur = dur + jnp.where(ckd, pen, 0.0)
            ck_end = jnp.where(due, t + dur, st["ck_end"])
            ck_off = jnp.where(due, consumed, st["ck_off"])
            ck_lvls = jnp.where(due[:, None],
                                c["trig_lvls"][c["plan_id"], idx],
                                st["ck_lvls"])
            ck_active = ck_active | due
            pol_last = jnp.where(due, t, pol_last)

            # capacity + processing
            checkpointing = up & ck_active
            if any_deg:
                reset = up & (t >= st["dg_cap_until"])
                dg_cap_scale = jnp.where(reset, 1.0, st["dg_cap_scale"])
                mu = jnp.where(checkpointing, c["_mu_ck"], c["eps"]) \
                    * dg_cap_scale
            else:
                mu = jnp.where(checkpointing, c["_mu_ck"], c["eps"])
            inflow = lag + lam
            processed = jnp.where(up, jnp.minimum(inflow, mu), 0.0)
            lag = jnp.where(up, jnp.maximum(0.0, inflow - processed), lag)
            consumed = consumed + processed
            processed_total = st["processed_total"] + processed

            if hist_rows:
                # the NumPy step skips the row write entirely when no lane
                # is alive (leaving the zero initialization in place)
                any_alive = jnp.any(alive)
                hist = hist.at[i].set(jnp.where(any_alive, lag, 0.0))
                if lat_extra:
                    la = alive & (t < st["dg_lat_until"])
                    pen = jnp.where(
                        la, c["src_f"] * st["dg_lat_delay"]
                        + st["dg_lat_jitter"] * phase(t, st["dg_lat_t0"]),
                        0.0)
                    lat = lat.at[i].set(pen)

            # recovery bookkeeping (records scattered into bounded per-lane
            # slots; the host materializes dicts after the chunk)
            settled = alive & ~down          # post-restart down
            st = dict(
                st, t=jnp.where(alive, t + 1.0, t), lag=lag,
                consumed=consumed, processed_total=processed_total,
                pol_last=pol_last, down=down, off_lvl=off_lvl,
                last_off=last_off, ck_active=ck_active, ck_end=ck_end,
                ck_off=ck_off, ck_lvls=ck_lvls, ckpt_count=ckpt_count,
                save_count=save_count)
            if any_deg:
                st.update(bp_suppressed=bp_suppressed,
                          dg_cap_scale=dg_cap_scale)
            if track_af:
                env = lag <= jnp.maximum(2.0 * lam,
                                         1.05 * st["steady_lag"] + 1.0)
                af_active = st["af_active"]
                upd = settled & ~af_active
                near = af_active & settled & env
                j = jnp.minimum(st["_rec_count"], R - 1)
                # one-hot masked writes, NOT .at[].set: XLA:CPU lowers
                # scatter to a serial row loop (~90x slower than an
                # elementwise pass)
                slot = (jnp.arange(R)[None, :] == j[:, None]) & near[:, None]

                def rec_set(arr, val):
                    return jnp.where(slot, val[:, None], arr)

                st.update(
                    af_active=af_active & ~near,
                    _rec_t_start=rec_set(st["_rec_t_start"], get("af_t0")),
                    _rec_kind=rec_set(st["_rec_kind"], get("af_kind")),
                    _rec_ci=rec_set(st["_rec_ci"], get("af_ci")),
                    _rec_level=rec_set(st["_rec_level"], get("af_level")),
                    _rec_t_end=rec_set(st["_rec_t_end"], t),
                    _rec_count=st["_rec_count"] + near)
            else:
                upd = settled
            st["steady_lag"] = jnp.where(
                upd, 0.9 * st["steady_lag"] + 0.1 * lag, st["steady_lag"])
            return (st, hist, lat)

        hist0 = jnp.zeros((hist_rows, n_act))
        lat0 = jnp.zeros((hist_rows if lat_extra else 0, n_act))
        return lax.fori_loop(0, n, tick, (s, hist0, lat0))

    return jax.jit(chunk)


class DeviceCampaign(BatchedCampaign):
    """``BatchedCampaign`` advanced by the jitted device program.

    Construction, per-lane actuation, compaction, handles, and all result
    surfaces are inherited; ``run`` advances the lane state in device
    chunks that are bit-exact with the corresponding number of NumPy
    ``_step`` calls, syncing the full host state at every chunk boundary
    (so mid-run ``lane_set_ci``/``lane_set_plan`` between ``run`` calls
    behave identically to the NumPy engine).

    ``compact_every`` defaults to 0 here: compaction changes the active
    lane count, which forces an XLA retrace per new shape.  It remains
    fully supported (pass a nonzero value) for long mixed-horizon runs
    where the retrace amortizes.

    ``history_chunk_bytes`` bounds the device-side lag-history buffer; a
    recording campaign advances in ``history_chunk_bytes / (8 * n_lanes)``
    -tick chunks and copies each chunk's rows back to the host history
    matrix in one readback.
    """

    _PER_LANE = BatchedCampaign._PER_LANE + (
        "_rec_t_start", "_rec_kind", "_rec_ci", "_rec_level", "_rec_t_end",
        "_rec_count", "_rec_seen")

    def __init__(self, cost: SimCostModel, lanes: Sequence[LaneSpec],
                 record_history: bool = True, flink_semantics: bool = True,
                 early_exit: bool = False, compact_every: int = 0,
                 history_chunk_bytes: int = 64 << 20):
        super().__init__(cost, lanes, record_history=record_history,
                         flink_semantics=flink_semantics,
                         early_exit=early_exit, compact_every=compact_every)
        N = self.n_lanes
        R = max(1, self._n_fail)
        self._rec_t_start = np.zeros((N, R))
        self._rec_kind = np.zeros((N, R), dtype=np.int64)
        self._rec_ci = np.zeros((N, R))
        self._rec_level = np.full((N, R), -1, dtype=np.int64)
        self._rec_t_end = np.zeros((N, R))
        self._rec_count = np.zeros(N, dtype=np.int64)
        self._rec_seen = np.zeros(N, dtype=np.int64)
        # λ columns deduplicated by shared rate array: lanes built from one
        # recording all point at the same dense_rates precompute, so the
        # big (T, W) upload holds W unique columns, not N
        col_of: dict[int, int] = {}
        firsts: list[int] = []
        self._rate_col_all = np.zeros(N, dtype=np.int64)
        for i, l in enumerate(self.lanes):
            w = col_of.setdefault(id(l.rates), len(col_of))
            self._rate_col_all[i] = w
            if w == len(firsts):
                firsts.append(i)
        self._rates_u = np.ascontiguousarray(self._rates_tm[:, firsts])
        self._rates_dev = None
        if record_history:
            rows = int(history_chunk_bytes) // (8 * max(1, N))
            self._hist_rows = max(16, min(self.horizon, rows))
        else:
            self._hist_rows = 0

    # -- device advance -------------------------------------------------
    def run(self, n_ticks: Optional[int] = None) -> "DeviceCampaign":
        end = self.horizon if n_ticks is None \
            else min(self.horizon, self._step_idx + n_ticks)
        ce = self.compact_every
        while self._step_idx < end and self._active.size:
            stop = min(end, ((self._step_idx // ce) + 1) * ce) if ce else end
            left = stop - self._step_idx
            while left > 0:
                c = min(left, self._hist_rows) if self._hist_rows else left
                self._device_chunk(c)
                left -= c
            if ce and self._step_idx % ce == 0:
                self._maybe_compact()
        if self.done:
            self._finalize()
        return self

    def _device_chunk(self, n: int) -> None:
        has_fail = bool(np.isfinite(self._next_fail).any())
        lat_extra = self._lat_extra_tm is not None
        # recovery tracking is needed only while a failure can still fire
        # or a recovery is in flight — the common no-failure throughput
        # configuration then carries no af/rec state at all
        track_af = has_fail or bool(self.af_active.any())
        carried, ro_consts = _carry_partition(self._any_deg, has_fail,
                                              track_af)
        fn = _chunk_fn(self._hist_rows, self._any_deg, has_fail, lat_extra,
                       track_af)
        cost = self.cost
        with jax.enable_x64(True):
            if self._rates_dev is None:
                self._rates_dev = jnp.asarray(self._rates_u)
            const_names = _LANE_CONST + ro_consts
            if has_fail:
                const_names += _FAIL_CONST
            if self._any_deg:
                const_names += _DEG_CONST
            c = {name: jnp.asarray(getattr(self, name))
                 for name in const_names}
            c.update(
                rates_u=self._rates_dev,
                rate_col=jnp.asarray(self._rate_col_all[self._active]),
                trig_dur=jnp.asarray(self.table.trig_dur),
                trig_lvls=jnp.asarray(self.table.trig_lvls),
                eps=jnp.float64(cost.capacity_eps),
                sbf=jnp.float64(cost.straggler_barrier_fraction),
                store_f=jnp.float64(cost.net_delay_store_factor),
                src_f=jnp.float64(cost.net_delay_source_factor))
            if has_fail:
                c.update(
                    restore_dur=jnp.asarray(self.table.restore_dur),
                    cold_restore=jnp.asarray(self.table.cold_restore),
                    surviving=jnp.asarray(self.table.surviving),
                    wipes=jnp.asarray(self.table.wipes),
                    detect_s=jnp.float64(cost.detect_s),
                    restart_s=jnp.float64(cost.restart_s))
            s = {name: jnp.asarray(getattr(self, name)) for name in carried}
            s, hist, lat = fn(s, c, self._step_idx, n)
            # np.array (not asarray): device buffers come back read-only,
            # and host-side actuation/compaction mutates these in place
            out = {name: np.array(s[name]) for name in carried}
        for name, arr in out.items():
            setattr(self, name, arr)
        if self._hist_rows:
            k0 = self._step_idx
            rows = np.asarray(hist)[:n]
            if self._final is None:
                self._lag_hist_tm[k0:k0 + n] = rows
            else:
                self._lag_hist_tm[k0:k0 + n, self._active] = rows
            if lat_extra:
                lrows = np.asarray(lat)[:n]
                if self._final is None:
                    self._lat_extra_tm[k0:k0 + n] = lrows
                else:
                    self._lat_extra_tm[k0:k0 + n, self._active] = lrows
        self._step_idx += n
        self._materialize_recoveries()

    def _materialize_recoveries(self) -> None:
        """Append recovery dicts for records the device scattered since the
        last chunk (same shape as the NumPy engine's in-loop appends; done
        before any compaction so retiring lanes never strand records)."""
        from repro.sim.batched import KINDS, LEVELS
        new = np.flatnonzero(self._rec_count > self._rec_seen)
        for i in new:
            oi = int(self._active[i])
            for j in range(int(self._rec_seen[i]), int(self._rec_count[i])):
                lvl = int(self._rec_level[i, j])
                t_end = float(self._rec_t_end[i, j])
                t_start = float(self._rec_t_start[i, j])
                self.recoveries[oi].append({
                    "t_start": t_start,
                    "kind": KINDS[int(self._rec_kind[i, j])],
                    "ci": float(self._rec_ci[i, j]),
                    "restore_level": LEVELS[lvl] if lvl >= 0 else None,
                    "plan": self.lane_plan_name[oi],
                    "t_end": t_end,
                    "recovery_s": float(t_end - t_start),
                })
            self._rec_seen[i] = self._rec_count[i]
