"""The adaptive execution planner (PR 18, ROADMAP item 4).

One process-wide ``ExecutionPlanner`` closes the loop between the
analytic cost model and the measured runtime:

- **Predict**: an arm's wall time is its kernel's ideal roofline time
  (max of flops/peak_flops, bytes/peak_bw, ici_bytes/peak_ici from the
  PR-5 cost model) divided by that kernel's *measured* achieved-roofline
  EMA. The EMA is fed by every `telemetry.time_kernel` exit (the same
  utilization record that drives the MFU/bw histograms), so the
  predictor prices each arm at the efficiency this host actually
  achieves — not the datasheet peak.

- **Choose**: every arm dispatch site routes through
  ``choose_arm(site, candidates)`` with its eligible arms in today's
  static priority order (fused > impact > exact). Cold state (any
  candidate unpredictable) falls back to the FIRST candidate — byte-
  identical to the pre-planner routing; warm state picks the argmin of
  the predictions. The registry of sites/arms/kernels (``ARM_SITES``)
  is lint-enforced (tests/test_planner.py): no orphan env-gate routing.

- **Feed back**: at observe time the planner recomputes the prediction
  it would have made for the dispatch (pre-update state) and exports
  the relative residual (actual − predicted) / predicted as the
  ``es.planner.residual`` histogram + per-kernel gauge, the PR-12 drift
  discipline; `slo.planner.residual` turns the worst kernel's |residual|
  EMA into a standing SLO floor.

- **Reprice**: the PR-14 degradation pins are subsumed — a device OOM
  reprices the fused (and, for the retry, impact) arm to ∞ (filtered
  from the candidate list) instead of pinning `ES_TPU_FUSED=0` env
  vars; the repricing lifts when the recovery ramp finishes.

- **Knobs**: the same predictor advises `knn.nprobe` from a latency
  target (`planner.knn.target_ms`), the serving wave close (effective
  max_wave / coalesce window from queue depth vs the measured drain and
  arrival EMAs), and request-cache admission by predicted recompute
  cost (`planner.cache.min_recompute_us`). Every knob is clamped to its
  static bounds and passes through untouched when cold or disabled.

State is deliberately tiny (dicts of floats under one lock): a decision
is pure dict/float arithmetic and stays well under the 100 µs budget.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager

# site -> arm -> the kernel whose cost model prices that arm. Keys are
# the literal choose_arm(...) site names at the dispatch call sites —
# the tier-1 lint (tests/test_planner.py) enforces the bijection, the
# same discipline KERNEL_COSTS gets from tests/test_monitoring.py.
# `sharded.msearch_merged` prices impact and exact through the same
# one-program kernel (sharded.allgather_topk) with different tier
# fields; their efficiency EMA is shared — documented, not hidden.
ARM_SITES: dict[str, dict[str, str]] = {
    "batched.msearch": {
        "fused": "fused.pallas_scan",
        "impact": "sparse.impact_sum",
        "exact": "batched.disjunction",
    },
    "sharded.msearch_merged": {
        "fused": "sharded.fused_allgather_topk",
        "impact": "sharded.allgather_topk",
        "exact": "sharded.allgather_topk",
    },
    "sharded.msearch_partials": {
        "fused": "sharded.fused_pipeline",
        "impact": "sharded.impact_disjunction",
        "exact": "sharded.exact_disjunction",
    },
}

_DEFAULTS = {
    "enabled": True,
    "alpha": 0.2,            # planner.ema.alpha
    "knn_target_ms": 0.0,    # planner.knn.target_ms (0 = advisory off)
    "cache_min_recompute_us": 0.0,  # planner.cache.min_recompute_us
}


class ExecutionPlanner:
    """Per-process planner state: kernel efficiency EMAs, residual
    tracking, arm repricing, decision accounting."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cfg = dict(_DEFAULTS)
        # kernel -> EMA of achieved roofline fraction (max of mfu /
        # bw_util / ici_util). Seeded lazily from the FIRST time_kernel
        # observation (the normalization basis is the KERNEL_COSTS
        # device peaks); an empty entry means COLD -> static fallback.
        self._eff: dict[str, float] = {}
        self._obs: dict[str, int] = {}
        # kernel -> EMA of posting rows per query, harvested from
        # observed dispatch fields: lets rows-dependent cost fns
        # (impact gather) price future dispatches before planning.
        self._rows_per_q: dict[str, float] = {}
        # kernel -> residual state (last, EMA of |residual|, count)
        self._residual: dict[str, dict] = {}
        # arm -> active repricing count (scoped ∞-cost contexts) and
        # arm -> {key: predicate} standing repricers (degradation state)
        self._repriced_scoped: dict[str, int] = {}
        self._repricers: dict[str, dict] = {}
        self._decisions: dict[str, int] = {}
        self._modes = {"model": 0, "static": 0, "repriced": 0}
        self._knobs = {"nprobe_adjustments": 0, "wave_adjustments": 0,
                       "cache_rejections": 0, "cache_admissions": 0}

    # -- configuration ------------------------------------------------------

    def configure(self, **kw) -> None:
        with self._lock:
            for key, val in kw.items():
                if key in self._cfg and val is not None:
                    self._cfg[key] = val

    @property
    def enabled(self) -> bool:
        if os.environ.get("ES_TPU_PLANNER", "1") == "0":
            return False
        return bool(self._cfg["enabled"])

    # -- the measurement feed (telemetry.time_kernel exit hook) -------------

    def observe(self, kernel: str, fields: dict, seconds: float,
                util: dict) -> None:
        """Fold one timed dispatch into the kernel's efficiency EMA and
        export the predicted-vs-actual residual. Never raises — the
        planner is routing advice, not the serving path."""
        achieved = max(util.get("mfu", 0.0), util.get("bw_util", 0.0),
                       util.get("ici_util", 0.0))
        if achieved <= 0 or seconds <= 0:
            return
        from ..telemetry import metrics

        with self._lock:
            # the prediction this dispatch WOULD have gotten (pre-update
            # EMA state) — the residual convention of BENCH_NOTES r22
            predicted_s = self._predict_seconds_locked(kernel, fields)
            alpha = float(self._cfg["alpha"])
            prev = self._eff.get(kernel)
            self._eff[kernel] = (achieved if prev is None
                                 else (1 - alpha) * prev + alpha * achieved)
            self._obs[kernel] = self._obs.get(kernel, 0) + 1
            rows, q = fields.get("rows"), fields.get("queries")
            if rows and q:
                rq = float(rows) / max(int(q), 1)
                prev_rq = self._rows_per_q.get(kernel)
                self._rows_per_q[kernel] = (
                    rq if prev_rq is None
                    else (1 - alpha) * prev_rq + alpha * rq)
            residual = None
            if predicted_s is not None and predicted_s > 0:
                residual = (seconds - predicted_s) / predicted_s
                st = self._residual.setdefault(
                    kernel, {"last": 0.0, "abs_ema": None, "count": 0})
                st["last"] = residual
                st["abs_ema"] = (
                    abs(residual) if st["abs_ema"] is None
                    else (1 - alpha) * st["abs_ema"] + alpha * abs(residual))
                st["count"] += 1
        if residual is not None:
            metrics.histogram_record("es.planner.residual", residual)
            metrics.gauge_set(f"es.planner.residual.{kernel}",
                              round(residual, 6))

    def observe_wall(self, kernel: str, fields: dict,
                     seconds: float) -> None:
        """Serving-path feed: on the wave route the arm kernels' own
        `time_kernel` exits fold into the ONE combined fetch
        (`serving.wave_program`), so no utilization record exists for
        the routed arm itself. Per-wave decision attribution
        (serving/service._record_flight) reports the arm's apportioned
        wall here and the achieved-roofline fraction is recovered from
        the analytic ideal — closing the same loop the solo paths close
        directly in `time_kernel`."""
        if seconds <= 0:
            return
        with self._lock:
            ideal = self._ideal_seconds(kernel, fields)
        if ideal is None or ideal <= 0:
            return
        self.observe(kernel, fields, seconds,
                     {"mfu": min(ideal / seconds, 1.0)})

    # -- prediction ---------------------------------------------------------

    def _ideal_seconds(self, kernel: str, fields: dict) -> float | None:
        """Roofline-ideal wall of one dispatch from the analytic cost
        model: max over the compute / HBM / ICI terms."""
        from ..monitoring.costmodel import device_peaks, ici_peak, kernel_cost

        cost = kernel_cost(kernel, fields)
        if cost is None and "rows" not in fields:
            # rows-dependent cost fn before planning: price with the
            # measured rows-per-query EMA when one exists
            rq = self._rows_per_q.get(kernel)
            q = fields.get("queries")
            if rq is not None and q:
                cost = kernel_cost(
                    kernel, {**fields, "rows": int(rq * int(q))})
        if cost is None:
            return None
        peak_f, peak_b, _kind = device_peaks()
        t = max(cost["flops"] / peak_f, cost["bytes"] / peak_b)
        if cost.get("ici_bytes"):
            t = max(t, cost["ici_bytes"] / ici_peak())
        return t

    def _predict_seconds_locked(self, kernel: str,
                                fields: dict) -> float | None:
        eff = self._eff.get(kernel)
        if eff is None or eff <= 0:
            return None
        t = self._ideal_seconds(kernel, fields)
        if t is None:
            return None
        return t / eff

    def predict_ms(self, kernel: str, fields: dict) -> float | None:
        """Predicted wall ms of one dispatch, or None while cold."""
        with self._lock:
            sec = self._predict_seconds_locked(kernel, fields)
        return None if sec is None else sec * 1000.0

    # -- repricing (subsumes the PR-14 degradation pins) --------------------

    def repriced(self, arm: str) -> bool:
        """An arm priced at ∞: filtered from every candidate list."""
        with self._lock:
            if self._repriced_scoped.get(arm, 0) > 0:
                return True
            preds = list(self._repricers.get(arm, {}).values())
        for fn in preds:
            try:
                if fn():
                    return True
            except Exception:  # noqa: BLE001 - a dead predicate never pins
                continue
        return False

    def repriced_arms(self) -> list[str]:
        arms = set(self._repriced_scoped) | set(self._repricers)
        return sorted(a for a in arms if self.repriced(a))

    @contextmanager
    def reprice(self, arms, reason: str = ""):
        """Scope in which `arms` cost ∞ (the device-OOM retry runs the
        exact arm through ordinary candidate filtering, not env pins)."""
        from ..telemetry import metrics

        arms = tuple(arms)
        with self._lock:
            for a in arms:
                self._repriced_scoped[a] = \
                    self._repriced_scoped.get(a, 0) + 1
        for a in arms:
            metrics.counter_inc(f"es.planner.repriced.{a}")
        try:
            yield
        finally:
            with self._lock:
                for a in arms:
                    n = self._repriced_scoped.get(a, 1) - 1
                    if n <= 0:
                        self._repriced_scoped.pop(a, None)
                    else:
                        self._repriced_scoped[a] = n

    def add_repricer(self, arm: str, key, predicate) -> None:
        """Standing repricer (e.g. DeviceDegradation.degraded): the arm
        stays at ∞ for as long as the predicate holds."""
        with self._lock:
            self._repricers.setdefault(arm, {})[key] = predicate

    def remove_repricer(self, arm: str, key) -> None:
        with self._lock:
            self._repricers.get(arm, {}).pop(key, None)

    # -- arm choice ---------------------------------------------------------

    def choose_arm(self, site: str, candidates, model: bool = True) -> str:
        """Pick one arm for a dispatch. `candidates` is a list of
        (arm, kernel, fields) in TODAY'S static priority order; the
        last entry must be the always-correct exact arm. Returns the
        arm name. Cold (any surviving candidate unpredictable) ->
        static fallback = first survivor, so an empty-EMA planner is
        byte-identical to the pre-planner routing. `model=False` keeps
        the static order whatever the EMAs hold (repricing still
        applies): for a site whose arms are families of compiled
        programs, where a flip of the model's mind costs a family."""
        t0 = time.perf_counter()
        alive = [c for c in candidates if not self.repriced(c[0])]
        mode = "static"
        if not alive:
            # everything repriced: the last candidate is the smallest-
            # footprint correct arm (the PR-14 stage-3 contract)
            alive = [candidates[-1]]
            mode = "repriced"
        chosen = alive[0]
        predicted: dict[str, float] = {}
        if model and self.enabled and len(alive) > 1:
            preds = []
            with self._lock:
                for arm, kernel, fields in alive:
                    preds.append(
                        self._predict_seconds_locked(kernel, fields))
            if all(p is not None for p in preds):
                mode = "model"
                best = min(range(len(preds)), key=lambda j: preds[j])
                chosen = alive[best]
            predicted = {alive[j][0]: round(preds[j] * 1000.0, 4)
                         for j in range(len(alive))
                         if preds[j] is not None}
        if len(alive) < len(candidates) and mode == "static":
            mode = "repriced"  # the filtering, not the model, routed this
        decision_us = (time.perf_counter() - t0) * 1e6
        arm = chosen[0]
        with self._lock:
            self._decisions[arm] = self._decisions.get(arm, 0) + 1
            self._modes[mode] = self._modes.get(mode, 0) + 1
        from ..telemetry import metrics, profile_event

        metrics.counter_inc(f"es.planner.decisions.{arm}")
        metrics.histogram_record("es.planner.decision_us", decision_us)
        # `priced_kernel`, not `kernel`: profile-event consumers treat a
        # `kernel` key as a utilization record (kind == "kernel")
        profile_event("planner", site=site, arm=arm, mode=mode,
                      priced_kernel=chosen[1], fields=dict(chosen[2]),
                      predicted_ms=predicted,
                      decision_us=round(decision_us, 2))
        return arm

    # -- knobs --------------------------------------------------------------

    def advise_nprobe(self, default_nprobe: int, nlist: int,
                      fields: dict) -> int:
        """Largest nprobe in [1, nlist] whose predicted ann.gather_scan
        wall stays under planner.knn.target_ms (binary search over the
        monotone cost). Cold / disabled / no target -> the default
        (coverage-heuristic) value, untouched."""
        target_ms = float(self._cfg["knn_target_ms"])
        if not self.enabled or target_ms <= 0:
            return default_nprobe
        kernel = "ann.gather_scan"
        with self._lock:
            if self._eff.get(kernel) is None:
                return default_nprobe
            lo, hi = 1, max(int(nlist), 1)
            best = 1
            while lo <= hi:
                mid = (lo + hi) // 2
                sec = self._predict_seconds_locked(
                    kernel, {**fields, "nprobe": mid})
                if sec is None:
                    return default_nprobe
                if sec * 1000.0 <= target_ms:
                    best = mid
                    lo = mid + 1
                else:
                    hi = mid - 1
            advised = max(1, min(best, int(nlist)))
            if advised != default_nprobe:
                self._knobs["nprobe_adjustments"] += 1
        return advised

    def advise_wave_close(self, max_wave: int, max_wait_s: float,
                          depth: int, drain_ms_ema: float | None,
                          arrivals_per_s_ema: float | None):
        """Effective (wave size, coalesce window) for one wave close.
        Warm: holding the wave open is only worth the arrivals one
        drain period is expected to deliver — the wave target becomes
        depth + E[arrivals during drain] (clamped to [1, max_wave]) and
        the window becomes the time to accumulate that target (clamped
        to [0, max_wait_s]). Cold or disabled: the configured values,
        untouched (byte parity with the static scheduler)."""
        if (not self.enabled or not drain_ms_ema or drain_ms_ema <= 0
                or not arrivals_per_s_ema or arrivals_per_s_ema <= 0):
            return max_wave, max_wait_s
        expect = arrivals_per_s_ema * (drain_ms_ema / 1000.0)
        eff_wave = int(min(max_wave, max(1, depth + expect)))
        need = max(eff_wave - depth, 0)
        eff_wait = min(max_wait_s,
                       max(0.0, need / arrivals_per_s_ema))
        if eff_wave != max_wave or eff_wait != max_wait_s:
            with self._lock:
                self._knobs["wave_adjustments"] += 1
        return eff_wave, eff_wait

    def admit_cache(self, recompute_ms: float | None) -> bool:
        """Request-cache admission by predicted recompute cost: entries
        cheaper to recompute than planner.cache.min_recompute_us are
        not worth their residency. Floor 0 (default) admits everything
        — parity with the pre-planner cache."""
        floor_us = float(self._cfg["cache_min_recompute_us"])
        if not self.enabled or floor_us <= 0 or recompute_ms is None:
            return True
        ok = recompute_ms * 1000.0 >= floor_us
        with self._lock:
            self._knobs["cache_admissions" if ok else
                        "cache_rejections"] += 1
        return ok

    # -- introspection ------------------------------------------------------

    def worst_kernel(self) -> tuple[str | None, float | None]:
        """(kernel, |residual| EMA) of the worst-predicted kernel."""
        with self._lock:
            worst, worst_val = None, None
            for k, st in self._residual.items():
                v = st.get("abs_ema")
                if v is not None and (worst_val is None or v > worst_val):
                    worst, worst_val = k, v
        return worst, worst_val

    def stats(self) -> dict:
        worst, worst_val = self.worst_kernel()
        with self._lock:
            kernels = {
                k: {
                    "efficiency_ema": round(self._eff[k], 6),
                    "observations": self._obs.get(k, 0),
                    **({"residual_last":
                        round(self._residual[k]["last"], 6),
                        "residual_abs_ema":
                        round(self._residual[k]["abs_ema"], 6),
                        "predictions": self._residual[k]["count"]}
                       if k in self._residual
                       and self._residual[k]["abs_ema"] is not None
                       else {}),
                }
                for k in sorted(self._eff)
            }
            out = {
                "enabled": self.enabled,
                "config": {
                    "ema_alpha": self._cfg["alpha"],
                    "knn_target_ms": self._cfg["knn_target_ms"],
                    "cache_min_recompute_us":
                        self._cfg["cache_min_recompute_us"],
                },
                "decisions": dict(sorted(self._decisions.items())),
                "decision_modes": dict(self._modes),
                "knobs": dict(self._knobs),
                "kernels": kernels,
                "sites": sorted(ARM_SITES),
            }
        out["repriced"] = self.repriced_arms()
        out["worst_kernel"] = worst
        out["worst_abs_residual_ema"] = (
            round(worst_val, 6) if worst_val is not None else None)
        return out

    def reset(self) -> None:
        with self._lock:
            self._cfg = dict(_DEFAULTS)
            self._eff.clear()
            self._obs.clear()
            self._rows_per_q.clear()
            self._residual.clear()
            self._repriced_scoped.clear()
            self._repricers.clear()
            self._decisions.clear()
            self._modes = {"model": 0, "static": 0, "repriced": 0}
            for k in self._knobs:
                self._knobs[k] = 0


_singleton: ExecutionPlanner | None = None
_singleton_lock = threading.Lock()


def execution_planner() -> ExecutionPlanner:
    """The process-wide planner every dispatch site consults. An Engine
    binds its planner.* settings consumers onto it at construction."""
    global _singleton
    if _singleton is None:
        with _singleton_lock:
            if _singleton is None:
                _singleton = ExecutionPlanner()
    return _singleton


def reset_for_tests() -> None:
    execution_planner().reset()
