"""Continuous-batching wave scheduler: the serving front end's core.

Pipeline (the inference-server treatment for scatter/gather search):

    REST handler ──submit──▶ per-tenant queues ──pop_wave──▶ scheduler
        ▲  future                                   │ weighted RR + deadlines
        │                                           ▼
        │                            engine thread: search_wave_begin
        │                            (parse/plan/DISPATCH, no fetch)
        │                                           │ depth-1 handoff
        │                                           ▼
        │                            completer thread: search_wave_fetch
        │                            (device pull — engine-state-free)
        │                                           │
        └──────── resolve ◀── engine thread: search_wave_finish ◀──┘

The depth-1 handoff queue is the double buffer: while the completer
waits on wave k's device outputs, the engine thread is free to plan and
dispatch wave k+1 — host-side parse/plan of the next wave overlaps
device execution of the current one (the generalization of the depth-32
C3 host↔device pipelining to the serving path). Waves close when the
device pipeline is idle (a lone request dispatches promptly), the wave
is full, or the oldest entry has waited `serving.coalesce.max_wait`.

Backpressure is layered: a bounded queue sheds with 429 + Retry-After
(`serving.queue.max_depth`), admission charges the `in_flight_requests`
breaker (trips shed the same way, before any device memory is
committed), and the depth-1 handoff bounds in-flight waves at two.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
import weakref
from collections import deque

from ..common.breaker import CircuitBreakingError
from ..tasks import TaskCancelledException
from ..tenancy.metering import (
    apportion, fairshare_weights, normalize_tenant,
)
from ..utils.durations import parse_duration_seconds
from .coalesce import classify_request
from .queue import (
    PendingSearch, ServingRejectedError, TenantQueues, parse_tenant_weights,
)

# hidden dump target of the flight recorder (daily, pruned by the
# monitoring CleanerService alongside .monitoring-es-8-*)
FLIGHT_INDEX_PREFIX = ".flight-recorder-"


def flight_index_name(ts: float | None = None) -> str:
    t = time.time() if ts is None else ts
    return FLIGHT_INDEX_PREFIX + time.strftime("%Y.%m.%d", time.gmtime(t))


def _iso_utc(ts: float | None = None) -> str:
    t = time.time() if ts is None else ts
    ms = int(t * 1000) % 1000
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(t)) + f".{ms:03d}Z"

# live services, for test hygiene (conftest drains/stops them at module
# boundaries so leaked engines never keep scheduler threads alive)
_LIVE_SERVICES: "weakref.WeakSet[ServingService]" = weakref.WeakSet()


def reset_all_for_tests():
    for sv in list(_LIVE_SERVICES):
        sv.reset_for_tests()


def reservation_leaks() -> list[dict]:
    """in_flight_requests reservations still held by live serving
    services. After reset_all_for_tests drained everything this must be
    empty — a non-empty list means some rejected/terminal path kept its
    breaker charge (the PR-14 shed-path bug class). Asserted by the
    conftest module hygiene."""
    out = []
    for sv in list(_LIVE_SERVICES):
        with sv._lock:
            if sv._reserved_bytes:
                out.append({"service": repr(sv),
                            "reserved_bytes": sv._reserved_bytes})
    return out


def _timed_out_response() -> dict:
    """A search whose queue wait exceeded its deadline degrades the way a
    shard-timeout does in the reference (partial results, timed_out
    flag) — here the 'partial result' of a never-dispatched search is
    empty."""
    return {
        "timed_out": True,
        "hits": {"total": {"value": 0, "relation": "eq"},
                 "max_score": None, "hits": []},
    }


class ServingService:
    """Admission + coalescing + deadline/fairness scheduling +
    backpressure between REST and the executor (ROADMAP item 3)."""

    TASK_ACTION = "indices:data/read/search[serving]"
    # the internal background-merge tenant (PR 15): device index merges
    # ride the SAME weighted-RR admission as search traffic, at a low
    # weight — the RR fairness contract means a full search wave can
    # slow a merge but never block it, and vice versa
    MERGE_TENANT = "_merge"

    def __init__(self, engine):
        self.engine = engine
        s = engine.settings
        self.enabled = False
        self.max_wave = int(s.get("serving.max_wave"))
        self.max_wait_s = parse_duration_seconds(
            s.get("serving.coalesce.max_wait"), 0.002) or 0.0
        self.queue_cap = int(s.get("serving.queue.max_depth"))
        self._tenants = TenantQueues()
        try:
            self._merge_weight = float(s.get("serving.merge.weight"))
        except Exception:  # noqa: BLE001 - engines without the setting
            self._merge_weight = 1.0
        # PR 19: budget-fed fair scheduling — static weights stay the
        # canonical source; the fairshare knob derives EFFECTIVE weights
        # from per-tenant device-budget burn (off/cold: the static dict
        # itself, byte-identical — the PR-18 cold-parity discipline)
        self._static_weights: dict[str, float] = {}
        try:
            self._fairshare_on = bool(s.get("planner.tenant.fairshare"))
        except Exception:  # noqa: BLE001 - engines without the setting
            self._fairshare_on = False
        try:
            self._fairshare_min = float(
                s.get("planner.tenant.fairshare.min_factor"))
        except Exception:  # noqa: BLE001
            self._fairshare_min = 0.25
        try:
            self._fairshare_budget = float(
                s.get("slo.tenant.device_ms_per_s"))
        except Exception:  # noqa: BLE001
            self._fairshare_budget = 0.0
        self.set_tenant_weights(s.get("serving.tenant.weights"))
        self._cv = threading.Condition()
        self._lock = threading.Lock()
        self._inflight: _queue.Queue = _queue.Queue(maxsize=1)
        self._inflight_count = 0
        self._threads: list[threading.Thread] = []
        self._stop = False
        self._own_pool = None
        self._submit_engine = None
        self.counters = {
            "admitted": 0, "dispatched": 0, "completed": 0, "errors": 0,
            "shed": 0, "expired": 0, "cancelled": 0, "waves": 0,
            "coalesced": 0, "term_packed": 0, "fallback_solo": 0,
            "merges": 0,
        }
        self._occ_sum = 0.0
        self._occ_n = 0
        self._size_sum = 0
        # host-transition accounting (PR 11): the wave executor proves
        # end-to-end fusion with one dispatch phase + one combined fetch
        # per wave; these sums expose the achieved per-wave average
        self._disp_sum = 0
        self._fetch_sum = 0
        self._wave_ms_ema: float | None = None
        # PR 18: inter-arrival EMA — with the drain EMA above, the two
        # inputs the execution planner's wave-close advisory needs to
        # size a wave to the arrivals one drain period can deliver
        self._arrival_rate_ema: float | None = None
        self._last_arrival: float | None = None
        self._last_gap: float | None = None   # between the last two arrivals
        self._arrived = 0                     # members since the last close
        self._last_close = time.monotonic()
        # flight recorder (PR 12): bounded ring of per-wave records —
        # segment timings (admission→claim→dispatch→device→complete),
        # tenant/lane mix, per-kernel utilization deltas, cache traffic,
        # escalations. The black box a breach-triggered capture dumps.
        try:
            fr_size = int(s.get("serving.flight_recorder.size"))
        except Exception:  # noqa: BLE001 - engines without the setting
            fr_size = 256
        self._flight: deque = deque(maxlen=max(fr_size, 1))
        self._wave_seq = 0
        # in_flight_requests bytes this service has charged but not yet
        # released: the conftest module-hygiene leak assertion reads it
        # (a rejected request that keeps its reservation is a slow leak)
        self._reserved_bytes = 0
        _LIVE_SERVICES.add(self)
        # what a wave adds to (`_count_wave`) reads 0 from the start, not
        # nothing: a node whose front end is off has served no wave
        from ..telemetry import WAVE_STAGES, metrics

        metrics.counters_add(
            [((f"es.span.{s}.ns", f"es.span.{s}.count"), (0, 0))
             for s in WAVE_STAGES] + [
                (("es.serving.wave.count", "es.serving.wave.members"), (0, 0)),
                (("es.serving.wave.padded_rows", "es.serving.wave.wait_ns"),
                 (0, 0)),
                (("es.jit.cache.wave_program.misses",
                  "es.jit.cache.wave_program.hits"), (0, 0))])

    # ---- settings consumers ---------------------------------------------

    def set_enabled(self, v: bool):
        self.enabled = bool(v)
        if self.enabled:
            self._ensure_threads()

    def set_max_wave(self, v):
        self.max_wave = max(1, int(v))

    def set_max_wait(self, v):
        self.max_wait_s = parse_duration_seconds(v, 0.002) or 0.0

    def set_queue_depth(self, v):
        self.queue_cap = max(1, int(v))

    def set_tenant_weights(self, raw):
        # weight keys pass through the SAME normalizer as queue keys, so
        # a weight for tenant "team a!" matches its sanitized queue row
        w = {normalize_tenant(t): v
             for t, v in parse_tenant_weights(raw).items()}
        # the merge tenant's weight comes from serving.merge.weight, not
        # the user weight table (an internal tenant, not a caller)
        w.setdefault(self.MERGE_TENANT, self._merge_weight)
        self._static_weights = w
        self._apply_fairshare()

    def set_merge_weight(self, v):
        try:
            self._merge_weight = max(float(v), 0.0)
        except (TypeError, ValueError):
            return
        self._static_weights = dict(self._static_weights)
        self._static_weights[self.MERGE_TENANT] = self._merge_weight
        self._apply_fairshare()

    def configure_fairshare(self, enabled=None, budget_ms_per_s=None,
                            min_factor=None):
        """Dynamic-settings consumer for the fair-share advisory knob
        (`planner.tenant.fairshare`, budget from
        `slo.tenant.device_ms_per_s`). Flipping it off — the kill
        switch — restores the static weight table on the next call."""
        if enabled is not None:
            self._fairshare_on = bool(enabled)
        if budget_ms_per_s is not None:
            try:
                self._fairshare_budget = float(budget_ms_per_s)
            except (TypeError, ValueError):
                pass
        if min_factor is not None:
            try:
                self._fairshare_min = float(min_factor)
            except (TypeError, ValueError):
                pass
        self._apply_fairshare()

    def _meter(self):
        """The engine's per-tenant ledger, or None on stub engines."""
        try:
            return self.engine.metering
        except Exception:  # noqa: BLE001 - test stubs without the property
            return None

    def _apply_fairshare(self):
        """Recompute the effective weighted-RR table. With fairshare off
        (or no budget, or a cold meter) the STATIC dict passes through
        unchanged — byte-identical scheduling, asserted by tests; with a
        tenant over its device-ms/s budget, its weight scales by
        budget/burn clamped to [min_factor, 1.0]: slowed, never starved
        (pop_wave still visits it every round)."""
        eff = self._static_weights
        if self._fairshare_on and self._fairshare_budget > 0.0:
            meter = self._meter()
            if meter is not None:
                burn = {t: r for t, r in meter.burn_rates().items()
                        if t != self.MERGE_TENANT}
                eff = fairshare_weights(
                    self._static_weights, burn, self._fairshare_budget,
                    self._fairshare_min)
        if eff is not self._tenants.weights \
                and eff != self._tenants.weights:
            self._tenants.set_weights(eff)

    def set_flight_recorder_size(self, v):
        with self._lock:
            self._flight = deque(self._flight, maxlen=max(1, int(v)))

    def bind_executor(self, submit):
        """Route engine-touching wave stages through the caller's single
        engine thread (the REST app pool), preserving the one-writer
        engine discipline; unbound, the service owns its own."""
        self._submit_engine = submit

    def _engine_submit(self, fn):
        if self._submit_engine is not None:
            return self._submit_engine(fn)
        if self._own_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._own_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="serving-engine")
        return self._own_pool.submit(fn)

    # ---- admission -------------------------------------------------------

    def classify(self, expression, body, query_params):
        return classify_request(self.engine, expression, body, query_params)

    def _retry_after_s(self) -> float:
        ema = self._wave_ms_ema or 50.0
        depth = self._tenants.depth
        return min(30.0, max(1.0, depth * (ema / 1000.0) / self.max_wave))

    def submit(self, entry: dict, tenant: str = "_anonymous",
               timeout_s: float | None = None,
               parent_task_id: str | None = None,
               est_bytes: int = 4096):
        """Admit one classified search -> concurrent Future resolving to
        the engine-core response dict. Sheds (429 + Retry-After) on a
        full queue or an in_flight_requests breaker trip — BEFORE any
        device work is queued."""
        from ..telemetry import metrics

        # satellite fix (PR 19): X-Opaque-Id normalizes ONCE at admission
        # — the queue key, the shed ledger row, and every metering
        # surface downstream see the same canonical tenant string
        tenant = normalize_tenant(tenant)
        meter = self._meter()
        if self._tenants.depth >= self.queue_cap:
            with self._lock:
                self.counters["shed"] += 1
            metrics.counter_inc("es.serving.shed_total")
            if meter is not None:
                meter.note("sheds", tenant)
            raise ServingRejectedError(
                f"serving queue full [{self.queue_cap}] — node saturated, "
                f"retry after backoff", self._retry_after_s())
        try:
            self.engine.breakers.add_estimate(
                "in_flight_requests", est_bytes, "serving_admission")
        except CircuitBreakingError as ex:
            with self._lock:
                self.counters["shed"] += 1
            metrics.counter_inc("es.serving.shed_total")
            if meter is not None:
                meter.note("sheds", tenant)
            ex.retry_after_s = self._retry_after_s()
            raise
        with self._lock:
            self._reserved_bytes += est_bytes
        # the breaker is charged: from here EVERY exit path must release
        # the reservation (PR-14 audit: a task-registration or queue-push
        # failure after the charge leaked it forever — the breaker crept
        # toward its limit and shed traffic a restart couldn't explain)
        task = None
        try:
            task = self.engine.tasks.register(
                self.TASK_ACTION,
                description=f"serving search [{entry.get('index')}]",
                cancellable=True, parent_task_id=parent_task_id)
            now = time.monotonic()
            ps = PendingSearch(
                entry=entry, tenant=tenant,
                deadline=(now + timeout_s) if timeout_s else None,
                task=task, est_bytes=est_bytes)
            # cancelling a QUEUED task removes it from the serving queue
            # and resolves the caller without a device round-trip
            # (satellite fix: pre-dispatch cancellation had no path)
            task.add_cancel_listener(
                lambda reason, ps=ps: self._cancel_queued(ps, reason))
            with self._cv:
                self._tenants.push(ps)
                self.counters["admitted"] += 1
                self._arrived += 1
                if self._last_arrival is not None:
                    self._last_gap = now - self._last_arrival
                self._last_arrival = now
                metrics.gauge_set("es.serving.queue_depth",
                                  self._tenants.depth)
                self._cv.notify_all()
        except BaseException:
            self.engine.breakers.release("in_flight_requests", est_bytes)
            with self._lock:
                self._reserved_bytes -= est_bytes
            if task is not None:
                self.engine.tasks.unregister(task)
            raise
        self._ensure_threads()
        return ps.future

    def submit_merge(self, fn, *, index: str = "", est_bytes: int = 1024):
        """Admit one background DEVICE index merge as the low-weight
        `_merge` internal tenant (PR 15 / ROADMAP item 2): the fold runs
        on the engine thread inside a wave slot, scheduled by the SAME
        weighted round-robin that drains search tenants — heavy indexing
        and heavy search share the chip under the existing breakers,
        shed path, and SLO floors. -> Future resolving when the merge
        ran (or shed with 429 under saturation — the caller retries at a
        later refresh)."""
        entry = {"internal": fn, "index": index, "kind": "merge"}
        return self.submit(entry, tenant=self.MERGE_TENANT,
                           est_bytes=est_bytes)

    # ---- terminal paths --------------------------------------------------

    def _terminal(self, ps: PendingSearch):
        self.engine.breakers.release("in_flight_requests", ps.est_bytes)
        with self._lock:
            self._reserved_bytes -= ps.est_bytes
        if ps.task is not None:
            self.engine.tasks.unregister(ps.task)

    def _finish_entry(self, ps: PendingSearch, result=None, error=None):
        self._terminal(ps)
        with self._lock:
            self.counters["errors" if error is not None else
                          "completed"] += 1
        if ps.future.done():
            return
        if error is not None:
            ps.future.set_exception(error)
        else:
            ps.future.set_result(result)

    def _cancel_queued(self, ps: PendingSearch, reason: str):
        if not self._tenants.claim(ps):
            return  # already dispatched (or otherwise settled): best-effort
        with self._lock:
            self.counters["cancelled"] += 1
        meter = self._meter()
        if meter is not None:
            meter.note("cancelled", ps.tenant)
        self._terminal(ps)
        ps.future.set_exception(TaskCancelledException(
            f"task cancelled before dispatch [{reason}]"))
        from ..telemetry import metrics

        metrics.gauge_set("es.serving.queue_depth", self._tenants.depth)

    def _resolve_expired(self, ps: PendingSearch):
        # cancel through the task manager (flag + listeners fire for any
        # children), then resolve with the timed-out degradation
        if ps.task is not None:
            ps.task.cancel("serving deadline exceeded before dispatch")
        with self._lock:
            self.counters["expired"] += 1
        meter = self._meter()
        if meter is not None:
            meter.note("expired", ps.tenant)
        self._terminal(ps)
        ps.future.set_result(_timed_out_response())

    # ---- scheduler -------------------------------------------------------

    def _ensure_threads(self):
        with self._lock:
            if self._threads and all(t.is_alive() for t in self._threads):
                return
            self._stop = False
            self._threads = [
                threading.Thread(target=self._scheduler_loop,
                                 name="serving-scheduler", daemon=True),
                threading.Thread(target=self._completer_loop,
                                 name="serving-completer", daemon=True),
            ]
            for t in self._threads:
                t.start()

    def _close_wave(self) -> list[PendingSearch]:
        """Block until a wave should dispatch, then claim it. Continuous
        batching: an idle pipeline dispatches whatever is queued at once
        (a lone request never waits), a busy one accumulates until the
        wave is full or the oldest entry has waited max_wait."""
        from ..planner import execution_planner

        deadline = None
        eff_wave, eff_wait = self.max_wave, self.max_wait_s
        while not self._stop:
            with self._cv:
                depth = self._tenants.depth
                if depth == 0:
                    deadline = None
                    self._cv.wait(0.05)
                    continue
                if deadline is None:
                    # PR 18: the planner sizes the wave to depth + expected
                    # arrivals during one measured drain period, and shrinks
                    # the coalesce window to the time those arrivals need
                    # (cold EMAs -> the configured values, unchanged). Asked
                    # once a wave, at its first member: a target that moved
                    # up with the depth at every look was never reached,
                    # and every busy wave sat out its whole window.
                    eff_wave, eff_wait = execution_planner().advise_wave_close(
                        self.max_wave, self.max_wait_s, depth,
                        self._wave_ms_ema, self._arrival_rate_ema)
                    deadline = time.monotonic() + eff_wait
                if depth >= eff_wave:
                    break
                if self._inflight_count == 0 and self._stream_sparse():
                    break  # a lone request on an idle pipeline never waits
                if time.monotonic() >= deadline:
                    break
                self._cv.wait(max(min(eff_wait, 0.005), 0.0005))
        if self._stop:
            return []
        wave = self._tenants.pop_wave(eff_wave)
        self._note_wave_closed()
        return wave

    def _stream_sparse(self) -> bool:
        """Whether arrivals lie further apart than the coalescing window, so
        that holding a wave open would gather nobody (caller holds _cv).
        Read off the last gap alone, not off an average: under one load
        the answer is the same whatever came before."""
        gap = self._last_gap
        return gap is None or gap > self.max_wait_s

    def _note_wave_closed(self) -> None:
        """The arrival rate the wave-close advisory reads: members admitted
        since the last close over the time since, smoothed over waves (the
        reciprocal of single gaps, which it replaces, reads a burst of
        two as a million a second)."""
        now = time.monotonic()
        with self._cv:
            n, self._arrived = self._arrived, 0
            dt, self._last_close = now - self._last_close, now
        if n and dt > 0:
            inst = n / dt
            self._arrival_rate_ema = (
                inst if self._arrival_rate_ema is None
                else 0.8 * self._arrival_rate_ema + 0.2 * inst)

    def _scheduler_loop(self):
        from ..telemetry import metrics

        while not self._stop:
            try:
                wave = self._close_wave()
                if self._stop:
                    break
                now, now_ns = time.monotonic(), time.perf_counter_ns()
                ready = []
                dropped = {"expired": 0, "cancelled": 0}
                meter = self._meter()
                for ps in wave:
                    if ps.task is not None and ps.task.cancelled:
                        with self._lock:
                            self.counters["cancelled"] += 1
                        dropped["cancelled"] += 1
                        self._terminal(ps)
                        ps.future.set_exception(TaskCancelledException(
                            f"task cancelled before dispatch "
                            f"[{ps.task.cancel_reason}]"))
                        continue
                    if ps.expired(now):
                        dropped["expired"] += 1
                        self._resolve_expired(ps)
                        continue
                    wait_ms = (now - ps.enqueue_t) * 1000
                    metrics.histogram_record(
                        "es.serving.coalesce_wait_ms", wait_ms)
                    if meter is not None:
                        meter.note_queue_wait(ps.tenant, wait_ms)
                    ps.claim_ns = now_ns
                    ready.append(ps)
                metrics.gauge_set(
                    "es.serving.queue_depth", self._tenants.depth)
                if not ready:
                    continue
                with self._lock:
                    self._inflight_count += 1
                    self.counters["dispatched"] += len(ready)
                try:
                    state = self._engine_submit(
                        lambda: self._wave_begin(ready)).result()
                except Exception as ex:  # noqa: BLE001 - resolve, don't die
                    for ps in ready:
                        self._finish_entry(ps, error=ex)
                    with self._lock:
                        self._inflight_count -= 1
                    continue
                # flight-recorder timestamps: contiguous boundaries so the
                # per-wave segments sum to the wall time by construction
                state["t_admit"] = min(ps.enqueue_t for ps in ready)
                state["t_claim"] = now
                state["t_dispatched"] = time.monotonic()
                state["dropped"] = dropped
                # depth-1 handoff: the double buffer — blocks only while
                # the completer still owns the PREVIOUS wave
                handed = False
                while not self._stop:
                    try:
                        self._inflight.put(state, timeout=0.1)
                        handed = True
                        break
                    except _queue.Full:
                        continue
                if not handed:
                    # stopped between dispatch and hand-off: the completer
                    # is exiting, so resolve this wave's members here —
                    # abandoned futures would hang their callers forever
                    for ps in ready:
                        if not ps.future.done():
                            self._finish_entry(ps, error=ServingRejectedError(
                                "serving front end stopped"))
                    with self._lock:
                        self._inflight_count -= 1
            except Exception:  # noqa: BLE001 - scheduler must survive
                time.sleep(0.01)

    def _completer_loop(self):
        while True:
            try:
                state = self._inflight.get(timeout=0.1)
            except _queue.Empty:
                if self._stop:
                    return
                continue
            if state is None:
                return
            from ..common import faults
            from ..telemetry import collect_profile_events

            try:
                faults.check("serving.wave", n=state["n"])
                with collect_profile_events() as events, \
                        state["stages"].stage("engine.wave_fetch"):
                    for idx, _members, job in state["jobs"]:
                        # engine-state-free device pull: overlaps the
                        # engine thread's planning of the next wave
                        idx.search_wave_fetch(job)
                state.setdefault("events", []).extend(events)
            except Exception as ex:  # noqa: BLE001
                state["fetch_error"] = ex
            state["t_fetched"] = time.monotonic()
            try:
                self._engine_submit(lambda: self._wave_finish(state)).result()
            except Exception as ex:  # noqa: BLE001
                for _idx, members, _job in state["jobs"]:
                    for ps in members:
                        if not ps.future.done():
                            self._finish_entry(ps, error=ex)
            with self._lock:
                self._inflight_count -= 1

    # ---- wave stages (engine thread) ------------------------------------

    def _entry_cost(self, ps: PendingSearch, idx=None) -> dict:
        """Analytic roofline weight for one wave entry (PR 19): the
        PR-5 cost shapes priced per member, so the shared wave's
        measured device wall can be apportioned proportional to each
        entry's modeled work. Superpack-claimed entries price the
        tenant-gather shape over their size class; per-index entries
        price the batched disjunction over the index's resident docs.
        -> {"weight", "flops", "bytes", "kernel"}; weight 0.0 means
        'shape unavailable' (apportion degrades to equal split)."""
        from ..monitoring.costmodel import device_peaks, kernel_cost

        out = {"weight": 0.0, "flops": 0.0, "bytes": 0.0, "kernel": None}
        try:
            sp = ps.entry.get("_superpack")
            if sp is not None:
                from ..tenancy import size_class_of

                member = sp["member"]
                n_pad, nb_pad = size_class_of(member.num_docs,
                                              member.num_blocks)
                fields = {"queries": 1, "num_docs": n_pad,
                          "rows": len(sp.get("terms") or ()) * nb_pad}
                kernel = "superpack.tenant_gather"
            else:
                n = len(getattr(idx, "docs", None) or ()) or 1
                fields = {"queries": 1, "num_docs": n}
                kernel = "batched.disjunction"
            cost = kernel_cost(kernel, fields)
            if cost is None:
                return out
            peak_f, peak_b, _kind = device_peaks()
            out["flops"] = float(cost.get("flops", 0.0))
            out["bytes"] = float(cost.get("bytes", 0.0))
            out["kernel"] = kernel
            # roofline seconds: the max of the compute- and bandwidth-
            # bound walls is the modeled device time — the weight
            out["weight"] = max(out["flops"] / peak_f,
                                out["bytes"] / peak_b)
        except Exception:  # noqa: BLE001 - metering must never fail a wave
            pass
        return out

    @staticmethod
    def _add_cost(tenant_cost: dict, tenant: str, c: dict) -> None:
        tc = tenant_cost.setdefault(tenant, {"weight": 0.0, "flops": 0.0,
                                             "bytes": 0.0, "kernels": {}})
        tc["weight"] += c["weight"]
        tc["flops"] += c["flops"]
        tc["bytes"] += c["bytes"]
        if c["kernel"] is not None:
            tc["kernels"][c["kernel"]] = (
                tc["kernels"].get(c["kernel"], 0.0) + (c["weight"] or 1.0))

    def _wave_begin(self, ready: list[PendingSearch]) -> dict:
        """Plan and launch one wave (engine thread): the stage
        `engine.wave_plan`, less the launches inside it, each of which is
        an `engine.wave_launch` of its own (parallel/sharded._wave_launch)."""
        from ..telemetry import WaveStages

        stages = WaveStages()
        with stages.stage("engine.wave_plan"):
            state = self._wave_begin_staged(ready)
        state["stages"] = stages
        state["members"] = ready
        return state

    def _wave_begin_staged(self, ready: list[PendingSearch]) -> dict:
        from ..telemetry import collect_profile_events

        tenants: dict[str, int] = {}
        for ps in ready:
            tenants[ps.tenant] = tenants.get(ps.tenant, 0) + 1
        state = {"t0": time.monotonic(), "jobs": [], "n": len(ready),
                 "tenants": tenants, "tenant_cost": {}, "events": [],
                 "fallback_solo": 0}
        # internal lane (PR 15): background merges claimed into this
        # wave run here on the engine thread (the one-writer discipline)
        # and resolve immediately — a merge occupies its weighted-RR
        # slot, the rest of the wave packs search lanes around it
        searches = []
        for ps in ready:
            fn = ps.entry.get("internal")
            if not callable(fn):
                searches.append(ps)
                continue
            with self._lock:
                self.counters["merges"] += 1
            try:
                res = fn()
                self._finish_entry(ps, result={"merged": bool(res)})
            except Exception as ex:  # noqa: BLE001 - per-entry envelope
                self._finish_entry(ps, error=ex)
        ready = searches
        # superpack lane (PR 17): entries whose member lane is CURRENT in
        # a shared tenant superpack serve from one tenant-gather program —
        # a single wave job mixing queries from many small tenant indices
        # in one dispatch. A failed claim (stale lane, ineligible query)
        # falls through to the per-index path, byte-identical by contract.
        sp_members: list[PendingSearch] = []
        mgr = self.engine.superpacks_if_enabled()
        if mgr is not None:
            rest = []
            for ps in ready:
                try:
                    claimed = mgr.wave_claim(ps.entry)
                except Exception:  # noqa: BLE001 - claim must never poison
                    claimed = False
                (sp_members if claimed else rest).append(ps)
            ready = rest
        by_index: dict[str, list[PendingSearch]] = {}
        for ps in ready:
            by_index.setdefault(ps.entry["index"], []).append(ps)
        with collect_profile_events() as events:
            if sp_members:
                # priced BEFORE search_wave_begin consumes the claim ctx;
                # attributed only if the superpack job actually forms
                sp_costs = [(ps, self._entry_cost(ps))
                            for ps in sp_members]
                try:
                    job = mgr.search_wave_begin(
                        [ps.entry for ps in sp_members])
                    state["jobs"].append((mgr, sp_members, job))
                    for ps, c in sp_costs:
                        self._add_cost(state["tenant_cost"], ps.tenant, c)
                except Exception:  # noqa: BLE001 - degrade, don't poison
                    for ps in sp_members:
                        with self._lock:
                            self.counters["fallback_solo"] += 1
                        state["fallback_solo"] += 1
                        try:
                            res = self.engine.search_multi(
                                ps.entry.get("expression"),
                                ignore_unavailable=ps.entry.get("iu", False),
                                allow_no_indices=ps.entry.get("ani", True),
                                **ps.entry["kwargs"])
                            self._finish_entry(ps, result=res)
                        except Exception as ex:  # noqa: BLE001
                            self._finish_entry(ps, error=ex)
            for name, members in by_index.items():
                idx = self.engine.indices.get(name)
                if idx is None:
                    # index vanished between classify and dispatch: the
                    # solo path produces the canonical behavior
                    # (404 / empty)
                    for ps in members:
                        with self._lock:
                            self.counters["fallback_solo"] += 1
                        state["fallback_solo"] += 1
                        try:
                            res = self.engine.search_multi(
                                ps.entry.get("expression"),
                                ignore_unavailable=ps.entry.get("iu", False),
                                allow_no_indices=ps.entry.get("ani", True),
                                **ps.entry["kwargs"])
                            self._finish_entry(ps, result=res)
                        except Exception as ex:  # noqa: BLE001
                            self._finish_entry(ps, error=ex)
                    continue
                job = idx.search_wave_begin([ps.entry["kwargs"]
                                             for ps in members])
                state["jobs"].append((idx, members, job))
                for ps in members:
                    self._add_cost(state["tenant_cost"], ps.tenant,
                                   self._entry_cost(ps, idx))
        state["events"].extend(events)
        return state

    def _wave_finish(self, state: dict):
        """Build and hand out the wave's answers (engine thread): the stage
        `engine.wave_finish`; then the wave's stages and counts reach the
        counters at once, and each member learns its share of them."""
        stages = state["stages"]
        with stages.stage("engine.wave_finish"):
            rows = self._wave_finish_staged(state)
        self._count_wave(state, stages, rows)
        for ps, res in state.pop("answers", ()):
            if isinstance(res, Exception):
                self._finish_entry(ps, error=res)
            else:
                self._finish_entry(ps, result=res)

    def _count_wave(self, state: dict, stages, rows: int) -> None:
        """What one wave adds to `_nodes/stats` -> metrics.counters, under
        one acquisition of the lock: its four stages (`es.span.engine.
        wave_*.ns` / `.count`), 1 to `es.serving.wave.count`, its members,
        the rows its programs computed for them (a term lane's batch tier,
        one for any other member) and the nanoseconds the members waited
        between admission and the claim. A member's own `rest.search` gets
        an equal share of each stage through `member_spans`."""
        from ..telemetry import metrics

        members = state["members"]
        n = len(members)
        wait_ns = sum(ps.claim_ns - ps.enqueue_ns for ps in members)
        metrics.counters_add(stages.counter_pairs() + [
            (("es.serving.wave.count", "es.serving.wave.members"), (1, n)),
            (("es.serving.wave.padded_rows", "es.serving.wave.wait_ns"),
             (rows, wait_ns)),
        ])
        done_ns = time.perf_counter_ns()
        parse_ns = sum(job.get("meta", {}).get("parse_ns", 0)
                       for _idx, _members, job in state["jobs"])
        for ps in members:
            ps.future.wave = (ps.enqueue_ns, ps.claim_ns, done_ns, n, stages,
                              parse_ns)

    @staticmethod
    def member_spans(future) -> None:
        """Record, in the caller's context (the member's `rest.search`),
        what a served search spent where: `engine.queue` from admission to
        the claim, `engine.search` from the claim to the answer, and inside
        it a 1/n share of each of the wave's stages under the name the solo
        path gives that work (`engine.plan`, `.dispatch`, `.fetch`,
        `.collect`): engine- and completer-thread time a search, as in the
        cells without the front end. The rest of `engine.search` is the
        wait for the wave: the other members' shares, the device, the
        hand-offs between the threads."""
        from ..telemetry import TRACER

        wave = getattr(future, "wave", None)
        if wave is None:
            return
        enq, claim, done, n, stages, parse_ns = wave
        TRACER.record("engine.queue", enq, claim)
        shares, at = [], claim
        for solo, ns in (
                ("engine.parse", parse_ns),
                ("engine.plan", stages.ns("engine.wave_plan") - parse_ns),
                ("engine.dispatch", stages.ns("engine.wave_launch")),
                ("engine.fetch", stages.ns("engine.wave_fetch")),
                ("engine.collect", stages.ns("engine.wave_finish"))):
            shares.append((solo, at, at + max(ns, 0) // n))
            at = shares[-1][2]
        TRACER.record("engine.search", claim, done, children=shares)

    def _wave_finish_staged(self, state: dict) -> int:
        from ..telemetry import collect_profile_events, metrics

        err = state.get("fetch_error")
        if err is not None:
            # the wave's DEVICE stage died (injected serving.wave fault,
            # real device failure): degrade to per-member SOLO re-runs so
            # one poisoned wave costs its members a slower path, not an
            # error — and a device OOM additionally runs the staged
            # degradation before the re-runs
            from ..common.resilience import (is_device_oom,
                                             node_resilience)

            if is_device_oom(err):
                try:
                    self.engine.device_degradation.on_oom(err, "wave")
                except Exception:  # noqa: BLE001 - rescue must proceed
                    pass
            node_resilience(getattr(
                self.engine.tasks, "node", "node-0")).count("wave_rescues")
            metrics.counter_inc("es.serving.wave_rescues")
        wave_tr = {"dispatch": 0, "fetch": 0}
        lanes = {"generic": 0, "term": 0, "tiered": 0,
                 "fallback_solo": state.get("fallback_solo", 0)}
        occ = []
        indices = []
        rows = 0
        with collect_profile_events() as fin_events:
            for idx, members, job in state["jobs"]:
                if err is not None:
                    results = self._rescue_solo(members)
                else:
                    results = idx.search_wave_finish(job)
                # handed out by `_wave_finish` once the wave is counted, so
                # that no member wakes before its share of the stages is known
                state.setdefault("answers", []).extend(zip(members, results))
                # a superpack job serves MANY indices: report the member
                # names (ordered, unique), not the job owner's synthetic
                # "_superpack" — flight records must name real tenants
                for nm in (job.get("index_names") or (idx.name,)):
                    if nm not in indices:
                        indices.append(nm)
                lanes["generic"] += len(job.get("lanes", ()))
                lanes["term"] += len(job.get("term_lanes", ()))
                lanes["tiered"] += 1 if job.get("tiered") else 0
                meta = job.get("meta", {})
                tr = meta.get("transitions") or {}
                metrics.histogram_record(
                    "es.serving.host_transitions",
                    tr.get("dispatch", 0) + tr.get("fetch", 0))
                wave_tr["dispatch"] += tr.get("dispatch", 0)
                wave_tr["fetch"] += tr.get("fetch", 0)
                with self._lock:
                    self.counters["term_packed"] += meta.get(
                        "term_packed", 0)
                    self._disp_sum += tr.get("dispatch", 0)
                    self._fetch_sum += tr.get("fetch", 0)
                rows += len(members) - meta.get("term_packed", 0)
                for q, tier in meta.get("term_waves", ()):
                    rows += tier
                    metrics.histogram_record(
                        "es.serving.wave_occupancy", q / max(tier, 1))
                    occ.append(q / max(tier, 1))
                    with self._lock:
                        self._occ_sum += q / max(tier, 1)
                        self._occ_n += 1
        state.setdefault("events", []).extend(fin_events)
        t_complete = time.monotonic()
        wave_ms = (t_complete - state["t0"]) * 1000
        with self._lock:
            self.counters["waves"] += 1
            if state["n"] > 1:
                self.counters["coalesced"] += state["n"]
            self._size_sum += state["n"]
            self._wave_ms_ema = (wave_ms if self._wave_ms_ema is None else
                                 0.8 * self._wave_ms_ema + 0.2 * wave_ms)
        metrics.histogram_record("es.serving.wave_size", state["n"])
        self._record_flight(state, t_complete, wave_tr, lanes, occ,
                            indices, err)
        # PR 19: the ledger just absorbed this wave's shares — refresh
        # the fair-share effective weights from the new burn rates (a
        # no-op dict compare when the knob is off or nothing changed)
        try:
            self._apply_fairshare()
        except Exception:  # noqa: BLE001 - advisory, never fails a wave
            pass
        return rows

    def _rescue_solo(self, members) -> list:
        """Re-run a poisoned wave's members one by one on the classic
        engine path (engine thread — _wave_finish runs there). Members
        whose re-run also fails carry their exception; the rest get real
        results. Counted per wave in `wave_rescues`."""
        out = []
        for ps in members:
            try:
                out.append(self.engine.search_multi(
                    ps.entry.get("expression"),
                    ignore_unavailable=ps.entry.get("iu", False),
                    allow_no_indices=ps.entry.get("ani", True),
                    **ps.entry["kwargs"]))
            except Exception as ex:  # noqa: BLE001 - per-member envelope
                out.append(ex)
        return out

    def record_degradation(self, event: dict) -> None:
        """Stamp a device-degradation event into the flight recorder ring
        (PR 14): the black box must show WHEN the degradation happened
        relative to the waves around it. The record shares the ring and
        the wave sequence so dumps/pruning treat it uniformly."""
        with self._lock:
            self._wave_seq += 1
            self._flight.append({
                "wave": self._wave_seq,
                "@timestamp": _iso_utc(),
                "node": getattr(self.engine.tasks, "node", "node-0"),
                "kind": "degradation",
                "degradation": {k: v for k, v in event.items()
                                if k != "ts"},
            })

    # ---- flight recorder -------------------------------------------------

    def _record_flight(self, state, t_complete, wave_tr, lanes, occ,
                       indices, err) -> None:
        """Append one per-wave record to the ring. Segment boundaries are
        contiguous timestamps (admission→claim→dispatched→fetched→
        complete), so segments_ms sums to wall_ms by construction —
        asserted by tests. Never raises: the recorder is observability,
        not the serving path."""
        try:
            t_admit = state.get("t_admit", state["t0"])
            t_claim = state.get("t_claim", state["t0"])
            t_disp = state.get("t_dispatched", state["t0"])
            t_fetch = state.get("t_fetched", t_disp)
            seg = {
                # admission → wave claimed (queue wait + coalesce window)
                "queue": (t_claim - t_admit) * 1000,
                # claim → every lane planned + dispatched (host plan cost)
                "plan": (t_disp - t_claim) * 1000,
                # dispatch → combined fetch done (device execution + pull)
                "device": (t_fetch - t_disp) * 1000,
                # fetch → futures resolved (host finish/merge/aggs)
                "finish": (t_complete - t_fetch) * 1000,
            }
            seg = {k: round(v, 4) for k, v in seg.items()}
            kernels: dict = {}
            cache = {"hits": 0, "misses": 0}
            escalations = 0
            decisions: list = []
            for e in state.get("events", ()):
                kind = e.get("kind")
                if kind == "planner":
                    # PR 18: per-wave decision attribution — which arms
                    # competed, what the planner predicted for each, and
                    # (below, once kernels are aggregated) what the chosen
                    # arm actually cost
                    decisions.append({
                        "site": e.get("site"), "arm": e.get("arm"),
                        "mode": e.get("mode"),
                        "kernel": e.get("priced_kernel"),
                        "fields": dict(e.get("fields") or {}),
                        "predicted_ms": dict(e.get("predicted_ms") or {}),
                        "decision_us": e.get("decision_us"),
                    })
                elif kind == "kernel":
                    u = kernels.setdefault(e["kernel"], {
                        "calls": 0, "ms": 0.0, "flops": 0.0, "bytes": 0.0,
                        "ici_bytes": 0.0})
                    u["calls"] += 1
                    u["ms"] += float(e.get("ms", 0.0))
                    u["flops"] += float(e.get("flops", 0.0))
                    u["bytes"] += float(e.get("bytes", 0.0))
                    u["ici_bytes"] += float(e.get("ici_bytes", 0.0))
                elif kind == "cache":
                    cache["hits"] += int(e.get("hits", 0))
                    cache["misses"] += int(e.get("misses", 0))
                elif kind == "tier" and "escalation" in str(
                        e.get("tier", "")):
                    escalations += int(e.get("queries", 1))
            from ..monitoring.costmodel import device_peaks, ici_peak

            peak_f, peak_b, _kind = device_peaks()
            for u in kernels.values():
                sec = max(u["ms"] / 1e3, 1e-9)
                u["mfu"] = round(u["flops"] / sec / peak_f, 6)
                u["bw_util"] = round(u["bytes"] / sec / peak_b, 6)
                if u["ici_bytes"]:
                    u["ici_util"] = round(
                        u["ici_bytes"] / sec / ici_peak(), 6)
                else:
                    u.pop("ici_bytes")
                u["ms"] = round(u["ms"], 4)
            wave_prog = kernels.get("serving.wave_program")
            for d in decisions:
                u = kernels.get(d.get("kernel"))
                if not (u and u.get("calls")) and len(decisions) == 1 \
                        and wave_prog and wave_prog.get("calls"):
                    # wave route: the routed arm's own timer folded into
                    # the ONE combined fetch — with a single decision in
                    # the wave the attribution is unambiguous, so the
                    # wave program's wall IS the arm's wall
                    u = wave_prog
                fields = d.pop("fields", None)
                if u and u.get("calls"):
                    actual = u["ms"] / u["calls"]
                    d["actual_ms"] = round(actual, 4)
                    pred = d["predicted_ms"].get(d["arm"])
                    if pred:
                        d["residual"] = round((actual - pred) / pred, 4)
                    if fields:
                        # feed the efficiency EMA the solo paths feed
                        # through time_kernel directly: serving traffic
                        # is what the planner mostly routes, so it must
                        # also be what warms the model
                        from ..planner import execution_planner

                        execution_planner().observe_wall(
                            d["kernel"], fields, actual / 1e3)
            # PR 19: apportion the wave's measured device wall across
            # member tenants proportional to each entry's analytic cost.
            # The shares sum EXACTLY to segments_ms["device"] (fsum-exact
            # residual correction in tenancy/metering.apportion) —
            # asserted by tests, never sampled. Tenants whose entries
            # never reached a device job (inline merges, solo fallbacks)
            # carry weight 0 and get a 0.0 share: they did no device
            # work in this wave.
            req_counts = dict(state.get("tenants") or {})
            tcost = state.get("tenant_cost") or {}
            shares = apportion(
                seg["device"],
                {t: (tcost.get(t) or {}).get("weight", 0.0)
                 for t in req_counts}) if req_counts else {}
            dev = seg["device"]
            tenant_mix = {
                t: {"requests": req_counts[t],
                    "device_ms": shares.get(t, 0.0),
                    "share": (shares.get(t, 0.0) / dev) if dev else 0.0}
                for t in req_counts}
            meter = self._meter()
            if meter is not None:
                meter.record_wave(shares, req_counts, tcost,
                                  cache_hits=cache["hits"],
                                  cache_misses=cache["misses"])
            with self._lock:
                self._wave_seq += 1
                rec = {
                    "wave": self._wave_seq,
                    "@timestamp": _iso_utc(),
                    "node": getattr(self.engine.tasks, "node", "node-0"),
                    "size": state["n"],
                    "expired": state.get("dropped", {}).get("expired", 0),
                    "cancelled": state.get("dropped", {}).get(
                        "cancelled", 0),
                    "error": (f"{type(err).__name__}: {err}"
                              if err is not None else None),
                    "tenants": tenant_mix,
                    "indices": sorted(set(indices)),
                    "lanes": lanes,
                    "segments_ms": seg,
                    "wall_ms": round((t_complete - t_admit) * 1000, 4),
                    "host_transitions": wave_tr,
                    "term_occupancy": (round(sum(occ) / len(occ), 4)
                                       if occ else None),
                    "kernels": kernels,
                    "cache": cache,
                    "escalations": escalations,
                    "decisions": decisions,
                }
                self._flight.append(rec)
        except Exception:  # noqa: BLE001 - recorder must never fail a wave
            pass

    def flight_recorder(self, n: int | None = None) -> dict:
        """The recorded waves, oldest first (`GET /_serving/flight_recorder`)."""
        with self._lock:
            waves = list(self._flight)
        if n is not None:
            waves = waves[-max(int(n), 0):]
        return {
            "capacity": self._flight.maxlen,
            "recorded_total": self._wave_seq,
            "retained": len(waves),
            "waves": waves,
        }

    def dump_flight_recorder(self) -> dict:
        """Dump the ring into the hidden daily `.flight-recorder-*` index
        (idempotent per (node, wave): the doc id is the wave sequence).
        The watcher `capture` action calls this on SLO breach so the
        breach ships evidence, not just an alert doc."""
        snap = self.flight_recorder()
        name = flight_index_name()
        eng = self.engine
        if name not in eng.indices:
            eng.create_index(name, mappings={"properties": {
                "@timestamp": {"type": "date"},
                "node": {"type": "keyword"},
                "wave": {"type": "long"},
            }}, settings={"hidden": True, "number_of_shards": 1,
                          "refresh_interval": "1s"})
        idx = eng.indices[name]
        for rec in snap["waves"]:
            idx.index_doc(f"{rec['node']}_{rec['wave']}", dict(rec))
        idx.refresh()
        from ..telemetry import metrics

        metrics.counter_inc("es.serving.flight_recorder.dumps")
        return {"index": name, "docs": len(snap["waves"]),
                "capacity": snap["capacity"]}

    # ---- introspection / lifecycle --------------------------------------

    def stats(self) -> dict:
        from ..parallel.spmd import spmd_mode
        from ..telemetry import metrics

        # cumulative PR-11 host-transition counters (node-wide, also on
        # the Prometheus scrape as es_serving_host_transitions_total)
        c = metrics.snapshot()["counters"]
        transitions_total = {
            kind: int(c.get(f"es.device.host_transitions.{kind}", 0))
            for kind in ("dispatch", "fetch")}
        with self._lock:
            waves = max(self.counters["waves"], 1)
            return {
                "enabled": self.enabled,
                # which slice execution model the wave lanes dispatch into
                # (pjit = one SPMD program incl. the device merge)
                "spmd_mode": spmd_mode(),
                "queue": {**self._tenants.stats(),
                          "max_depth": self.queue_cap},
                # PR 19: the advisory fair-share knob's observable state
                # — static vs effective weights (equal when off/cold)
                "fairshare": {
                    "enabled": self._fairshare_on,
                    "budget_device_ms_per_s": self._fairshare_budget,
                    "min_factor": self._fairshare_min,
                    "static_weights": dict(self._static_weights),
                    "effective_weights": dict(self._tenants.weights),
                },
                "wave": {
                    "max_wave": self.max_wave,
                    "max_wait_ms": self.max_wait_s * 1000,
                    "in_flight": self._inflight_count,
                    "avg_size": self._size_sum / waves,
                    "avg_term_occupancy": (self._occ_sum / self._occ_n
                                           if self._occ_n else None),
                    "service_ms_ema": self._wave_ms_ema,
                    # PR 18: the wave-close advisory's second input
                    "arrival_rate_ema": self._arrival_rate_ema,
                    # ≤1 dispatch + ≤1 fetch per wave is the PR-11
                    # contract; extras mean escalations/two-pass aggs
                    "host_transitions_per_wave": {
                        "dispatch": self._disp_sum / waves,
                        "fetch": self._fetch_sum / waves,
                    },
                },
                "host_transitions_total": transitions_total,
                "flight_recorder": {
                    "capacity": self._flight.maxlen,
                    "retained": len(self._flight),
                    "recorded_total": self._wave_seq,
                },
                **{k: v for k, v in self.counters.items()},
            }

    def drain(self, timeout_s: float = 10.0) -> bool:
        """Wait until the queue and in-flight waves are empty."""
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            with self._lock:
                idle = (self._tenants.depth == 0
                        and self._inflight_count == 0)
            if idle:
                return True
            time.sleep(0.002)
        return False

    def stop(self):
        """Stop the scheduler threads; queued entries resolve as shed."""
        self._stop = True
        with self._cv:
            self._cv.notify_all()
        try:
            self._inflight.put_nowait(None)
        except _queue.Full:
            pass
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
        # a completer that consumed a real wave before the sentinel may
        # leave the sentinel queued; clear it for a future restart
        try:
            while True:
                self._inflight.get_nowait()
        except _queue.Empty:
            pass
        self._inflight_count = 0
        for ps in self._tenants.drain():
            self._terminal(ps)
            if not ps.future.done():
                ps.future.set_exception(ServingRejectedError(
                    "serving front end stopped"))
        if self._own_pool is not None:
            self._own_pool.shutdown(wait=True)
            self._own_pool = None

    def reset_for_tests(self):
        self.stop()
        with self._lock:
            for k in self.counters:
                self.counters[k] = 0
            self._occ_sum = self._occ_n = 0
            self._size_sum = 0
            self._disp_sum = self._fetch_sum = 0
            self._wave_ms_ema = self._arrival_rate_ema = None
            self._last_arrival = self._last_gap = None
            self._arrived = 0
            self._flight.clear()
            self._wave_seq = 0
