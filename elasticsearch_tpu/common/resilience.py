"""Data-plane resilience: retry policy, per-peer circuit breakers,
device-failure graceful degradation (PR 14).

Three cooperating pieces, all observable through `_nodes/stats`
(`resilience` section), Prometheus (`es.resilience.*`) and the
`data_plane_resilience` health indicator (xpack/health.py):

- ``RetryPolicy``: deadline-aware exponential backoff with deterministic
  jitter for IDEMPOTENT transport actions (reads: get / shard search /
  trace collect / health / dump). Writes are never retried here — the
  replication path has its own exactly-once discipline.

- ``PeerBreaker``: per-peer circuit breaker. `threshold` consecutive
  failures trip it OPEN (fan-out to that peer fast-fails instead of
  eating a timeout per request); after `cooldown_s` it goes HALF_OPEN
  and admits one probe; a probe success closes it, a failure re-opens.
  Every transition is counted and kept in a bounded event log.

- ``DeviceDegradation``: maps a device RESOURCE_EXHAUSTED/OOM to a
  staged response — evict the request cache and compiled-plan caches,
  halve ``serving.max_wave`` with a timed recovery ramp back to the
  configured value, then re-run the failing program on the exact/XLA
  arm — instead of surfacing a 500. Every degradation event is stamped
  into the serving flight recorder and counted.

State lives in a process-global registry keyed by node id, so the
in-process 3-node test clusters get per-node breakers while the single
Engine deployment uses the default node entry.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class RetryPolicy:
    """Exponential backoff with deterministic jitter, bounded by both an
    attempt budget and a wall-clock deadline. `delay(attempt)` is pure:
    the jitter derives from (attempt, salt), so a seeded test and the
    production path compute identical schedules."""

    def __init__(self, max_attempts: int = 2, base_s: float = 0.05,
                 multiplier: float = 2.0, max_delay_s: float = 2.0,
                 deadline_s: float | None = None, salt: int = 0):
        self.max_attempts = max(1, int(max_attempts))
        self.base_s = base_s
        self.multiplier = multiplier
        self.max_delay_s = max_delay_s
        self.deadline = (time.monotonic() + deadline_s
                         if deadline_s is not None else None)
        self.salt = salt

    def delay(self, attempt: int) -> float:
        raw = min(self.base_s * (self.multiplier ** attempt),
                  self.max_delay_s)
        # deterministic jitter in [0.5, 1.0) of the raw delay: spreads
        # synchronized retry storms without an RNG dependency
        frac = (hash((attempt, self.salt)) & 0xFFFF) / 0x10000
        return raw * (0.5 + 0.5 * frac)

    def should_retry(self, attempt: int) -> bool:
        """attempt is 0-based: attempt N failed; is attempt N+1 allowed?"""
        if attempt + 1 >= self.max_attempts:
            return False
        if self.deadline is not None and (
                time.monotonic() + self.delay(attempt) >= self.deadline):
            return False  # the retry could not complete inside the deadline
        return True


class PeerBreaker:
    """Consecutive-failure circuit breaker for one remote peer."""

    def __init__(self, peer: str, threshold: int = 3,
                 cooldown_s: float = 5.0, on_transition=None):
        self.peer = peer
        self.threshold = max(1, int(threshold))
        self.cooldown_s = cooldown_s
        self.state = CLOSED
        self.consecutive_failures = 0
        self.opened_at: float | None = None
        self.trips = 0
        self._on_transition = on_transition
        self._lock = threading.Lock()

    def _transition(self, new: str, reason: str):
        old, self.state = self.state, new
        if old != new and self._on_transition is not None:
            self._on_transition(self.peer, old, new, reason)

    def allow_request(self) -> bool:
        """False = fast-fail without touching the network. An OPEN
        breaker past its cooldown admits exactly one probe (HALF_OPEN)."""
        with self._lock:
            if self.state == CLOSED:
                return True
            if self.state == OPEN:
                if (self.opened_at is not None and
                        time.monotonic() - self.opened_at >= self.cooldown_s):
                    self._transition(HALF_OPEN, "cooldown elapsed")
                    return True  # the probe
                return False
            # HALF_OPEN: one probe is already in flight
            return False

    def record_success(self):
        with self._lock:
            self.consecutive_failures = 0
            if self.state != CLOSED:
                self._transition(CLOSED, "probe succeeded")
            self.opened_at = None

    def record_failure(self, reason: str = ""):
        with self._lock:
            self.consecutive_failures += 1
            if self.state == HALF_OPEN:
                self.opened_at = time.monotonic()
                self._transition(OPEN, f"probe failed: {reason}")
            elif (self.state == CLOSED
                    and self.consecutive_failures >= self.threshold):
                self.opened_at = time.monotonic()
                self.trips += 1
                self._transition(
                    OPEN,
                    f"{self.consecutive_failures} consecutive failures: "
                    f"{reason}")

    def to_dict(self) -> dict:
        return {"peer": self.peer, "state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "trips": self.trips,
                "threshold": self.threshold,
                "cooldown_s": self.cooldown_s}


class NodeResilience:
    """Per-node resilience state: peer breakers + counters + event log."""

    def __init__(self, node_id: str):
        self.node_id = node_id
        self.breaker_threshold = int(
            _env_float("ES_TPU_BREAKER_THRESHOLD", 3))
        self.breaker_cooldown_s = _env_float("ES_TPU_BREAKER_COOLDOWN_S",
                                             5.0)
        self.retry_max_attempts = int(
            _env_float("ES_TPU_RETRY_MAX_ATTEMPTS", 2))
        self.retry_base_s = _env_float("ES_TPU_RETRY_BASE_S", 0.05)
        self._breakers: dict[str, PeerBreaker] = {}
        self._lock = threading.Lock()
        self.counters = {
            "retries": 0, "failovers": 0, "partial_responses": 0,
            "fast_fails": 0, "circuit_trips": 0, "circuit_closes": 0,
            "device_degradations": 0, "wave_rescues": 0,
        }
        self.events: deque = deque(maxlen=64)

    # -- breakers ----------------------------------------------------------

    def breaker(self, peer: str) -> PeerBreaker:
        with self._lock:
            b = self._breakers.get(peer)
            if b is None:
                b = PeerBreaker(peer, self.breaker_threshold,
                                self.breaker_cooldown_s,
                                on_transition=self._record_transition)
                self._breakers[peer] = b
            return b

    def _record_transition(self, peer, old, new, reason):
        from ..telemetry import metrics

        self.record_event("circuit", peer=peer, from_state=old,
                          to_state=new, reason=reason)
        if new == OPEN:
            self.count("circuit_trips")
            metrics.counter_inc("es.resilience.circuit.trips")
        elif new == CLOSED:
            self.count("circuit_closes")
            metrics.counter_inc("es.resilience.circuit.closes")
        metrics.gauge_set(
            f"es.resilience.circuit_open.{self.node_id}",
            sum(1 for b in self._breakers.values() if b.state != CLOSED))

    def open_peers(self) -> list[str]:
        with self._lock:
            return sorted(p for p, b in self._breakers.items()
                          if b.state != CLOSED)

    # -- counters / events -------------------------------------------------

    def count(self, key: str, n: int = 1):
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def record_event(self, kind: str, **fields):
        self.events.append({"kind": kind, "ts": time.time(),
                            "node": self.node_id, **fields})

    def retry_policy(self, deadline_s: float | None = None,
                     salt: int = 0) -> RetryPolicy:
        return RetryPolicy(max_attempts=self.retry_max_attempts,
                           base_s=self.retry_base_s,
                           deadline_s=deadline_s, salt=salt)

    def stats(self) -> dict:
        with self._lock:
            return {
                "node": self.node_id,
                "counters": dict(self.counters),
                "circuit_breakers": {
                    p: b.to_dict() for p, b in sorted(
                        self._breakers.items())},
                "open_circuits": sorted(
                    p for p, b in self._breakers.items()
                    if b.state != CLOSED),
                "recent_events": list(self.events)[-16:],
            }


# ---------------------------------------------------------------------------
# process-global registry (in-process test clusters share the process;
# each node's state keys by its node id)
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, NodeResilience] = {}
_REGISTRY_LOCK = threading.Lock()


def node_resilience(node_id: str = "node-0") -> NodeResilience:
    with _REGISTRY_LOCK:
        nr = _REGISTRY.get(node_id)
        if nr is None:
            nr = _REGISTRY[node_id] = NodeResilience(node_id)
        return nr


def resilience_stats() -> dict:
    """Merged view for `_nodes/stats` — every node registered in this
    process (one entry for a single-engine deployment)."""
    with _REGISTRY_LOCK:
        nodes = dict(_REGISTRY)
    if not nodes:
        return {"nodes": {}, "open_circuits": 0}
    per = {nid: nr.stats() for nid, nr in sorted(nodes.items())}
    return {
        "nodes": per,
        "open_circuits": sum(len(s["open_circuits"]) for s in per.values()),
    }


def reset_for_tests():
    with _REGISTRY_LOCK:
        _REGISTRY.clear()


# ---------------------------------------------------------------------------
# retrying, breaker-guarded transport send (callback style, scheduled
# through the network so it works on both transports)
# ---------------------------------------------------------------------------

def resilient_send(service, nr: NodeResilience, peer: str, action: str,
                   request, on_response, on_failure,
                   timeout: float | None = None,
                   policy: RetryPolicy | None = None) -> None:
    """`TransportService.send_request` with the read-path policy applied:
    the peer's breaker is consulted first (OPEN = fast-fail without
    network latency), retryable transport failures back off and retry
    inside the policy's budget, and every outcome feeds the breaker.
    ONLY for idempotent actions — a retried write could double-apply."""
    from ..telemetry import metrics
    from ..transport.base import (ConnectTransportError,
                                  ReceiveTimeoutError)

    breaker = nr.breaker(peer)
    if not breaker.allow_request():
        nr.count("fast_fails")
        metrics.counter_inc("es.resilience.fast_fails")
        on_failure(ConnectTransportError(
            f"circuit breaker open for peer [{peer}] "
            f"({breaker.consecutive_failures} consecutive failures)"))
        return
    if policy is None:
        policy = nr.retry_policy(deadline_s=timeout, salt=hash(action))

    def attempt(n: int):
        def ok(resp):
            breaker.record_success()
            on_response(resp)

        def fail(err):
            retryable = isinstance(err, (ConnectTransportError,
                                         ReceiveTimeoutError))
            breaker.record_failure(str(err))
            if retryable and policy.should_retry(n) \
                    and breaker.allow_request():
                nr.count("retries")
                metrics.counter_inc("es.resilience.retries")
                service.network.schedule(
                    policy.delay(n), lambda: attempt(n + 1))
                return
            on_failure(err)

        service.send_request(peer, action, request, ok, fail,
                             timeout=timeout)

    attempt(0)


# ---------------------------------------------------------------------------
# device-failure graceful degradation
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory",
                "out of memory", "OOM")


def is_device_oom(ex: BaseException) -> bool:
    """A device allocation failure, real (XlaRuntimeError with the
    RESOURCE_EXHAUSTED status) or injected (faults.InjectedDeviceOOM)."""
    from .faults import InjectedDeviceOOM

    if isinstance(ex, InjectedDeviceOOM):
        return True
    if type(ex).__name__ == "XlaRuntimeError":
        return any(m in str(ex) for m in _OOM_MARKERS)
    return isinstance(ex, MemoryError) or any(
        m in str(ex) for m in _OOM_MARKERS[:1])


class DeviceDegradation:
    """Staged device-OOM response for one engine. Stage 1: shed cached
    state (request cache + compiled-plan caches — the recoverable HBM
    and host memory). Stage 2: halve serving.max_wave so the next waves
    allocate half the scratch, with a timed ramp (doubling every
    `ramp_interval_s`) back to the configured value. Stage 3 happens at
    the call site: re-run the failing program once on the exact/XLA arm
    (the fused Pallas arm's VMEM appetite is the usual OOM culprit)."""

    def __init__(self, engine, ramp_interval_s: float | None = None):
        self.engine = engine
        self.ramp_interval_s = (
            ramp_interval_s if ramp_interval_s is not None
            else _env_float("ES_TPU_DEVICE_RAMP_S", 30.0))
        self._lock = threading.Lock()
        self._timer: threading.Timer | None = None
        self._target_wave: int | None = None
        self.events: deque = deque(maxlen=32)
        # PR 18: while degraded, the fused arm is REPRICED to ∞ in the
        # execution planner — routing shifts off it through ordinary
        # candidate filtering (and back, the moment the ramp completes)
        # instead of the PR-14 env-var pins
        from ..planner import execution_planner

        execution_planner().add_repricer(
            "fused", self, lambda: self.degraded)

    # -- stage 1: evict recoverable state ---------------------------------

    def _evict_caches(self) -> dict:
        from ..cache import request_cache

        rc = request_cache()
        before = rc.stats().get("entry_count", 0)
        rc.lru.clear()
        plans = 0
        for idx in list(self.engine.indices.values()):
            s = getattr(idx, "_searcher", None)
            for holder in (s, getattr(s, "_fused", None)):
                cache = getattr(holder, "_cache", None)
                if isinstance(cache, dict):
                    plans += len(cache)
                    cache.clear()
        return {"request_cache_entries": before, "compiled_plans": plans}

    # -- stage 2: wave halving + recovery ramp ----------------------------

    def _halve_wave(self) -> dict | None:
        sv = getattr(self.engine, "_serving", None)
        if sv is None:
            return None
        with self._lock:
            if self._target_wave is None:
                self._target_wave = int(
                    self.engine.settings.get("serving.max_wave"))
            cur = sv.max_wave
            sv.set_max_wave(max(1, cur // 2))
            self._schedule_ramp_locked()
            return {"from": cur, "to": sv.max_wave,
                    "target": self._target_wave}

    def _schedule_ramp_locked(self):
        if self._timer is not None:
            self._timer.cancel()
        self._timer = threading.Timer(self.ramp_interval_s, self._ramp_step)
        self._timer.daemon = True
        self._timer.start()

    def _ramp_step(self):
        with self._lock:
            sv = getattr(self.engine, "_serving", None)
            if sv is None or self._target_wave is None:
                self._timer = None
                return
            nxt = min(self._target_wave, max(sv.max_wave * 2, 1))
            sv.set_max_wave(nxt)
            self.events.append({"kind": "ramp", "ts": time.time(),
                                "max_wave": nxt,
                                "target": self._target_wave})
            if nxt >= self._target_wave:
                self._target_wave = None
                self._timer = None
            else:
                self._schedule_ramp_locked()

    def recover_now(self):
        """Collapse the ramp (tests / operator intervention)."""
        with self._lock:
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            sv = getattr(self.engine, "_serving", None)
            if sv is not None and self._target_wave is not None:
                sv.set_max_wave(self._target_wave)
            self._target_wave = None

    @property
    def degraded(self) -> bool:
        with self._lock:
            return self._target_wave is not None

    # -- the entry point ---------------------------------------------------

    def on_oom(self, ex: BaseException, where: str) -> dict:
        from ..telemetry import metrics

        evicted = self._evict_caches()
        wave = self._halve_wave()
        event = {
            "kind": "device_degradation", "ts": time.time(),
            "where": where, "error": f"{type(ex).__name__}: {ex}"[:256],
            "evicted": evicted, "wave": wave,
        }
        self.events.append(event)
        nr = node_resilience(getattr(self.engine.tasks, "node", "node-0"))
        nr.count("device_degradations")
        nr.record_event("device_degradation", where=where,
                        evicted=evicted, wave=wave)
        metrics.counter_inc("es.resilience.device.oom")
        metrics.counter_inc(f"es.resilience.device.oom.{where}")
        sv = getattr(self.engine, "_serving", None)
        if sv is not None:
            # stamp the PR-12 flight recorder: the black box must show
            # WHEN the degradation happened relative to the waves around it
            sv.record_degradation(event)
        return event

    def stats(self) -> dict:
        with self._lock:
            return {"degraded": self._target_wave is not None,
                    "ramp_interval_s": self.ramp_interval_s,
                    "target_max_wave": self._target_wave,
                    "recent_events": list(self.events)[-8:]}

    def close(self):
        from ..planner import execution_planner

        execution_planner().remove_repricer("fused", self)
        with self._lock:
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None


def run_with_device_recovery(engine, fn, where: str):
    """Stage-3 wrapper for a device dispatch/fetch site: a device OOM
    triggers the staged degradation, then the program re-runs ONCE with
    the fused Pallas + impact arms REPRICED to ∞ in the execution
    planner (PR 18) — their scratch appetite is what usually OOMs, and
    repricing routes the retry onto the exact/XLA arm (the smallest-
    footprint plan that returns correct results) through ordinary
    candidate filtering instead of env-var pins. Any other exception
    propagates untouched."""
    try:
        return fn()
    except Exception as ex:  # noqa: BLE001 - OOM-classified below
        if not is_device_oom(ex):
            raise
        from ..planner import execution_planner
        from .engine_thread import engine_turn

        # a solo search's fetch may have failed on the completer: the
        # degradation and the re-run are the engine thread's work
        with engine_turn():
            engine.device_degradation.on_oom(ex, where)
            with execution_planner().reprice(
                    ("fused", "impact"), reason=f"device_oom:{where}"):
                return fn()
