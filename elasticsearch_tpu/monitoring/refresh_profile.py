"""Write-path ground truth: refresh/build stage profiling (PR 13).

Every query-side kernel reports its roofline fraction (PR 5 cost model,
PR 12 XLA cross-check), but the write path — kmeans IVF builds, impact
quantization, CSR assembly, ANN tile packing, device uploads — ran as
unprofiled host loops. ROADMAP item 2 ("device-side index construction")
needs a baseline to beat and a way to prove the port moved work onto the
chip; this module is that measurement substrate:

  - `build_stage("build.<name>", **fields)` wraps one build stage in a
    `telemetry.time_kernel` dispatch (KERNEL_COSTS carries a flops/bytes
    model per stage, so host-vs-device attribution works the day the
    stage becomes a device kernel) AND, when a refresh is being
    profiled, charges the stage's wall time to the active collector;
  - `refresh_stage("<name>")` marks collector-only host phases
    (routing, analysis) that are not candidate device kernels — they
    stay visible in the profile instead of hiding in a residual;
  - `RefreshProfile` records follow the PR-12 flight-recorder
    discipline: stage timings are cut from ONE contiguous sequence of
    boundary timestamps, so they sum to the refresh wall time by
    construction (asserted by tests, not sampled); each record carries
    docs/bytes processed, the refresh kind (full/incremental/merge) and
    the resulting tail-tier state;
  - a bounded ring per engine (`indexing.profile.size`, dynamic) serves
    `GET /_refresh/profile`, feeds the `_nodes/stats` `indexing`
    section and the monitoring TSDB node_stats docs, and underwrites
    the `slo.write.*` objectives (monitoring/slo.py).

The reference's RefreshStats/MergeStats count operations and total
millis (index/refresh/RefreshStats.java); they never say WHERE a
refresh spent its time, because a CPU engine has no host-vs-device
attribution problem. Here the split IS the roadmap item."""

from __future__ import annotations

import contextvars
import threading
import time
from collections import deque
from contextlib import contextmanager

# ---------------------------------------------------------------------------
# contiguous stage collection
# ---------------------------------------------------------------------------

# the residual bucket: wall time not inside any named stage (doc
# routing, python bookkeeping, breaker admission). Named explicitly so
# an untagged hot loop shows up as a growing host_other, not as silently
# missing time.
OTHER_STAGE = "host_other"


class StageCollector:
    """Flat-sum stage clock: every stage enter/exit cuts the clock at one
    boundary timestamp and charges the elapsed segment to the stage that
    was on top of the stack. All segments derive from the SAME timestamp
    sequence, so sum(stages) == wall exactly (before rounding) — the
    flight-recorder contiguity discipline applied to refresh."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._last = self._t0
        self._stack: list[str] = [OTHER_STAGE]
        self.stages: dict[str, float] = {}  # name -> seconds
        # (name, start_s, end_s) spans relative to t0: main-thread stage
        # windows (events) and the spans of work done on other threads
        # (async_events: a shard's build, parallel/stacked.py), so that
        # shards built side by side are visible in the RefreshProfile
        # timestamps, not just inferable from sums
        self.events: list[tuple[str, float, float]] = []
        self.async_events: list[tuple[str, float, float]] = []
        # seconds of work per stage name done on other threads — kept OUT
        # of `stages` so the flat-sum invariant (sum(stages) == wall)
        # stays one thread's
        self.async_stages: dict[str, float] = {}
        self._elock = threading.Lock()

    def _cut(self) -> None:
        now = time.perf_counter()
        name = self._stack[-1]
        self.stages[name] = self.stages.get(name, 0.0) + (now - self._last)
        self._last = now

    @contextmanager
    def stage(self, name: str):
        self._cut()
        t_en = self._last
        self._stack.append(name)
        try:
            yield
        finally:
            self._cut()
            self._stack.pop()
            with self._elock:
                self.events.append(
                    (name, t_en - self._t0, self._last - self._t0))

    def note_worker(self, name: str, t_start: float, t_end: float,
                    stages: dict[str, float]) -> None:
        """Record work done on ANOTHER thread: the span `name` between two
        perf_counter readings for the timeline, and `stages`, that
        thread's own flat-sum stage seconds (they add up to the span), as
        async stage charges. Thread-safe; never touches the flat-sum
        clock."""
        with self._elock:
            self.async_events.append(
                (name, t_start - self._t0, t_end - self._t0))
            for stage, seconds in stages.items():
                self.async_stages[stage] = (
                    self.async_stages.get(stage, 0.0) + seconds)

    def finish(self) -> tuple[float, dict[str, float]]:
        """-> (wall_seconds, {stage: seconds}). wall is the last boundary
        minus the first, i.e. exactly the stage sum."""
        self._cut()
        return self._last - self._t0, dict(self.stages)


_collector: contextvars.ContextVar[StageCollector | None] = (
    contextvars.ContextVar("refresh_stage_collector", default=None))


def active_collector() -> StageCollector | None:
    """The collector of the refresh being profiled on THIS thread, if
    any. The stacked build's shard workers start in fresh contexts, run
    under a collector of their own and are reported to this one through
    note_worker."""
    return _collector.get()


@contextmanager
def collect_build_stages():
    """Activate a StageCollector for the duration of one refresh/build;
    nested build_stage/refresh_stage marks charge into it. Yields the
    collector (bench.py reads .finish() directly for the build_profile
    record)."""
    c = StageCollector()
    token = _collector.set(c)
    try:
        yield c
    finally:
        _collector.reset(token)


@contextmanager
def refresh_stage(name: str):
    """Collector-only stage mark for host phases that are NOT candidate
    device kernels (doc routing, analysis/tokenization): visible in the
    RefreshProfile, absent from KERNEL_COSTS by design."""
    c = _collector.get()
    if c is None:
        yield
        return
    with c.stage(name):
        yield


@contextmanager
def build_stage(name: str, **fields):
    """One build-stage dispatch: always a `telemetry.time_kernel(name)`
    (the dispatch-site lint requires a KERNEL_COSTS entry for the
    literal name — a new build stage cannot ship unaccounted), plus a
    collector stage charge when a refresh is being profiled. `name` is
    the full kernel name ("build.kmeans", "build.csr_assemble", ...)."""
    from ..telemetry import time_kernel

    c = _collector.get()
    if c is None:
        with time_kernel(name, **fields):
            yield
        return
    with c.stage(name):
        with time_kernel(name, **fields):
            yield


# ---------------------------------------------------------------------------
# the per-refresh record + bounded ring
# ---------------------------------------------------------------------------

def _iso_utc(ts: float | None = None) -> str:
    t = time.time() if ts is None else ts
    ms = int(t * 1000) % 1000
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(t)) + f".{ms:03d}Z"


class RefreshRecorder:
    """Bounded ring of RefreshProfile records plus the cumulative
    write-path accounting the `_nodes/stats` `indexing` section reports:
    refresh/merge counts by kind, cumulative per-stage millis, the
    current tail fraction, and a docs/s ingest EMA (rate measured
    refresh-over-refresh, smoothed — the closed-loop C7 bench arm's
    sustained-ingest readout)."""

    EMA_ALPHA = 0.3

    def __init__(self, size: int = 256):
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=max(int(size), 1))
        self._seq = 0
        self._counts: dict[str, int] = {}
        self._stage_ms: dict[str, float] = {}
        self._docs_total = 0
        self._last_record_t: float | None = None
        self._docs_per_s_ema: float | None = None
        self._last_tail_fraction = 0.0

    def set_size(self, size) -> None:
        size = max(int(size), 1)
        with self._lock:
            if size != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=size)

    def record(self, profile: dict) -> dict:
        """Append one finished RefreshProfile; returns it with its
        sequence number stamped."""
        now = time.monotonic()
        with self._lock:
            self._seq += 1
            profile = {"refresh": self._seq, **profile}
            self._ring.append(profile)
            kind = profile.get("kind", "full")
            self._counts[kind] = self._counts.get(kind, 0) + 1
            # worker-thread stage time (shards built side by side) counts
            # in the cumulative accounting: the SLO analyze fraction and
            # the health dominant-stage diagnosis must see every
            # millisecond, overlapped or not. The main thread's `build`
            # stage is then the wait for those workers, whose own stages
            # are what it was spent on: counted once, by them
            workers_ms = profile.get("async_stages_ms") or {}
            for stage, ms in (profile.get("stages_ms") or {}).items():
                if stage == "build" and workers_ms:
                    continue
                self._stage_ms[stage] = self._stage_ms.get(stage, 0.0) + ms
            for stage, ms in workers_ms.items():
                self._stage_ms[stage] = self._stage_ms.get(stage, 0.0) + ms
            docs = int(profile.get("docs", 0))
            self._docs_total += docs
            if profile.get("tail_fraction") is not None:
                self._last_tail_fraction = profile["tail_fraction"]
            if self._last_record_t is not None and docs:
                dt = max(now - self._last_record_t, 1e-6)
                rate = docs / dt
                self._docs_per_s_ema = (
                    rate if self._docs_per_s_ema is None
                    else self.EMA_ALPHA * rate
                    + (1.0 - self.EMA_ALPHA) * self._docs_per_s_ema)
            self._last_record_t = now
        from ..telemetry import metrics

        metrics.counter_inc(f"es.indexing.refresh.{kind}")
        return profile

    def profiles(self, n: int | None = None) -> dict:
        """The recorded refreshes, oldest first (GET /_refresh/profile)."""
        with self._lock:
            profs = list(self._ring)
            total = self._seq
        if n is not None:
            profs = profs[-max(int(n), 0):]
        return {
            "capacity": self._ring.maxlen,
            "recorded_total": total,
            "retained": len(profs),
            "profiles": profs,
        }

    def indexing_stats(self) -> dict:
        with self._lock:
            return {
                "refresh_total": sum(self._counts.values()),
                "refresh_kinds": dict(self._counts),
                "merge_total": (self._counts.get("merge", 0)
                                + self._counts.get("segment_merge", 0)),
                "stage_ms": {k: round(v, 3)
                             for k, v in sorted(self._stage_ms.items())},
                "docs_refreshed_total": self._docs_total,
                "docs_per_s_ema": (round(self._docs_per_s_ema, 3)
                                   if self._docs_per_s_ema is not None
                                   else None),
                "tail_fraction": self._last_tail_fraction,
            }

    def reset_for_tests(self) -> None:
        with self._lock:
            self._ring.clear()
            self._seq = 0
            self._counts.clear()
            self._stage_ms.clear()
            self._docs_total = 0
            self._last_record_t = None
            self._docs_per_s_ema = None
            self._last_tail_fraction = 0.0


# standalone EsIndex instances (no owning Engine — the unit-test and
# library-embedding path) record here; Engine-owned indices record into
# their engine's own recorder so in-process multi-node fixtures never
# mix nodes' write paths
_default_recorder = RefreshRecorder()


def default_recorder() -> RefreshRecorder:
    return _default_recorder


def recorder_for(index) -> RefreshRecorder:
    eng = getattr(index, "engine", None)
    if eng is not None:
        try:
            return eng.refresh_recorder
        except Exception:  # noqa: BLE001 - recorder must never fail refresh
            pass
    return _default_recorder


@contextmanager
def profile_refresh(index, kind: str):
    """Wrap one refresh/merge of `index`: activates the stage collector,
    and on exit assembles the RefreshProfile (stage sums == wall by
    construction) with the resulting tier state and records it. Never
    raises past the refresh itself."""
    from ..telemetry import current_node_name

    with collect_build_stages() as c:
        yield c
    try:
        wall_s, stages = c.finish()
        tiers = index.tier_stats()
        if kind == "incremental" or kind == "segment_merge":
            # incremental packs the new docs; a segment fold (PR 15)
            # reprocesses exactly the tail union — never the base
            docs = tiers["tail_docs"]
        else:  # full rebuild / major merge processes every visible doc
            docs = tiers["base_docs"] + tiers["tail_docs"]
        profile = {
            "@timestamp": _iso_utc(),
            "node": current_node_name(),
            "index": index.name,
            "kind": kind,
            "docs": int(docs),
            "bytes": int(getattr(index, "_base_nbytes", 0)),
            "stages_ms": {k: round(v * 1000, 4) for k, v in stages.items()},
            "wall_ms": round(wall_s * 1000, 4),
            "tail_fraction": tiers["tail_fraction"],
            "tiers": {"base_docs": tiers["base_docs"],
                      "tail_docs": tiers["tail_docs"],
                      "segments": tiers.get("segments", 0)},
        }
        with c._elock:
            events = list(c.events)
            async_events = list(c.async_events)
            async_stages = dict(c.async_stages)
        profile["stage_events_ms"] = (
            [[name, round(s * 1000, 3), round(e * 1000, 3), "main"]
             for name, s, e in events]
            + [[name, round(s * 1000, 3), round(e * 1000, 3), "worker"]
               for name, s, e in async_events])
        if async_stages:
            # worker-thread time (the shards' builders): outside the
            # flat-sum stages by construction, folded into the
            # recorder's cumulative stage accounting by record()
            profile["async_stages_ms"] = {
                k: round(v * 1000, 4) for k, v in async_stages.items()}
            # overlap evidence as one scalar: worker span time that ran
            # concurrently with main-thread stage work (main spans
            # union-merged first — nesting must not double count)
            merged: list[list[float]] = []
            for s, e in sorted((s, e) for _n, s, e in events):
                if merged and s <= merged[-1][1]:
                    merged[-1][1] = max(merged[-1][1], e)
                else:
                    merged.append([s, e])
            ov = 0.0
            for _n, a0, a1 in async_events:
                for m0, m1 in merged:
                    ov += max(0.0, min(a1, m1) - max(a0, m0))
            profile["analyze_overlap_ms"] = round(ov * 1000, 4)
        recorder_for(index).record(profile)
    except Exception:  # noqa: BLE001 - profiling must never fail a refresh
        pass
