"""Telemetry: distributed tracing, device-cost profiling, slow logs,
metrics, deprecation warnings.

Parity targets (reference): telemetry/tracing/Tracer.java:33 (OTel-API
abstraction; spans started around search phases, SearchService.java:677),
tasks/TaskManager + ThreadContext header propagation (trace context rides
transport request headers so coordinator->shard fan-out is one trace),
index/SearchSlowLog.java + IndexingSlowLog.java (per-index thresholds,
dedicated loggers), common/logging/HeaderWarning.java (deprecation warnings
returned as RFC-7234 `Warning` response headers and logged once), and the
APM metering surface (telemetry/metric/MeterRegistry) — here exported as
Prometheus text exposition instead of an APM agent."""

from __future__ import annotations

import contextvars
import itertools
import logging
import math
import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass

log = logging.getLogger("elasticsearch_tpu")
slowlog_search = logging.getLogger("elasticsearch_tpu.slowlog.search")
slowlog_index = logging.getLogger("elasticsearch_tpu.slowlog.index")
deprecation_log = logging.getLogger("elasticsearch_tpu.deprecation")


# ---------------------------------------------------------------------------
# trace context (W3C traceparent + task id propagation)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TraceContext:
    """The propagated identity of one end-to-end request: carried in REST
    headers (W3C `traceparent` + `X-Opaque-Id`) and threaded through
    transport request headers so every node's spans join one trace
    (reference behavior: ThreadContext trace headers + Task#getParentTaskId
    riding TransportService requests)."""

    trace_id: str                      # 32 lowercase hex chars
    parent_span_id: str | None = None  # 16 hex: span to parent under
    task_id: str | None = None         # X-Opaque-Id / task identity


def new_trace_id() -> str:
    return os.urandom(16).hex()


# span ids come from a process-local counter (no syscall per span) and are
# formatted when read; the random base keeps two processes of one cluster
# from minting the same id
_span_ids = itertools.count(int.from_bytes(os.urandom(8), "big"))


def _hex16(n: int) -> str:
    return f"{n & 0xFFFFFFFFFFFFFFFF:016x}"


_trace_ctx: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "trace_context", default=None)
_node_name: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "telemetry_node_name", default=None)


def current_trace() -> TraceContext | None:
    return _trace_ctx.get()


def current_node_name() -> str:
    return _node_name.get() or "node-0"


@contextmanager
def activate_trace(ctx: TraceContext | None, node: str | None = None):
    """Install a trace context (and optionally a node identity) for the
    duration of a request / transport handler invocation."""
    t1 = _trace_ctx.set(ctx) if ctx is not None else None
    t2 = _node_name.set(node) if node is not None else None
    try:
        yield ctx
    finally:
        if t2 is not None:
            _node_name.reset(t2)
        if t1 is not None:
            _trace_ctx.reset(t1)


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """W3C traceparent `00-<32hex>-<16hex>-<2hex>` -> (trace_id, span_id)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    try:
        int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None
    if parts[1] == "0" * 32 or parts[2] == "0" * 16:
        return None
    return parts[1].lower(), parts[2].lower()


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def propagation_headers() -> dict | None:
    """Transport-request headers carrying the caller's trace identity:
    the receiving node's handler spans parent under the caller's CURRENT
    span (the coordinator fan-out span), reconstructing one tree."""
    ctx = _trace_ctx.get()
    cur = TRACER.current_span()
    if ctx is None and cur is None:
        return None
    trace_id = cur.trace_id if cur is not None else ctx.trace_id
    parent = cur.span_id if cur is not None else ctx.parent_span_id
    out = {"trace_id": trace_id, "parent_span_id": parent}
    if ctx is not None and ctx.task_id:
        out["task_id"] = ctx.task_id
    return out


def context_from_headers(headers: dict | None) -> TraceContext | None:
    if not headers or not headers.get("trace_id"):
        return None
    return TraceContext(
        trace_id=str(headers["trace_id"]),
        parent_span_id=headers.get("parent_span_id"),
        task_id=headers.get("task_id"),
    )


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

# The stages of a served search, from the REST handler down to the device
# fetch. Inside a `rest.search` these names are summed into
# `es.span.<name>.ns` / `.count` (what `_nodes/stats` -> metrics.counters
# ships and the benchmark's per-layer readers divide into a mean a search).
# Any other span, and a stage entered from elsewhere (`_msearch`, a library
# call), is recorded and summed nowhere: the counters stay one search's, and
# a span named by a request's data can never mint one. A search that the
# serving front end answered records them from its wave's stages
# (`ServingService.member_spans`). Of the engine thread's work for a search,
# `engine.route` is what comes before its parsing (the index's resolution,
# the refresh check, the recovery wrapper, the request cache's look-up),
# `engine.unpack` the fetched outputs' unpacking and `engine.account` the
# bookkeeping of its measurements (`account()`); `rest.read` is the body's
# reading and decoding on the event loop, and `rest.resume` the loop's
# wake-up once the answer is ready, a wait that no thread performs.
STAGES = (
    "rest.search", "engine.queue", "engine.search", "engine.parse",
    "engine.plan", "engine.dispatch", "engine.fetch", "engine.collect",
    "rest.respond", "engine.route", "engine.unpack", "engine.account",
    "rest.read", "rest.resume",
)
# The stages of a serving wave (serving/service.py), which several members
# share and two threads carry: host planning on the engine thread up to each
# program's launch, the launches, the one combined fetch on the completer
# thread, the finish on the engine thread. `WaveStages` sums them and the
# service adds a wave's to `es.span.<stage>.ns` / `.count` at once when the
# wave ends, as a search's are when its `rest.search` ends. They never
# nest (a launch pauses the planning around it), so all four are leaves.
WAVE_STAGES = ("engine.wave_plan", "engine.wave_launch", "engine.wave_fetch",
               "engine.wave_finish")
# The hand-offs between a wave's threads, which no thread performs: from the
# claim to the engine thread's start of the planning, from the last launch
# to the completer's start of the fetch, from the fetch's end to the engine
# thread's start of the finish. With the four stages above they tile a wave
# from its claim to its answers (`WaveStages.handoffs`).
WAVE_HANDOFFS = ("engine.wave_queue", "engine.wave_handoff",
                 "engine.wave_rejoin")
# The leaves among them also open a `jax.profiler.TraceAnnotation` while a
# capture runs, which puts them on its host plane, on the capture's clock,
# beside PJRT's own events. Parents stay off it: an idle gap of the device
# runs from one request's fetch to the next one's launch, and a parent
# would cover every gap and name none. A leaf entered inside another one
# (the solo path's stages inside a wave's) opens none either: annotations
# never nest.
ANNOTATED_STAGES = frozenset({
    "engine.parse", "engine.plan", "engine.dispatch", "engine.fetch",
    "engine.collect", "rest.respond", "engine.route", "engine.unpack",
    "engine.account", "rest.read", *WAVE_STAGES,
})
# A solo search's hand-over of the rest of its work to the completer
# (common/engine_thread), which no thread performs: from its launch's end to
# the moment another thread takes the engine, the wait for the completer to
# finish the search before it included. Only a search that hands over has one.
SOLO_HANDOFF = "engine.handoff"
# the solo searches whose fetch and what follows it ran on the completer
HANDED_OFF = "es.search.solo.handed_off"
_STAGE_COUNTERS = {name: (f"es.span.{name}.ns", f"es.span.{name}.count")
                   for name in STAGES + WAVE_STAGES + WAVE_HANDOFFS
                   + (SOLO_HANDOFF,)}
# The time from a connection's last answer to its next request's arrival at
# the REST layer (`rest/app.py`): the client's turnaround, the socket,
# aiohttp's parsing and the event loop's delay in starting the handler.
CLIENT_GAP = ("es.rest.client_gap.ns", "es.rest.client_gap.count")
# What every solo search's plan adds at once (parallel/sharded._agg_dispatch):
# the rows of the match family's two lists (query/nodes.match_params) its
# program gathers, the sparse block rows plus the dense rows over its shards,
# without and with the padding to the family's tiers; 0 and 0 for a query
# that is no match. A searcher ships both at 0 from its start.
SOLO_ROWS = ("es.search.solo.rows", "es.search.solo.padded_rows")
_annotation = None  # jax.profiler.TraceAnnotation while a capture runs


def annotate_stages(on: bool) -> None:
    """ProfilerService's switch: the leaf stages open annotations only
    between a capture's start and its stop, and cost nothing of it else."""
    global _annotation
    if on:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    else:
        _annotation = None


class Span:
    """One recorded interval. `Tracer.span` hands it out as its own context
    manager: entering stamps the start and makes it the current span,
    leaving stamps the end and files it under its parent. Times are
    `time.perf_counter_ns` readings; the wall clock is read for a root
    alone, and a child's `start_unix` is its root's plus the distance."""

    __slots__ = ("name", "attributes", "children", "trace_id", "node",
                 "_id", "_parent_id", "_t0", "_t1", "_wall", "_sums",
                 "_tracer", "_parent", "_token", "_ann")

    def __init__(self, tracer, name: str, attributes: dict):
        self.name = name
        self.attributes = attributes
        self.children: list = []
        self.node = _node_name.get() or "node-0"
        self._id = next(_span_ids)
        self._t1 = self._ann = None
        self._tracer = tracer
        parent = self._parent = tracer._current.get()
        if parent is not None:
            self.trace_id = parent.trace_id
            self._parent_id = parent._id
            self._wall = None
            self._sums = parent._sums
        else:
            # a root: under the request's propagated context (REST headers,
            # a transport request's) or a trace of its own
            ctx = _trace_ctx.get()
            self.trace_id = ctx.trace_id if ctx is not None else new_trace_id()
            self._parent_id = ctx.parent_span_id if ctx is not None else None
            self._wall = time.time()  # epoch seconds (cross-node alignment)
            self._sums = None
        if name == "rest.search":
            self._sums = {}  # stage -> [ns, count] of this search's spans

    def __enter__(self) -> "Span":
        self._token = self._tracer._current.set(self)
        if self.name in ANNOTATED_STAGES:
            if _lead.get() is not None:
                end_lead()
            if _annotation is not None and not _in_stage_leaf(self._parent):
                self._ann = _annotation(self.name)
                self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._tracer._current.reset(self._token)
        self._token = None  # it holds the parent: no cycle through it
        self._tracer._finish(self, t1)
        return False

    def close(self, end_ns: int) -> None:
        """Leave the span at `end_ns`, a `time.perf_counter_ns()` reading
        that several intervals share as the end of one and the start of the
        next (`WaveStages`)."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._current.reset(self._token)
        self._token = None
        self._tracer._finish(self, end_ns)

    @property
    def span_id(self) -> str:
        return _hex16(self._id)

    @property
    def parent_span_id(self) -> str | None:
        p = self._parent_id  # a local parent's counter, or a caller's hex id
        return _hex16(p) if isinstance(p, int) else p

    @property
    def start(self) -> float:
        return self._t0 * 1e-9      # seconds on time.perf_counter's clock

    @property
    def end(self) -> float | None:
        return None if self._t1 is None else self._t1 * 1e-9

    @property
    def duration_ms(self) -> float:
        return ((self._t1 or time.perf_counter_ns()) - self._t0) * 1e-6

    def to_dict(self, root: "Span | None" = None) -> dict:
        root = root or self
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "node": self.node,
            "start_unix": root._wall + (self._t0 - root._t0) * 1e-9,
            "duration_ms": round(self.duration_ms, 3),
            "attributes": dict(self.attributes),
        }


# a leaf stage that crosses functions: open, and no span's parent, until the
# calling thread's next leaf stage begins or `end_lead()`
_lead: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "lead_stage", default=None)


def begin_lead(name: str) -> None:
    """Open the leaf stage `name` under the current span, without making it
    the current one: it ends where the next leaf stage of the calling thread
    begins (`Span.__enter__`), or at `end_lead()`, whichever comes first. A
    search's routing is such a stretch: from its index's resolution through
    the refresh check, the recovery wrapper and the request cache's look-up
    to its parsing, across the functions that call one another on the way."""
    s = Span(TRACER, name, {})
    if _annotation is not None and not _in_stage_leaf(s._parent):
        s._ann = _annotation(name)
        s._ann.__enter__()
    _lead.set(s)
    s._t0 = time.perf_counter_ns()


def end_lead() -> None:
    """End the calling thread's open lead stage, if any."""
    s = _lead.get()
    if s is None:
        return
    end_ns = time.perf_counter_ns()
    _lead.set(None)
    if s._ann is not None:
        s._ann.__exit__(None, None, None)
    TRACER._finish(s, end_ns)


def _in_stage_leaf(span: Span | None) -> bool:
    """Whether `span` is, or lies inside, an open leaf stage."""
    while span is not None:
        if span.name in ANNOTATED_STAGES:
            return True
        span = span._parent
    return False


def _walk_spans(span: Span):
    yield span
    for c in span.children:
        yield from _walk_spans(c)


class Tracer:
    """In-memory tracer: spans nest via a context variable; the last
    `keep` root spans are retained for inspection (`GET /_trace/{id}`).
    `span` is the one way to record a span around work a thread does;
    `record` takes a wait that no thread performs, with explicit ends."""

    def __init__(self, keep: int = 256):
        self.finished: deque[Span] = deque(maxlen=keep)
        self._current: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
            "current_span", default=None)

    def current_span(self) -> Span | None:
        return self._current.get()

    def span(self, name: str, **attributes) -> Span:
        return Span(self, name, attributes)

    def record(self, name: str, start_ns: int, end_ns: int,
               children=(), **attributes) -> Span:
        """A finished span from two `time.perf_counter_ns()` readings, as a
        child of the current span: counters and span tree, no annotation.
        `children` are (name, start_ns, end_ns) of finished spans under it."""
        s = Span(self, name, attributes)
        s._t0 = start_ns
        if s._wall is not None:
            s._wall -= (time.perf_counter_ns() - start_ns) * 1e-9
        if children:
            token = self._current.set(s)
            try:
                for child in children:
                    self.record(*child)
            finally:
                self._current.reset(token)
        self._finish(s, end_ns)
        return s

    def _finish(self, s: Span, end_ns: int) -> None:
        s._t1 = end_ns
        sums = s._sums
        if sums is not None and s.name in _STAGE_COUNTERS:
            rec = sums.get(s.name)
            if rec is None:
                sums[s.name] = [end_ns - s._t0, 1]
            else:
                rec[0] += end_ns - s._t0
                rec[1] += 1
            if s.name == "rest.search":
                # one search's stages reach the counters together, under
                # one acquisition of the lock: every stage's count moves by
                # the same searches, whatever window the counters are read in
                metrics.counters_add(
                    (_STAGE_COUNTERS[name], rec) for name, rec in sums.items())
        parent, s._parent = s._parent, None  # children point down only
        if parent is not None:
            parent.children.append(s)
        else:
            self.finished.append(s)
            log.debug("span %s %.2fms %s", s.name, s.duration_ms, s.attributes)

    # -- inspection / export ------------------------------------------------

    def spans_for_trace(self, trace_id: str) -> list[dict]:
        """Flattened span dicts (this process) belonging to one trace."""
        out = []
        for root in list(self.finished):
            if root.trace_id != trace_id:
                continue
            out.extend(s.to_dict(root) for s in _walk_spans(root))
        return out

    def recent_spans(self, n: int = 20) -> list[dict]:
        """Summaries of the most recently finished root spans (newest
        last), for _nodes/stats."""
        out = []
        for root in list(self.finished)[-n:]:
            d = root.to_dict()
            d["span_count"] = sum(1 for _ in _walk_spans(root))
            out.append(d)
        return out


TRACER = Tracer()


def account():
    """`engine.account` around the bookkeeping of a search's measurements
    (a dispatch's XLA cross-check and counters, `time_kernel`'s tail): a leaf
    stage of the calling thread's search. Nothing where the thread carries
    no span (a refresh in the background: no root per kernel call) or lies
    inside another leaf (a wave's stages): that work stays in the leaf it
    lies in, and is counted once."""
    cur = TRACER._current.get()
    if cur is None or _in_stage_leaf(cur):
        return nullcontext()
    return Span(TRACER, "engine.account", {})


class WaveStages:
    """The stage spans of one serving wave, summed: `stage(name)` is a span
    of WAVE_STAGES around work of the calling thread. Entered inside another
    stage of the same wave on the same thread (a program's launch inside the
    planning) it pauses that one, and the two share the reading at which one
    ends and the other starts, so the stages lie end to end and their sum is
    wall time of the threads that carried the wave. `sums` is
    {stage: [ns, spans]}; `bounds` holds the first start and the last end of
    each stage entered outside another (`handoffs` reads them);
    `counter_pairs()` is what `metrics.counters_add` takes once the wave has
    ended."""

    __slots__ = ("sums", "bounds", "_open")

    def __init__(self):
        self.sums: dict[str, list] = {}
        self.bounds: dict[str, list] = {}
        self._open: list = []     # (name, span) entered and not left, in order

    def _enter(self, name: str, at: int) -> None:
        span = TRACER.span(name)
        span.__enter__()
        span._t0 = at
        self._open.append((name, span))

    def _leave(self, at: int) -> None:
        name, span = self._open.pop()
        span.close(at)
        rec = self.sums.setdefault(name, [0, 0])
        rec[0] += at - span._t0
        rec[1] += 1

    @contextmanager
    def stage(self, name: str):
        at = time.perf_counter_ns()
        outer = self._open[-1][0] if self._open else None
        if outer is not None:
            self._leave(at)
        else:
            self.bounds.setdefault(name, [at, at])
        token = _wave_stages.set(self)
        self._enter(name, at)
        try:
            yield
        finally:
            at = time.perf_counter_ns()
            self._leave(at)
            _wave_stages.reset(token)
            if outer is not None:
                self._enter(outer, at)
            else:
                self.bounds[name][1] = at

    def ns(self, name: str) -> int:
        return self.sums.get(name, (0, 0))[0]

    def handoffs(self, claim_ns: int) -> list[tuple[str, int, int]]:
        """(name, start, end) of WAVE_HANDOFFS, once the wave's finish has
        begun: claim -> planning, planning -> fetch, fetch -> finish. Set in
        the sums, so that the seven stages sum to the claim -> finish."""
        b = self.bounds
        plan, fetch, finish = (b["engine.wave_plan"], b["engine.wave_fetch"],
                               b["engine.wave_finish"])
        out = list(zip(WAVE_HANDOFFS, (claim_ns, plan[1], fetch[1]),
                       (plan[0], fetch[0], finish[0])))
        for name, t0, t1 in out:
            self.sums[name] = [t1 - t0, 1]
        return out

    def counter_pairs(self):
        return [(_STAGE_COUNTERS[name], rec)
                for name, rec in self.sums.items()]


_wave_stages: contextvars.ContextVar[WaveStages | None] = \
    contextvars.ContextVar("wave_stages", default=None)


def wave_stage(name: str):
    """`stage(name)` of the wave this thread is carrying a stage of, and
    nothing outside one (a solo `_msearch` launches the same programs)."""
    ws = _wave_stages.get()
    return ws.stage(name) if ws is not None else nullcontext()


def stitch_trace(spans: list[dict]) -> dict:
    """Assemble flattened span dicts (possibly from several nodes) into
    the `/_trace/{trace_id}` response: deduped, time-ordered, with a
    parent/child tree reconstructed from span ids."""
    by_id: dict[str, dict] = {}
    for s in spans:
        by_id.setdefault(s["span_id"], s)
    ordered = sorted(by_id.values(), key=lambda s: s.get("start_unix", 0.0))
    roots: list[dict] = []
    for s in ordered:
        s = dict(s)
        s["children"] = []
        by_id[s["span_id"]] = s
    for s in by_id.values():
        p = s.get("parent_span_id")
        if p and p in by_id:
            by_id[p]["children"].append(s)
        else:
            roots.append(s)
    for s in by_id.values():
        s["children"].sort(key=lambda c: c.get("start_unix", 0.0))
    return {
        "trace_id": spans[0]["trace_id"] if spans else None,
        "span_count": len(by_id),
        "nodes": sorted({s["node"] for s in by_id.values()}),
        "spans": roots,
    }


# ---------------------------------------------------------------------------
# device-cost profiling ("profile": true collectors)
# ---------------------------------------------------------------------------

_profile_events: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "profile_events", default=None)


@contextmanager
def collect_profile_events():
    """Activate the per-request device-cost collector: kernel call sites
    (ops/fused, ops/batched, query/executor, parallel/sharded) append
    events while a `"profile": true` search executes. The yielded list is
    shared by reference, so events recorded on the engine worker thread
    (contextvars propagate through rest/app.call) are visible here."""
    events: list[dict] = []
    token = _profile_events.set(events)
    try:
        yield events
    finally:
        _profile_events.reset(token)


def profile_collector_active() -> bool:
    return _profile_events.get() is not None


def profile_event(kind: str, **fields) -> None:
    """Record one profiling event (kind: kernel | tier | cache | phase)
    when a collector is active; free otherwise."""
    bucket = _profile_events.get()
    if bucket is not None:
        bucket.append({"kind": kind, **fields})


def host_transition(kind: str) -> None:
    """Count one host↔device transition (kind: "dispatch" = a
    program-launch phase handed to the device, "fetch" = a blocking
    device→host result pull, "refresh" = a refresh-time pack/bitmap
    upload). PR 11: the serving wave executor proves its end-to-end
    fusion with these — one dispatch phase and ONE combined fetch per
    wave (extra rounds from rare escalations/two-pass aggs are counted,
    never hidden). PR 13 adds the refresh kind so ROADMAP item 2's
    background DEVICE merges have a transition budget to hold, not just
    the serving waves. Feeds the cumulative
    es.device.host_transitions.* counters and, when a collector is
    active, a per-request "transition" profile event."""
    metrics.counter_inc(f"es.device.host_transitions.{kind}")
    profile_event("transition", transition=kind)


@contextmanager
def time_kernel(name: str, **fields):
    """Wall-time one host-level device dispatch+fetch (the Pallas / XLA
    call sites). Always feeds the kernel-level latency histogram; also
    records a profile event when a collector is active.

    PR 5: the shape fields double as the cost-model input
    (monitoring/costmodel.KERNEL_COSTS keyed by `name`): when the model
    resolves, the dispatch also records its FLOPs/bytes and the achieved
    MFU + bandwidth utilization — per call into the profile event, and
    cumulatively into es.kernel.<name>.{flops,bytes} counters and
    .{mfu_pct,bw_pct} histograms (→ _nodes/stats device section,
    Prometheus exposition, and the .monitoring-es-* collectors). What it
    records after the interval is `engine.account` of the search whose
    thread it runs on (`account()`)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        sec = time.perf_counter() - t0
        with account():
            _account_kernel(name, fields, sec)


def _account_kernel(name: str, fields: dict, sec: float) -> None:
    """What `time_kernel` records of one interval of `sec` seconds."""
    ms = sec * 1000
    metrics.histogram_record(f"es.kernel.{name}.ms", ms)
    util = None
    try:
        from .monitoring.costmodel import utilization

        util = utilization(name, fields, sec)
    except Exception:  # noqa: BLE001 - accounting never fails a search
        util = None
    if util is not None:
        try:
            # feed the execution planner's achieved-roofline
            # EMA + predicted-vs-actual residual from the SAME
            # utilization record (pre-augmented fields)
            from .planner import execution_planner

            execution_planner().observe(name, fields, sec, util)
        except Exception:  # noqa: BLE001 - advice never fails a search
            pass
        metrics.counter_inc(f"es.kernel.{name}.flops", util["flops"])
        metrics.counter_inc(f"es.kernel.{name}.bytes", util["bytes"])
        metrics.histogram_record(f"es.kernel.{name}.mfu_pct",
                                 util["mfu"] * 100.0)
        metrics.histogram_record(f"es.kernel.{name}.bw_pct",
                                 util["bw_util"] * 100.0)
        fields = {**fields, "flops": util["flops"],
                  "bytes": util["bytes"],
                  "mfu": round(util["mfu"], 6),
                  "bw_util": round(util["bw_util"], 6)}
        if "ici_util" in util:
            # collective kernels: achieved interconnect
            # utilization of the all-gather merge traffic
            metrics.histogram_record(f"es.kernel.{name}.ici_pct",
                                     util["ici_util"] * 100.0)
            fields["ici_bytes"] = util["ici_bytes"]
            fields["ici_util"] = round(util["ici_util"], 6)
    profile_event("kernel", kernel=name, ms=round(ms, 4), **fields)


# ---------------------------------------------------------------------------
# slow logs
# ---------------------------------------------------------------------------

_LEVELS = (("warn", logging.WARNING), ("info", logging.INFO),
           ("debug", logging.DEBUG), ("trace", 5))

SLOWLOG_KEEP = 128
recent_slowlogs: deque[dict] = deque(maxlen=SLOWLOG_KEEP)


def _threshold_ms(settings: dict, prefix: str, level: str):
    from .utils.durations import parse_duration_seconds

    raw = settings.get(f"{prefix}.{level}")
    if raw is None:
        return None
    sec = parse_duration_seconds(raw, None)
    return None if sec is None else sec * 1000


def _slowlog_identity() -> dict:
    """trace/task/node identity of the in-flight request, so a slowlog
    line is joinable against its trace without log scraping (the
    reference stamps X-Opaque-Id and task ids into its slowlog ECS
    fields, index/SearchSlowLog.java)."""
    out = {"node": current_node_name()}
    cur = TRACER.current_span()
    ctx = _trace_ctx.get()
    if cur is not None and cur.trace_id:
        out["trace_id"] = cur.trace_id
    elif ctx is not None:
        out["trace_id"] = ctx.trace_id
    if ctx is not None and ctx.task_id:
        out["task_id"] = ctx.task_id
    return out


def record_search_slowlog(index_name: str, settings: dict, took_ms: float,
                          query_desc: str):
    """Log at the highest matching threshold (reference behavior:
    SearchSlowLog — one record per phase at the matched level)."""
    for level, py_level in _LEVELS:
        t = _threshold_ms(settings, "search.slowlog.threshold.query", level)
        if t is not None and took_ms >= t:
            entry = {"index": index_name, "took_ms": round(took_ms, 3),
                     "level": level, "source": query_desc, "kind": "search",
                     **_slowlog_identity()}
            recent_slowlogs.append(entry)
            slowlog_search.log(py_level,
                               "[%s] took[%dms], source[%s]",
                               index_name, took_ms, query_desc)
            return


def record_indexing_slowlog(index_name: str, settings: dict, took_ms: float,
                            doc_id: str):
    for level, py_level in _LEVELS:
        t = _threshold_ms(settings, "indexing.slowlog.threshold.index", level)
        if t is not None and took_ms >= t:
            entry = {"index": index_name, "took_ms": round(took_ms, 3),
                     "level": level, "id": doc_id, "kind": "indexing",
                     **_slowlog_identity()}
            recent_slowlogs.append(entry)
            slowlog_index.log(py_level, "[%s] took[%dms], id[%s]",
                              index_name, took_ms, doc_id)
            return


# ---- deprecation warnings -------------------------------------------------

_request_warnings: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "deprecation_warnings", default=None)


def begin_request_warnings() -> None:
    _request_warnings.set([])


def add_deprecation_warning(message: str) -> None:
    """Collect a warning for the in-flight REST request and log it
    (HeaderWarning.addWarning analog)."""
    deprecation_log.warning(message)
    bucket = _request_warnings.get()
    if bucket is not None and message not in bucket:
        bucket.append(message)


def drain_request_warnings() -> list[str]:
    out = _request_warnings.get() or []
    _request_warnings.set(None)
    return out


def warning_header_value(message: str) -> str:
    # RFC 7234 warn-code 299 (miscellaneous persistent warning), as ES emits
    return f'299 Elasticsearch-tpu "{message}"'


# ---------------------------------------------------------------------------
# metrics registry (APM metering analog)
# ---------------------------------------------------------------------------

# exponential histogram buckets: 4 per octave (factor 2^(1/4) ~ 1.19), so
# percentile estimates carry <~19% relative error — the OTel exponential
# histogram with scale=2, which the reference's APM metering exports
_HIST_SCALE = 4
_HIST_LOG_BASE = math.log(2.0) / _HIST_SCALE


def _bucket_index(value: float) -> int:
    # smallest i with 2^(i/4) >= value  (value > 0)
    return math.ceil(math.log(value) / _HIST_LOG_BASE - 1e-9)


def _bucket_upper(idx: int) -> float:
    return 2.0 ** (idx / _HIST_SCALE)


class _Histogram:
    """Exponential-bucket histogram: count/sum/min/max plus sparse
    bucket counts keyed by exponent index; <=0 values land in a dedicated
    zero bucket."""

    __slots__ = ("count", "sum", "min", "max", "zero_count", "buckets")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.zero_count = 0
        self.buckets: dict[int, int] = {}

    def record(self, value: float) -> None:
        self.count += 1
        self.sum += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        if value <= 0.0:
            self.zero_count += 1
            return
        i = _bucket_index(value)
        self.buckets[i] = self.buckets.get(i, 0) + 1

    def percentile(self, q: float) -> float:
        """Estimated q-quantile (0..1): geometric bucket midpoint of the
        bucket holding the q*count-th sample, clamped to observed
        min/max so tails never exceed real data."""
        if self.count == 0:
            return 0.0
        rank = q * self.count
        seen = self.zero_count
        if rank <= seen:
            return max(self.min, 0.0) if self.zero_count else 0.0
        for i in sorted(self.buckets):
            seen += self.buckets[i]
            if rank <= seen:
                mid = math.sqrt(_bucket_upper(i - 1) * _bucket_upper(i))
                return min(max(mid, self.min), self.max)
        return self.max

    def snapshot(self) -> dict:
        c = self.count
        return {
            "count": c,
            "sum": self.sum,
            "min": (self.min if c else 0.0),
            "max": (self.max if c else 0.0),
            "avg": (self.sum / c if c else 0.0),
            "p50": self.percentile(0.50),
            "p90": self.percentile(0.90),
            "p99": self.percentile(0.99),
        }


class MetricsRegistry:
    """Named counters / gauges / histograms with a snapshot API.

    The reference exposes a metering surface plugins and core register
    instruments on (reference behavior: server/.../telemetry/metric/
    MeterRegistry — LongCounter, DoubleGauge, LongHistogram), surfaced
    through the APM module. Here the registry is in-process; its snapshot
    feeds the _nodes/stats metrics section and `prometheus_text()` is the
    `GET /_prometheus/metrics` exposition body.

    Thread-safe: concurrent aiohttp handlers, the engine worker, and the
    transport dispatch/search threads all record into one registry — every
    read-modify-write holds the registry lock (PR 4; the previous plain
    dict updates raced and lost counts)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, object] = {}  # name -> callable or value
        self._histograms: dict[str, _Histogram] = {}

    # -- instruments -------------------------------------------------------

    def counter_inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    def counters_add(self, pairs) -> None:
        """Integer counters by twos, ((name_a, name_b), (a, b)), under one
        acquisition of the lock (a search's stages: nanoseconds and count)."""
        with self._lock:
            c = self._counters
            for (name_a, name_b), (a, b) in pairs:
                c[name_a] = c.get(name_a, 0) + a
                c[name_b] = c.get(name_b, 0) + b

    def gauge_set(self, name: str, value) -> None:
        """value: a number, or a zero-arg callable sampled at snapshot."""
        with self._lock:
            self._gauges[name] = value

    def histogram_record(self, name: str, value: float) -> None:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = _Histogram()
            h.record(value)

    def reset(self) -> None:
        """Drop every instrument (test hygiene: wired into the suite's
        module-boundary cleanup so one module's recordings can never leak
        into another's assertions)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()

    # -- export ------------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            gauges_raw = dict(self._gauges)
            hists = {name: h.snapshot()
                     for name, h in self._histograms.items()}
        gauges = {}
        for name, v in gauges_raw.items():
            try:
                gauges[name] = v() if callable(v) else v
            except Exception:  # a failing gauge must not break stats
                gauges[name] = None
        return {"counters": counters, "gauges": gauges, "histograms": hists}

    # exposition help text for well-known instruments; anything else gets
    # a generated line (prometheus_client requires HELP/TYPE per family,
    # and scrapers surface these strings in their metric explorers)
    HELP_TEXTS = {
        "es.rest.request.ms": "REST request wall time",
        "es.shard.search.ms": "per-shard query phase wall time",
        "es.health.status": "node health: 0=green 1=yellow 2=red",
        "es.slo.compliant": "1 when every SLO objective holds, else 0",
        "es.slo.breached": "number of breached SLO objectives",
        "es.slo.objectives": "number of evaluated SLO objectives",
        "es.watcher.executions": "watch executions (scheduled + manual)",
        "es.serving.queue_depth": "serving admission queue depth",
        "es.indexing.tail_fraction":
            "fraction of visible docs served by the exact-scan tail tier",
        "es.indexing.refresh_lag_ms":
            "ms the oldest unrefreshed write has waited for visibility",
        "es.indexing.docs_per_s_ema":
            "refresh-over-refresh ingest rate (EMA)",
    }

    def prometheus_text(self, extra_gauges: dict | None = None,
                        labeled: dict | None = None) -> str:
        """Prometheus text exposition (format 0.0.4): counters as
        `_total`, gauges, histograms as cumulative `_bucket{le=...}` +
        `_sum`/`_count` with the exponential bucket upper bounds; every
        metric family is preceded by its `# HELP` and `# TYPE` lines.
        `extra_gauges`: point-in-time values rendered as gauges (breaker /
        cache state sampled by the endpoint). `labeled`: multi-sample
        families rendered with label sets (PR 12 — host-transition
        counters by kind, cost-model drift gauges by kernel):
        {family_name: {"kind": "counter"|"gauge", "help": str,
        "samples": [(labels_dict, value), ...]}}."""
        import re as _re

        def san(name: str) -> str:
            n = _re.sub(r"[^a-zA-Z0-9_:]", "_", name)
            return ("_" + n) if n[:1].isdigit() else n

        def num(v) -> str:
            f = float(v)
            if f == int(f) and abs(f) < 1e15:
                return str(int(f))
            return repr(f)

        def head(lines, raw_name, metric, kind):
            help_text = self.HELP_TEXTS.get(
                raw_name, f"{raw_name} ({kind})").replace("\n", " ")
            lines.append(f"# HELP {metric} {help_text}")
            lines.append(f"# TYPE {metric} {kind}")

        with self._lock:
            counters = dict(self._counters)
            gauges_raw = dict(self._gauges)
            hist_data = {
                name: (h.count, h.sum, h.zero_count, dict(h.buckets))
                for name, h in self._histograms.items()
            }
        lines: list[str] = []
        for name in sorted(counters):
            m = san(name)
            if not m.endswith("_total"):  # prometheus counter convention
                m += "_total"
            head(lines, name, m, "counter")
            lines.append(f"{m} {num(counters[name])}")
        gauges = {}
        for name, v in gauges_raw.items():
            try:
                gauges[name] = v() if callable(v) else v
            except Exception:  # noqa: BLE001 - skip broken gauges
                continue
        for name, v in (extra_gauges or {}).items():
            gauges[name] = v
        for name in sorted(gauges):
            v = gauges[name]
            if isinstance(v, bool):
                v = int(v)
            if not isinstance(v, (int, float)):
                continue
            m = san(name)
            head(lines, name, m, "gauge")
            lines.append(f"{m} {num(v)}")
        for name in sorted(labeled or {}):
            fam = labeled[name]
            m = san(name)
            kind = fam.get("kind", "gauge")
            lines.append(f"# HELP {m} "
                         f"{(fam.get('help') or f'{name} ({kind})')}")
            lines.append(f"# TYPE {m} {kind}")
            for labels, v in fam.get("samples", ()):
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    v = int(v) if isinstance(v, bool) else None
                if v is None:
                    continue
                lab = ",".join(f'{san(k)}="{val}"'
                               for k, val in sorted(labels.items()))
                lines.append(f"{m}{{{lab}}} {num(v)}")
        for name in sorted(hist_data):
            count, total, zero_count, buckets = hist_data[name]
            m = san(name)
            head(lines, name, m, "histogram")
            cum = 0
            if zero_count:
                cum += zero_count
                lines.append(f'{m}_bucket{{le="0"}} {cum}')
            for i in sorted(buckets):
                cum += buckets[i]
                lines.append(
                    f'{m}_bucket{{le="{_bucket_upper(i):.6g}"}} {cum}')
            lines.append(f'{m}_bucket{{le="+Inf"}} {count}')
            lines.append(f"{m}_sum {num(total)}")
            lines.append(f"{m}_count {count}")
        return "\n".join(lines) + "\n"


metrics = MetricsRegistry()


# ---------------------------------------------------------------------------
# hot threads (reference: monitor/jvm/HotThreads.java)
# ---------------------------------------------------------------------------

_IDLE_FRAME_NAMES = frozenset({
    "wait", "_wait", "acquire", "select", "poll", "epoll", "get",
    "recv", "recv_into", "accept", "readinto", "read", "_read_exact",
    "run_forever", "_run_once", "sleep", "dequeue", "_worker",
    "wait_for", "join", "channel_get",
})


def hot_threads_report(threads: int = 3, snapshots: int = 10,
                       interval_s: float = 0.03) -> str:
    """Sample every Python thread's stack `snapshots` times over a short
    window and report the busiest first (busy = samples whose innermost
    frame is not a recognizable wait). Diagnoses a stuck event loop vs a
    device wait without attaching a debugger — the hot_threads analog;
    true per-thread CPU time needs OS support the reference gets from the
    JVM, so sampling stands in for it (documented divergence)."""
    import sys
    import traceback

    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    busy: dict[int, int] = {}
    last_stack: dict[int, list] = {}
    for i in range(max(snapshots, 1)):
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            stack = traceback.extract_stack(frame)
            last_stack[ident] = stack
            top = stack[-1].name if stack else ""
            is_idle = top in _IDLE_FRAME_NAMES or top.startswith("_wait")
            busy[ident] = busy.get(ident, 0) + (0 if is_idle else 1)
        if i + 1 < snapshots:
            time.sleep(interval_s)
    order = sorted(busy, key=lambda t: (-busy[t], names.get(t, "")))
    n = max(snapshots, 1)
    out = [f"::: {{{current_node_name()}}}",
           f"   Hot threads sampled {n} times over "
           f"{(n - 1) * interval_s * 1000:.0f}ms, "
           f"busiestThreads={threads}:", ""]
    for ident in order[:max(threads, 1)]:
        pct = 100.0 * busy[ident] / n
        out.append(f"   {pct:5.1f}% busy samples — thread "
                   f"'{names.get(ident, ident)}'")
        for fr in (last_stack.get(ident) or [])[-12:]:
            out.append(f"       at {fr.name} ({fr.filename}:{fr.lineno})")
        out.append("")
    return "\n".join(out) + "\n"


# ---- shard request cache ---------------------------------------------------

# span name used around cache-served results (the reference traces the
# query phase regardless of cache outcome; a hit span makes the skipped
# execution visible in traces instead of looking like a 0ms search)
CACHE_HIT_SPAN = "shardRequestCache.hit"


def record_cache_event(event: str, n: int = 1) -> None:
    """Count a request-cache event (hit/miss/put/eviction) in the metrics
    registry so _nodes/stats metrics carry cache counters alongside the
    cache's own stats() (cache/request_cache.py)."""
    metrics.counter_inc(f"request_cache.{event}", n)


# ---- machine learning ------------------------------------------------------

def record_ml_event(event: str, n: int = 1) -> None:
    """Count an ML lifecycle/processing event (jobs_opened,
    buckets_processed, records_written, model_snapshots_written, ...) so
    _nodes/stats metrics expose the ML workload alongside the ml section
    (the reference meters these through its MlStatsIndex + usage API)."""
    metrics.counter_inc(f"ml.{event}", n)


# ---------------------------------------------------------------------------
# structured (JSON-lines) logging
# ---------------------------------------------------------------------------

def enable_json_logging(stream=None) -> None:
    """Switch the root logger to ECS-shaped JSON lines (the reference logs
    ECS JSON via ecs-logging, config/log4j2.properties)."""
    import json as _json
    import logging
    import time as _time

    class _JsonFormatter(logging.Formatter):
        def format(self, record):
            doc = {
                "@timestamp": _time.strftime(
                    "%Y-%m-%dT%H:%M:%S", _time.gmtime(record.created))
                + f".{int(record.msecs):03d}Z",
                "log.level": record.levelname,
                "log.logger": record.name,
                "message": record.getMessage(),
                "ecs.version": "1.2.0",
            }
            if record.exc_info:
                doc["error.stack_trace"] = self.formatException(record.exc_info)
            return _json.dumps(doc)

    import sys as _sys

    h = logging.StreamHandler(stream or _sys.stdout)
    h.setFormatter(_JsonFormatter())
    root = logging.getLogger()
    root.handlers = [h]
    root.setLevel(logging.INFO)
