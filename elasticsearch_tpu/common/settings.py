"""Typed, scoped, dynamically-updatable settings.

Reference behavior: common/settings/Setting.java:80 (typed parsers,
Dynamic/Final properties, validators), common/settings/ClusterSettings.java:139
(registry of cluster-scoped settings; update consumers invoked on applied
changes; persistent vs transient), common/settings/IndexScopedSettings.java
(per-index registry; non-dynamic settings rejected on a live index).
"""

from __future__ import annotations

import json
import os
import re
from typing import Callable

from ..utils.errors import IllegalArgumentError

_SIZE_RE = re.compile(r"^(\d+(?:\.\d+)?)\s*(b|kb|mb|gb|tb|pb|%)?$", re.I)
_SIZE_MULT = {"b": 1, "kb": 1 << 10, "mb": 1 << 20, "gb": 1 << 30,
              "tb": 1 << 40, "pb": 1 << 50}


def parse_bytes(v, total_for_percent: int | None = None) -> int:
    """'512mb', '85%', 1024 -> bytes (reference: ByteSizeValue +
    MemorySizeValue percentage parsing)."""
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return int(v)
    m = _SIZE_RE.match(str(v).strip())
    if not m:
        raise IllegalArgumentError(f"failed to parse byte size [{v}]")
    num, unit = float(m.group(1)), (m.group(2) or "b").lower()
    if unit == "%":
        if total_for_percent is None:
            raise IllegalArgumentError(f"percentage not allowed here [{v}]")
        return int(total_for_percent * num / 100.0)
    return int(num * _SIZE_MULT[unit])


class Setting:
    """One typed setting: key, default, parser, dynamic flag, validator."""

    def __init__(self, key: str, default, parser: Callable = str, *,
                 dynamic: bool = False, validator: Callable | None = None):
        self.key = key
        self.default = default
        self.parser = parser
        self.dynamic = dynamic
        self.validator = validator

    def parse(self, raw):
        try:
            v = self.parser(raw)
        except IllegalArgumentError:
            raise
        except Exception as ex:
            raise IllegalArgumentError(
                f"failed to parse value [{raw}] for setting [{self.key}]: {ex}"
            )
        if self.validator is not None:
            self.validator(v)
        return v

    # common parsers
    @staticmethod
    def int_(raw):
        return int(raw)

    @staticmethod
    def float_(raw):
        return float(raw)

    @staticmethod
    def bool_(raw):
        if isinstance(raw, bool):
            return raw
        if str(raw).lower() in ("true", "1"):
            return True
        if str(raw).lower() in ("false", "0"):
            return False
        raise IllegalArgumentError(f"cannot parse boolean [{raw}]")

    @staticmethod
    def positive_int(raw):
        v = int(raw)
        if v < 0:
            raise IllegalArgumentError(f"must be >= 0, got [{raw}]")
        return v

    @staticmethod
    def at_least_one(raw):
        v = int(raw)
        if v < 1:
            raise IllegalArgumentError(f"must be >= 1, got [{raw}]")
        return v


class ClusterSettings:
    """Registry + live values + update consumers + persistence.

    `update({persistent: {...}, transient: {...}})` validates every key
    against the registry first, then applies and notifies consumers — one
    bad key rejects the whole request (the reference applies settings as a
    single cluster-state update)."""

    def __init__(self, registry: list[Setting], data_path: str | None = None):
        self.registry = {s.key: s for s in registry}
        self.persistent: dict = {}
        self.transient: dict = {}
        self._consumers: dict[str, list[Callable]] = {}
        self.data_path = data_path
        self._load()

    def _file(self):
        return (os.path.join(self.data_path, "cluster_settings.json")
                if self.data_path else None)

    def _load(self):
        f = self._file()
        if f and os.path.exists(f):
            with open(f, encoding="utf-8") as fh:
                state = json.load(fh)
            self.persistent = state.get("persistent", {})
            # transient settings do not survive restart (reference semantics)

    def _save(self):
        f = self._file()
        if not f:
            return
        tmp = f + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"persistent": self.persistent}, fh)
        os.replace(tmp, f)

    def _lookup(self, key: str) -> Setting:
        s = self.registry.get(key)
        if s is None:
            # group/wildcard settings: logger.* is dynamic free-form
            for pat, setting in self.registry.items():
                if pat.endswith(".*") and key.startswith(pat[:-1]):
                    return setting
            raise IllegalArgumentError(
                f"transient setting [{key}], not recognized"
            )
        return s

    def get(self, key: str):
        if key in self.transient:
            return self._lookup(key).parse(self.transient[key])
        if key in self.persistent:
            return self._lookup(key).parse(self.persistent[key])
        s = self.registry.get(key)
        if s is None:
            raise IllegalArgumentError(f"setting [{key}] not recognized")
        return s.default

    def add_consumer(self, key: str, fn: Callable):
        self._consumers.setdefault(key, []).append(fn)

    def update(self, body: dict) -> dict:
        changes = []
        for scope in ("persistent", "transient"):
            for key, raw in (body.get(scope) or {}).items():
                s = self._lookup(key)
                if raw is not None:
                    if not s.dynamic:
                        raise IllegalArgumentError(
                            f"final cluster setting [{key}], not updateable"
                        )
                    s.parse(raw)  # validate before applying anything
                changes.append((scope, key, raw))
        for scope, key, raw in changes:
            store = self.persistent if scope == "persistent" else self.transient
            if raw is None:
                store.pop(key, None)
            else:
                store[key] = raw
            for fn in self._consumers.get(key, []):
                fn(self.get(key) if raw is not None else self._lookup(key).default)
        self._save()
        return {
            "acknowledged": True,
            "persistent": dict(self.persistent),
            "transient": dict(self.transient),
        }


def _validate_duration(v):
    from ..utils.durations import parse_duration_seconds

    parse_duration_seconds(v, None)  # raises IllegalArgumentError when bad


def default_cluster_settings() -> list[Setting]:
    return [
        Setting("cluster.name", "elasticsearch-tpu"),
        Setting("indices.breaker.total.limit", "95%", str, dynamic=True),
        Setting("indices.breaker.fielddata.limit", "40%", str, dynamic=True),
        Setting("indices.breaker.request.limit", "60%", str, dynamic=True),
        # shard request cache (cache/request_cache.py; reference:
        # IndicesRequestCache INDICES_CACHE_QUERY_SIZE / index-level enable)
        Setting("indices.requests.cache.enable", True, Setting.bool_,
                dynamic=True),
        Setting("indices.requests.cache.size", "64mb", str, dynamic=True),
        Setting("search.default_search_timeout", "-1", str, dynamic=True),
        # honest partial results (PR 14, reference:
        # SearchService.DEFAULT_ALLOW_PARTIAL_SEARCH_RESULTS): the
        # cluster default a request's body/param can override; false
        # turns ANY shard failure into a 503 instead of partial results
        Setting("search.default_allow_partial_results", True,
                Setting.bool_, dynamic=True),
        Setting("search.max_buckets", 65536, Setting.positive_int, dynamic=True),
        Setting("action.auto_create_index", True, Setting.bool_, dynamic=True),
        Setting("cluster.max_shards_per_node", 1000, Setting.positive_int, dynamic=True),
        Setting("logger.*", "info", str, dynamic=True),
        Setting("xpack.security.enabled", False, Setting.bool_, dynamic=True),
        # machine learning (ml/): job admission + model-state placement.
        # model_inference is the breaker child accounting live model state
        # (the reference's ML memory tracker + model_inference breaker)
        Setting("xpack.ml.enabled", True, Setting.bool_, dynamic=True),
        Setting("xpack.ml.max_open_jobs", 32, Setting.positive_int,
                dynamic=True),
        Setting("xpack.ml.state_repository_path", None, lambda v: v,
                dynamic=True),
        Setting("indices.breaker.model_inference.limit", "50%", str,
                dynamic=True),
        # PR 20: transient ESQL whole-column materializations
        # (esql/profile.py charges each pipe stage's live table bytes;
        # trip -> 429 naming the dominant operator, never a node OOM)
        Setting("indices.breaker.esql.materialization.limit", "40%", str,
                dynamic=True),
        # remote clusters for CCS; the seed is the remote's HTTP endpoint
        # (this framework's transport IS HTTP — reference 9300 seeds analog)
        Setting("cluster.remote.*", None, lambda v: v, dynamic=True),
        # self-monitoring pipeline (monitoring/): interval collectors
        # writing .monitoring-es-* TSDB indices on the node's own engine
        # (the reference's xpack.monitoring.collection.* settings)
        Setting("xpack.monitoring.collection.enabled", False, Setting.bool_,
                dynamic=True),
        Setting("xpack.monitoring.collection.interval", "10s", str,
                dynamic=True, validator=_validate_duration),
        Setting("xpack.monitoring.history.duration", "7d", str,
                dynamic=True, validator=_validate_duration),
        # scheduled alerting (xpack/watcher.py): watches fire on their
        # own triggers via the persistent-task ticker; tick.interval is
        # the scheduler granularity (the reference's TickerSchedule
        # TICKER_INTERVAL_SETTING), not a watch's own schedule
        Setting("xpack.watcher.enabled", True, Setting.bool_, dynamic=True),
        Setting("xpack.watcher.tick.interval", "1s", str, dynamic=True,
                validator=_validate_duration),
        # SLO engine (monitoring/slo.py): declarative objectives over the
        # node's own measured signals, evaluated on the monitoring
        # collector interval; 0 / "" disables an objective family.
        # kernel.floors / custom are JSON documents so operators can
        # register objectives without a code change (see slo.py docstring)
        Setting("slo.enabled", True, Setting.bool_, dynamic=True),
        Setting("slo.search.p99_ms", 60000.0, Setting.float_, dynamic=True),
        Setting("slo.shard.p99_ms", 0.0, Setting.float_, dynamic=True),
        Setting("slo.kernel.floors", "", str, dynamic=True),
        Setting("slo.kernel.min_calls", 3, Setting.positive_int,
                dynamic=True),
        Setting("slo.serving.queue_fraction", 0.95, Setting.float_,
                dynamic=True),
        Setting("slo.serving.shed_rate", 0.2, Setting.float_, dynamic=True),
        Setting("slo.breaker.trip_budget", 1000.0, Setting.float_,
                dynamic=True),
        Setting("slo.hbm.headroom_fraction", 0.98, Setting.float_,
                dynamic=True),
        # write-path SLO floors (PR 13): bound the exact-scan tail-tier
        # doc fraction and the visibility lag of unrefreshed writes —
        # the standing invariants ROADMAP item 2's mixed read/write C7
        # bench arm is graded against. 0 disables (the default: floors
        # are set from measured baselines, not guessed)
        Setting("slo.write.tail_fraction", 0.0, Setting.float_,
                dynamic=True),
        Setting("slo.write.refresh_lag_ms", 0.0, Setting.float_,
                dynamic=True),
        # PR 16: bound the share of cumulative build-stage time spent in
        # text analysis (build.analyze + host `analyze`) — the
        # vectorized-ingest invariant; 0 disables like the other floors
        Setting("slo.write.analyze_fraction", 0.0, Setting.float_,
                dynamic=True),
        # PR 18: ceiling on the execution planner's worst per-kernel
        # |predicted-vs-actual| residual EMA — a drifting cost model is
        # an SLO breach, not a silent misrouter. 0 disables.
        Setting("slo.planner.residual", 0.0, Setting.float_, dynamic=True),
        # PR 19: per-tenant budget objectives over the metering ledger —
        # device-time burn (ms of device wall per wall-clock second),
        # per-tenant queue-wait p99, per-tenant shed rate. Breaches name
        # the worst tenant. 0 disables (budgets come from measured
        # baselines, like the write floors).
        Setting("slo.tenant.device_ms_per_s", 0.0, Setting.float_,
                dynamic=True),
        Setting("slo.tenant.queue_p99_ms", 0.0, Setting.float_,
                dynamic=True),
        Setting("slo.tenant.shed_rate", 0.0, Setting.float_, dynamic=True),
        # PR 20: ESQL dataflow objectives over the per-operator profile
        # substrate (esql/profile.py) — query p99 and the peak live
        # materialized-bytes high-water the item-5 paged port must
        # drive below one materialization budget. Breaches name the
        # dominant operator. 0 disables.
        Setting("slo.esql.p99_ms", 0.0, Setting.float_, dynamic=True),
        Setting("slo.esql.peak_bytes", 0.0, Setting.float_, dynamic=True),
        Setting("slo.custom", "", str, dynamic=True),
        # adaptive execution planner (PR 18, planner/): cost-model-driven
        # arm selection — predicted wall = analytic cost / measured
        # achieved-roofline EMA, argmin wins; cold EMAs fall back to the
        # static priority routing byte-for-byte. knn.target_ms > 0 lets
        # the planner RAISE nprobe to the largest value meeting the
        # latency target; cache.min_recompute_us > 0 rejects request-
        # cache entries cheaper to recompute than the floor.
        Setting("planner.enabled", True, Setting.bool_, dynamic=True),
        Setting("planner.ema.alpha", 0.2, Setting.float_, dynamic=True),
        Setting("planner.knn.target_ms", 0.0, Setting.float_, dynamic=True),
        Setting("planner.cache.min_recompute_us", 0.0, Setting.float_,
                dynamic=True),
        # PR 19: budget-fed fair scheduling — derive the serving
        # weighted-RR tenant weights from slo.tenant.device_ms_per_s
        # budget burn. Advisory and clamped: an over-budget tenant's
        # weight scales by budget/burn down to min_factor (slowed,
        # never starved); OFF (the default, the kill switch) leaves the
        # static serving.tenant.weights table byte-identical.
        Setting("planner.tenant.fairshare", False, Setting.bool_,
                dynamic=True),
        Setting("planner.tenant.fairshare.min_factor", 0.25,
                Setting.float_, dynamic=True),
        # PR 19: the tenant metering ledger's row budget — rows beyond
        # the top-K fold into `_other` (the Prometheus label-cardinality
        # bound, enforced by lint)
        Setting("metering.tenant.top_k", 16, Setting.positive_int,
                dynamic=True),
        # continuous-batching serving front end (serving/): admission,
        # coalescing into device waves, deadline/fairness scheduling,
        # backpressure. queue.max_depth is the analog of the reference's
        # search thread-pool queue_size (overflow -> 429), max_wait the
        # coalescing window a lone request may be held for at most.
        Setting("serving.enabled", False, Setting.bool_, dynamic=True),
        Setting("serving.max_wave", 256, Setting.positive_int, dynamic=True),
        Setting("serving.coalesce.max_wait", "2ms", str, dynamic=True,
                validator=_validate_duration),
        Setting("serving.queue.max_depth", 1000, Setting.positive_int,
                dynamic=True),
        # the smallest batch tier of the wave programs' ladder
        # (ops/batched.wave_q_tier: powers of two from here): a wave, or a
        # fused wave's escalation, of fewer queries is padded with empty
        # ones up to it. Raising it trades padded rows for fewer programs.
        Setting("serving.wave.min_tier", 1, Setting.at_least_one,
                dynamic=True),
        # the smallest rows tier of the solo path's `match` programs
        # (query/nodes.match_tiers: powers of two from here): a match's
        # sparse posting-block rows are padded up to it. Raising it trades
        # padded rows for fewer programs, as serving.wave.min_tier does
        Setting("search.solo.min_rows_tier", 8, Setting.at_least_one,
                dynamic=True),
        # per-tenant weighted fair scheduling: "tenantA:4,tenantB:1"
        # (X-Opaque-Id is the tenant identity; unlisted tenants weigh 1)
        Setting("serving.tenant.weights", "", str, dynamic=True),
        # background DEVICE index merges as the internal `_merge` tenant
        # (PR 15): the weighted-RR budget a tail-segment fold takes per
        # wave visit — low so search waves dominate, never zero-starved
        # (the RR visits every non-empty tenant)
        Setting("serving.merge.weight", 1.0, Setting.float_, dynamic=True),
        # tenant superpacks (tenancy/, PR 17): many small tenant indices
        # in one shared size-class device layout served by one compiled
        # tenant-gather program family. ES_TPU_SUPERPACK=1/0 overrides
        # the setting (the tier-1 shuffled-gate switch). max_docs bounds
        # membership: a tenant past it serves per-index (its own pack
        # amortizes; superpacks exist for the many-small-indices shape)
        Setting("superpack.enabled", False, Setting.bool_, dynamic=True),
        Setting("superpack.max_docs", 8192, Setting.positive_int,
                dynamic=True),
        # LSM tail-segment bound (PR 15): an incremental refresh packs
        # its new docs as one sealed segment; beyond this many segments
        # a background fold merges them (the Lucene merge-policy analog)
        Setting("indexing.tiers.max_segments", 4, Setting.positive_int,
                dynamic=True),
        # shards a refresh builds at once (analysis + pack build of each,
        # on its own thread, its device stages on the shard's own device;
        # parallel/stacked.py). Unset: one builder a shard, as far as the
        # host has cores. It bounds the host memory a refresh holds
        Setting("indexing.refresh.shard_builders", None, Setting.at_least_one,
                dynamic=True),
        # serving-wave flight recorder (PR 12): bounded ring of per-wave
        # segment timings / tenant mix / kernel deltas, dumped to the
        # hidden .flight-recorder-* index by the watcher capture action
        Setting("serving.flight_recorder.size", 256, Setting.positive_int,
                dynamic=True),
        # write-path RefreshProfile ring (PR 13): per-refresh stage
        # timings at GET /_refresh/profile, the refresh-side twin of the
        # serving flight recorder
        Setting("indexing.profile.size", 256, Setting.positive_int,
                dynamic=True),
        # breach-triggered device profiling (monitoring/profiler.py):
        # duration-bounded jax.profiler traces; trace dirs pruned on the
        # retention window by the monitoring CleanerService
        Setting("xpack.profiling.enabled", True, Setting.bool_,
                dynamic=True),
        Setting("xpack.profiling.trace_dir", "", str, dynamic=True),
        Setting("xpack.profiling.max_duration", "10s", str, dynamic=True,
                validator=_validate_duration),
        Setting("xpack.profiling.retention", "1h", str, dynamic=True,
                validator=_validate_duration),
    ]


# ---- index-scoped --------------------------------------------------------

INDEX_SETTINGS: dict[str, Setting] = {s.key: s for s in [
    Setting("number_of_shards", 1, Setting.int_, dynamic=False,
            validator=lambda v: None if v >= 1 else (_ for _ in ()).throw(
                IllegalArgumentError("number_of_shards must be >= 1"))),
    Setting("number_of_replicas", 0, Setting.positive_int, dynamic=True),
    Setting("refresh_interval", "1s", str, dynamic=True),
    Setting("default_pipeline", None, str, dynamic=True),
    Setting("final_pipeline", None, str, dynamic=True),
    Setting("max_result_window", 10000, Setting.positive_int, dynamic=True),
    Setting("hidden", False, Setting.bool_, dynamic=True),
    Setting("blocks.read_only", False, Setting.bool_, dynamic=True),
    Setting("blocks.write", False, Setting.bool_, dynamic=True),
    # ANN probe width for knn over IVF-indexed dense_vector fields
    # (ann/): 0 = auto (probes sized to cover ~num_candidates vectors);
    # dynamic — recall/latency is tunable on a live index, no rebuild
    Setting("knn.nprobe", 0, Setting.int_, dynamic=True,
            validator=lambda v: None if v >= 0 else (_ for _ in ()).throw(
                IllegalArgumentError("knn.nprobe must be >= 0"))),
    # per-index slowlog thresholds, dynamic + typed (reference behavior:
    # SearchSlowLog INDEX_SEARCH_SLOWLOG_THRESHOLD_*_SETTING — durations,
    # "-1" disables a level). telemetry.record_search_slowlog reads these
    # from EACH index's settings, so two indices can run different levels
    *[
        Setting(f"search.slowlog.threshold.query.{lvl}", None, str,
                dynamic=True, validator=_validate_duration)
        for lvl in ("warn", "info", "debug", "trace")
    ],
    *[
        Setting(f"search.slowlog.threshold.fetch.{lvl}", None, str,
                dynamic=True, validator=_validate_duration)
        for lvl in ("warn", "info", "debug", "trace")
    ],
    *[
        Setting(f"indexing.slowlog.threshold.index.{lvl}", None, str,
                dynamic=True, validator=_validate_duration)
        for lvl in ("warn", "info", "debug", "trace")
    ],
]}


class IndexScopedSettings:
    """Validates index settings at create and on dynamic update."""

    @staticmethod
    def normalize(key: str) -> str:
        return key.removeprefix("index.")

    # setting groups that arrive as nested objects in REST bodies but are
    # registered (and read) as dotted keys — flattened before validation,
    # so `{"search": {"slowlog": {"threshold": {"query": {"warn": ...}}}}}`
    # and `"search.slowlog.threshold.query.warn"` are the same update
    _FLATTEN_GROUPS = ("search", "indexing", "knn")

    @classmethod
    def _flatten_groups(cls, updates: dict) -> dict:
        out = {}

        def walk(prefix: str, val):
            if isinstance(val, dict) and val:
                for k2, v2 in val.items():
                    walk(f"{prefix}.{k2}", v2)
            else:
                out[prefix] = val

        for key, raw in updates.items():
            nk = cls.normalize(key)
            if nk.split(".", 1)[0] in cls._FLATTEN_GROUPS \
                    and isinstance(raw, dict):
                walk(nk, raw)
            else:
                out[key] = raw
        return out

    @classmethod
    def validate_update(cls, current: dict, updates: dict) -> dict:
        """-> normalized updates; rejects non-dynamic keys on a live index
        (reference behavior: MetadataUpdateSettingsService — 'final ... ,
        not updateable on open indices')."""
        out = {}
        updates = cls._flatten_groups(updates)
        for key, raw in updates.items():
            nk = cls.normalize(key)
            s = INDEX_SETTINGS.get(nk)
            if s is None:
                # unknown settings are stored opaquely (plugins do this in
                # the reference via IndexScopedSettings groups)
                out[nk] = raw
                continue
            if not s.dynamic:
                raise IllegalArgumentError(
                    f"Can't update non dynamic settings [[index.{nk}]] for open indices"
                )
            out[nk] = s.parse(raw) if raw is not None else None
        return out
