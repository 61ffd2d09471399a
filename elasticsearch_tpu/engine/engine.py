"""Host-side engine: mutable document state around immutable device packs.

The reference's per-shard engine is versioned CRUD over a Lucene IndexWriter
with a translog WAL for durability between commits (reference behavior:
index/engine/InternalEngine.java:1135 index() -> versioning -> Lucene write
-> translog append :1223; index/translog/Translog.java; refresh makes writes
searchable). The TPU design keeps the same contract with a different split:

  - mutation lives entirely on host: an id -> (seq_no, version, source) map
    (the LiveVersionMap analog, so GETs are realtime) + an append-only
    JSON-lines WAL with fsync
  - `refresh()` rebuilds the immutable stacked pack from live docs and ships
    it to the mesh — the analog of reopening a Lucene searcher, except a
    "segment" here is the whole HBM pack (incremental tail packs are a later
    optimization; the contract — writes invisible until refresh — is the
    same)
  - restart recovery = WAL replay (the reference's translog recovery,
    RecoverySourceHandler.java:318 phase2 analog for the local case)

seq_nos are per index (the reference assigns per shard,
index/seqno/LocalCheckpointTracker.java — a documented simplification).
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from ..index.mappings import Mappings
from ..parallel.sharded import StackedSearcher, make_mesh
from ..utils.errors import (
    DocumentMissingError,
    IndexAlreadyExistsError,
    IndexNotFoundError,
    ResourceNotFoundError,
    VersionConflictError,
    IllegalArgumentError,
)

_AUTO_ID_ALPHABET = "0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"


def _auto_id() -> str:
    import secrets

    return "".join(secrets.choice(_AUTO_ID_ALPHABET) for _ in range(20))


# Marker prefix for ids the CLUSTER GATEWAY pre-assigned to id-less write
# ops (cluster/http.py _normalize_op draws ids before replication so every
# replica applies a byte-identical op). A time-series engine must IGNORE
# such an id and derive the deterministic (_tsid, @timestamp) id instead —
# a random id per point would make duplicate points accumulate on TSDB
# indices behind the gateway (round-5 review finding). User-supplied ids
# starting with this prefix are vanishingly unlikely (documented caveat).
GATEWAY_AUTO_ID_PREFIX = "gwa-"


class _StrKey:
    """Orderable wrapper so descending string sort keys compose with numeric
    keys in one tuple sort during the cross-index merge."""

    __slots__ = ("v", "desc")

    def __init__(self, v, desc):
        self.v, self.desc = v, desc

    def __lt__(self, other):
        return (self.v > other.v) if self.desc else (self.v < other.v)

    def __eq__(self, other):
        return self.v == other.v


@dataclass
class _DocEntry:
    source: dict
    version: int
    seq_no: int
    alive: bool


@dataclass
class _TailSegment:
    """One sealed LSM tail segment (PR 15): the docs of one incremental
    refresh packed and shipped as their own immutable searcher. Newer
    segments supersede older copies via live-bit flips (the same
    discipline the base tier uses), so refresh cost is proportional to
    the NEW docs only — the old (base, tail) model rebuilt the whole
    tail union every refresh. `stats` freezes the segment's field/df
    statistics at build; the combined scoring stats are the base stats
    plus every segment's (superseded copies keep counting until a merge
    folds them out — Lucene's segment-stats behavior, see DIVERGENCES
    "Device-side builds")."""

    searcher: object            # StackedSearcher
    shard_docs: list            # routed [(id, source)] per shard
    pos: dict                   # id -> (shard, docid) within this segment
    stats: tuple                # (field_stats, global_df) at build
    nbytes: int = 0


class EsIndex:
    def __init__(
        self,
        name: str,
        mappings: Mappings,
        settings: dict,
        data_dir: str | None,
        _recovering: bool = False,
        breaker_account=None,
    ):
        from ..common.settings import INDEX_SETTINGS, IndexScopedSettings

        self.name = name
        self.mappings = mappings
        self.engine = None  # owning Engine backref (query-time inference)
        self.settings = {"number_of_shards": 1, "number_of_replicas": 0, "refresh_interval": "1s"}
        # nested slowlog-group bodies flatten to the dotted keys the
        # telemetry threshold reader consumes (same normalization as
        # dynamic updates — IndexScopedSettings._FLATTEN_GROUPS)
        settings = IndexScopedSettings._flatten_groups(settings or {})
        for k, v in (settings or {}).items():
            s = INDEX_SETTINGS.get(k)
            if s is not None and v is not None:
                s.parse(v)  # typed validation at create (Setting.java parsers)
            self.settings[k] = v
        if self.settings.get("analysis"):
            from ..analysis.custom import build_analysis_registry

            mappings.set_analysis(build_analysis_registry(self.settings["analysis"]))
        self.num_shards = int(self.settings["number_of_shards"])
        if self.num_shards < 1:
            raise IllegalArgumentError("number_of_shards must be >= 1")
        # index.mode=time_series: validated at create; None for standard
        # indices (index/tsdb.py — dimension routing, _tsid, time bounds)
        from ..index.tsdb import time_series_mode

        self.ts_mode = time_series_mode(self.settings, self.mappings)
        self._breaker_account = breaker_account
        self.docs: dict[str, _DocEntry] = {}
        self.seq_no = 0
        self.primary_term = 1
        # seq-ordered (seq_no, doc_id) tail for the CCR changes feed: a
        # follower poll reads just the ops since its checkpoint instead of
        # scanning the whole doc table (the reference tails the translog
        # by seq-no range, LuceneChangesSnapshot). Compacted to the last
        # OP_LOG_RETAIN entries; older checkpoints fall back to a full scan.
        self._op_log: list[tuple[int, str]] = []
        self._op_log_min = 0
        self.data_dir = data_dir
        self._wal = None
        # inside `wal_sync_deferred` (one `_bulk` request) records are
        # written and the one sync waits for the request's last item
        self._wal_deferred = False
        self._wal_unsynced = False
        self._dirty = True
        # refresh lag (PR 13): monotonic stamp of the OLDEST write not yet
        # made visible by a refresh — the write-path analog of queue wait,
        # surfaced as the `indexing.refresh_lag_ms` gauge and bounded by
        # the slo.write.refresh_lag_ms objective
        self._dirty_since: float | None = None
        self._last_refresh = 0.0
        self._searcher: StackedSearcher | None = None
        # searchable-snapshot lazy hydration (snapshots/service.py
        # mount_snapshot): fetches the mounted snapshot's blobs through
        # the shared cache on first use; cleared before running so the
        # hydration's own refresh cannot recurse
        self._hydrate = None
        self.shard_docs: list[list[tuple[str, dict]]] = []
        # ---- LSM tiered refresh state (PR 15; Lucene-segment analog: a
        # sealed base pack + N sealed tail segments; deletes/updates flip
        # live bits in whichever tier holds the old copy; background
        # merges fold segments — SURVEY §7 hard part #3) -------------------
        self._tails: list[_TailSegment] = []
        self._tail_docs: dict[str, dict] = {}  # id -> source, not in base
        # id -> (segment ordinal, shard, docid): where the newest
        # out-of-base copy lives, so an update/delete flips exactly one
        # older segment's live bit (rebuilt on merge)
        self._tail_pos: dict[str, tuple[int, int, int]] = {}
        self._merge_inflight = False  # a background fold is queued/running
        self._base_pos: dict[str, tuple[int, int]] = {}  # id -> (shard, docid)
        self._base_stats: tuple[dict, dict] | None = None  # at base build
        self._base_nbytes = 0
        self._pending: set[str] = set()  # ids touched since last refresh
        # operation counters surfaced by _stats (reference behavior:
        # index/shard/ shard-level CommonStats)
        self.counters: dict[str, int] = {}
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            self._persist_meta()
            self._wal = open(os.path.join(data_dir, "translog.log"), "a", encoding="utf-8")
        if not _recovering:
            # a new index is immediately searchable (as empty) — writes stay
            # invisible until the next refresh, like a fresh Lucene reader
            self.refresh()

    # ---- durability ------------------------------------------------------

    def _route_docs(self, docs):
        """Doc->shard placement. Standard indices: murmur3 of the id.
        time_series mode: hash of the routing_path dimension values (every
        doc of one series lands on one shard) with each shard's docs in
        (_tsid, @timestamp) order — the timestamp-ordered pack layout the
        reference gets from its TSDB codec (index/codec/tsdb/), which
        keeps one series' points adjacent in the columnar device arrays."""
        from ..parallel.stacked import route_docs

        if self.ts_mode is None:
            return route_docs(docs, self.num_shards)
        from ..index.tsdb import _parse_ts

        routed = [[] for _ in range(self.num_shards)]
        for doc_id, src_ in docs:
            routed[self.ts_mode.shard_of(src_, self.num_shards)].append(
                (doc_id, src_))
        for lst in routed:
            # _parse_ts, NOT check_timestamp: bounds were enforced at
            # write time; re-checking here would let any bounds drift
            # make refresh (and thus the whole index) unbuildable
            lst.sort(key=lambda p: (self.ts_mode.tsid_of(p[1]),
                                    _parse_ts(p[1]["@timestamp"])))
        return routed

    def _persist_meta(self):
        if not self.data_dir:
            return
        with open(os.path.join(self.data_dir, "meta.json"), "w", encoding="utf-8") as f:
            json.dump({"mappings": self.mappings.to_dict(), "settings": self.settings}, f)

    def _wal_append(self, line: str):
        """One record (a JSON line) to the WAL, synced before the write is
        acknowledged: at once for a single-document write, once for all the
        records of a `_bulk` request (`wal_sync_deferred`)."""
        if self._wal is None:
            return
        self._wal.write(line)
        if self._wal_deferred:
            self._wal_unsynced = True
        else:
            self._wal_sync()

    def _wal_sync(self):
        from ..telemetry import metrics

        self._wal.flush()
        os.fsync(self._wal.fileno())
        self._wal_unsynced = False
        metrics.counter_inc("es.wal.syncs")

    @contextlib.contextmanager
    def wal_sync_deferred(self):
        """The records appended inside are synced once, on the way out and
        so before anything of the request is acknowledged: the reference's
        default `index.translog.durability: request` (Translog.java,
        Durability.REQUEST; TransportWriteAction syncs the translog once a
        bulk shard request). Nested use syncs at the outermost exit."""
        if self._wal_deferred:
            yield
            return
        self._wal_deferred = True
        try:
            yield
        finally:
            self._wal_deferred = False
            if self._wal_unsynced and self._wal is not None:
                self._wal_sync()

    def flush(self):
        """Commit: snapshot live state + truncate the WAL + purge tombstones
        (the analog of a Lucene commit followed by translog generation
        rollover, index/translog/Translog.java trimUnreferencedReaders)."""
        if not self.data_dir:
            # purely in-memory index: just drop tombstones
            self.docs = {i: e for i, e in self.docs.items() if e.alive}
            return
        snap_tmp = os.path.join(self.data_dir, "commit.json.tmp")
        snap = os.path.join(self.data_dir, "commit.json")
        with open(snap_tmp, "w", encoding="utf-8") as f:
            state = {
                "seq_no": self.seq_no,
                "docs": [
                    {"id": i, "source": e.source, "version": e.version, "seq_no": e.seq_no}
                    for i, e in self.docs.items()
                    if e.alive
                ],
            }
            json.dump(state, f, separators=(",", ":"))
            f.flush()
            os.fsync(f.fileno())
        os.replace(snap_tmp, snap)
        # tombstones are durably superseded by the commit; purge them
        self.docs = {i: e for i, e in self.docs.items() if e.alive}
        if self._wal is not None:
            self._wal.close()
        wal_path = os.path.join(self.data_dir, "translog.log")
        self._wal = open(wal_path, "w", encoding="utf-8")
        self._wal.flush()
        os.fsync(self._wal.fileno())

    def update_settings(self, updates: dict):
        """PUT /{index}/_settings: dynamic settings only (reference behavior:
        MetadataUpdateSettingsService — non-dynamic keys rejected on open
        indices)."""
        from ..common.settings import IndexScopedSettings

        norm = IndexScopedSettings.validate_update(self.settings, updates)
        raw_end = norm.get("time_series.end_time")
        raw_start = norm.get("time_series.start_time")
        if isinstance(norm.get("time_series"), dict):
            raw_end = norm["time_series"].get("end_time", raw_end)
            raw_start = norm["time_series"].get("start_time", raw_start)
        if self.ts_mode is not None and (raw_end is not None
                                         or raw_start is not None):
            # a TSDB index's end bound may only GROW (the reference's
            # TimeSeriesSettings — a shrinking bound would orphan
            # already-accepted points); a bound change may also never
            # exclude a point this index already accepted, or the next
            # refresh would be unbuildable
            from ..index.tsdb import _parse_ts

            new_end = (_parse_ts(raw_end) if raw_end is not None
                       else self.ts_mode.end_millis)
            new_start = (_parse_ts(raw_start) if raw_start is not None
                         else self.ts_mode.start_millis)
            if (raw_end is not None and self.ts_mode.end_millis is not None
                    and new_end < self.ts_mode.end_millis):
                raise IllegalArgumentError(
                    f"index.time_series.end_time must be larger than "
                    f"current value [{self.ts_mode.end_millis}]")
            for e in self.docs.values():
                if not e.alive:
                    continue
                ts = _parse_ts(e.source.get("@timestamp"))
                if ((new_start is not None and ts < new_start)
                        or (new_end is not None and ts >= new_end)):
                    raise IllegalArgumentError(
                        "cannot update [index.time_series] bounds: an "
                        "already-accepted document's @timestamp "
                        f"[{e.source.get('@timestamp')}] would fall "
                        "outside the new bounds")
            self.ts_mode.end_millis = new_end
            self.ts_mode.start_millis = new_start
        if (isinstance(norm.get("time_series"), dict)
                and isinstance(self.settings.get("time_series"), dict)):
            # partial time_series updates merge into the stored group
            # instead of replacing it (losing start_time)
            norm["time_series"] = {**self.settings["time_series"],
                                   **norm["time_series"]}
        for k, v in norm.items():
            if v is None:
                self.settings.pop(k, None)
            else:
                self.settings[k] = v
        self._persist_meta()
        return {"acknowledged": True}

    @classmethod
    def open(cls, name: str, data_dir: str, breaker_account=None) -> "EsIndex":
        """Recover an index from disk: commit snapshot + WAL replay."""
        with open(os.path.join(data_dir, "meta.json"), encoding="utf-8") as f:
            meta = json.load(f)
        idx = cls(name, Mappings(meta["mappings"]), meta["settings"], data_dir=None,
                  _recovering=True, breaker_account=breaker_account)
        idx.data_dir = data_dir
        snap_path = os.path.join(data_dir, "commit.json")
        if os.path.exists(snap_path):
            with open(snap_path, encoding="utf-8") as f:
                state = json.load(f)
            idx.seq_no = state["seq_no"]
            for d in state["docs"]:
                idx.mappings.parse_document(d["source"])
                idx.docs[d["id"]] = _DocEntry(d["source"], d["version"], d["seq_no"], True)
        wal_path = os.path.join(data_dir, "translog.log")
        if os.path.exists(wal_path):
            with open(wal_path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    rec = json.loads(line)
                    if rec["op"] == "index":
                        idx.mappings.parse_document(rec["source"])  # re-grow dynamic mappings
                        idx.docs[rec["id"]] = _DocEntry(
                            rec["source"], rec["version"], rec["seq_no"], True
                        )
                    elif rec["op"] == "delete":
                        e = idx.docs.get(rec["id"])
                        if e is not None:
                            e.alive = False
                            e.version = rec["version"]
                            e.seq_no = rec["seq_no"]
                    idx.seq_no = max(idx.seq_no, rec["seq_no"] + 1)
        idx._wal = open(wal_path, "a", encoding="utf-8")
        # the op-log tail does not survive restarts: mark everything below
        # the recovered seq-no as outside the tail so a CCR follower whose
        # checkpoint predates the restart falls back to the full scan
        # (returning [] here would read as "caught up" — silent data loss)
        idx._op_log_min = idx.seq_no
        # recovery refresh: replayed ops are searchable after restart, as
        # after the reference's translog recovery
        idx.refresh()
        return idx

    # ---- CRUD ------------------------------------------------------------

    def _check_writable(self):
        from ..utils.errors import ClusterBlockError, IndexClosedError

        if self.settings.get("closed"):
            raise IndexClosedError(f"closed index [{self.name}]")
        if self.settings.get("blocks.write") or self.settings.get("blocks.read_only"):
            raise ClusterBlockError(
                f"index [{self.name}] blocked by: [FORBIDDEN/8/index write (api)]"
            )

    def index_doc(self, doc_id: str | None, source: dict, op_type: str = "index",
                  if_seq_no: int | None = None, if_primary_term: int | None = None):
        _t_index0 = time.monotonic()
        self._check_writable()
        if self.ts_mode is not None:
            # time-series writes: @timestamp validated against the index's
            # time bounds; _id derives from (_tsid, @timestamp) so an
            # exact duplicate point OVERWRITES (version 2) instead of
            # duplicating (reference TsidExtractingIdFieldMapper)
            if doc_id is None or doc_id.startswith(GATEWAY_AUTO_ID_PREFIX):
                doc_id = self.ts_mode.doc_id_of(source)
                op_type = "index"
            else:
                # an explicit id must BE the derived id (the reference's
                # TsidExtractingIdFieldMapper): accepting arbitrary ids
                # would let the same point exist twice under two ids
                derived = self.ts_mode.doc_id_of(source)
                if doc_id != derived:
                    raise IllegalArgumentError(
                        f"_id must be unset or set to [{derived}] but "
                        f"was [{doc_id}]")
            # validate routing extraction NOW: a doc the router cannot
            # place must be rejected at write time, not blow up refresh
            self.ts_mode.shard_of(source, self.num_shards)
        elif doc_id is None:
            doc_id = _auto_id()
            op_type = "create"
        existing = self.docs.get(doc_id)
        if op_type == "create" and existing is not None and existing.alive:
            raise VersionConflictError(
                f"[{doc_id}]: version conflict, document already exists (current version [{existing.version}])"
            )
        if (if_seq_no is None) != (if_primary_term is None):
            raise IllegalArgumentError(
                "if_seq_no and if_primary_term must be provided together"
            )
        if if_seq_no is not None:
            cur = existing.seq_no if existing is not None else -1
            if cur != if_seq_no or if_primary_term != self.primary_term:
                raise VersionConflictError(
                    f"[{doc_id}]: version conflict, required seqNo [{if_seq_no}] and "
                    f"primary term [{if_primary_term}], current seqNo [{cur}] and "
                    f"term [{self.primary_term}]"
                )
        # validate + grow dynamic mappings before accepting; snapshot the
        # source through its WAL serialization so later caller mutation
        # cannot diverge memory state from the durable log
        n_fields = len(self.mappings.fields)
        self.mappings.parse_document(source)
        version = (existing.version + 1) if existing is not None else 1
        seq = self.seq_no
        self.seq_no += 1
        src_json = json.dumps(source, separators=(",", ":"))
        source = json.loads(src_json)
        self.docs[doc_id] = _DocEntry(source, version, seq, True)
        self._op_log_append(seq, doc_id)
        self._pending.add(doc_id)
        # the record `json.dumps` would give, around the source's own
        # serialization above
        self._wal_append(f'{{"op":"index","id":{json.dumps(doc_id)},'
                         f'"source":{src_json},"version":{version},'
                         f'"seq_no":{seq}}}\n')
        if len(self.mappings.fields) != n_fields:
            self._persist_meta()  # dynamic mappings grew
        self._dirty = True
        if self._dirty_since is None:
            self._dirty_since = time.monotonic()
        self.counters["index_total"] = self.counters.get("index_total", 0) + 1
        if any(k.startswith("indexing.slowlog") for k in self.settings):
            from ..telemetry import record_indexing_slowlog

            record_indexing_slowlog(
                self.name, self.settings,
                (time.monotonic() - _t_index0) * 1000, doc_id)
        created = existing is None or not existing.alive
        return {"_id": doc_id, "_version": version, "_seq_no": seq,
                "result": "created" if created else "updated"}

    def delete_doc(self, doc_id: str):
        self._check_writable()
        e = self.docs.get(doc_id)
        if e is None or not e.alive:
            raise DocumentMissingError(f"[{doc_id}]: document missing", index=self.name)
        e.alive = False
        e.version += 1
        e.seq_no = self.seq_no
        self.seq_no += 1
        self._op_log_append(e.seq_no, doc_id)
        self._pending.add(doc_id)
        self._wal_append(json.dumps(
            {"op": "delete", "id": doc_id, "version": e.version,
             "seq_no": e.seq_no}, separators=(",", ":")) + "\n")
        self._dirty = True
        if self._dirty_since is None:
            self._dirty_since = time.monotonic()
        self.counters["delete_total"] = self.counters.get("delete_total", 0) + 1
        return {"_id": doc_id, "_version": e.version, "_seq_no": e.seq_no, "result": "deleted"}

    OP_LOG_RETAIN = 100_000

    def _op_log_append(self, seq: int, doc_id: str) -> None:
        self._op_log.append((seq, doc_id))
        if len(self._op_log) > 2 * self.OP_LOG_RETAIN:
            del self._op_log[: -self.OP_LOG_RETAIN]
            self._op_log_min = self._op_log[0][0]

    def ops_since(self, from_seq_no: int, size: int) -> list[dict] | None:
        """Seq-ordered ops at/after from_seq_no; None when the tail no
        longer covers that checkpoint (caller falls back to a full scan).
        Superseded entries (the doc changed again later) are skipped — the
        newer op appears later in the feed, and replay is idempotent."""
        import bisect

        if from_seq_no < self._op_log_min:
            return None
        lo = bisect.bisect_left(self._op_log, (from_seq_no, ""))
        out = []
        for seq, doc_id in self._op_log[lo:]:
            e = self.docs.get(doc_id)
            if e is None or e.seq_no != seq:
                continue  # superseded
            if e.alive:
                out.append({"op": "index", "id": doc_id, "seq_no": seq,
                            "version": e.version, "source": e.source})
            else:
                out.append({"op": "delete", "id": doc_id, "seq_no": seq,
                            "version": e.version})
            if len(out) >= size:
                break
        return out

    def get_doc(self, doc_id: str):
        """Realtime get from the version map (reference behavior:
        action/get/TransportGetAction.java:55 realtime reads via
        LiveVersionMap/translog, no refresh needed)."""
        e = self.docs.get(doc_id)
        if e is None or not e.alive:
            return None
        return {"_id": doc_id, "_version": e.version, "_seq_no": e.seq_no, "_source": e.source}

    @property
    def live_count(self) -> int:
        return sum(1 for e in self.docs.values() if e.alive)

    # ---- refresh / search ------------------------------------------------

    @property
    def _tail(self):
        """Compat view of the LSM segment list: the newest tail segment's
        searcher (None = fully merged). Assigning None clears every
        segment (snapshot restore / PIT paths)."""
        return self._tails[-1].searcher if self._tails else None

    @_tail.setter
    def _tail(self, value):
        if value is not None:
            raise ValueError(
                "tail tiers are LSM segments now — append via "
                "_refresh_incremental, clear by assigning None")
        self._tails = []
        self._tail_pos = {}

    @property
    def _tail_shard_docs(self):
        """Per-shard (id, source) lists across every tail segment, in
        segment order — the read-side compat view (stats/tests); the
        tiered search paths index each segment's own lists instead."""
        if not self._tails:
            return []
        out = [[] for _ in range(self.num_shards)]
        for seg in self._tails:
            for s, lst in enumerate(seg.shard_docs):
                out[s].extend(lst)
        return out

    @_tail_shard_docs.setter
    def _tail_shard_docs(self, value):
        if value:
            raise ValueError("assign tail segments via _tails")

    def tier_searchers(self) -> list:
        """Every live tier searcher, base first — the iteration target
        for memory accounting / cache invalidation."""
        out = [] if self._searcher is None else [self._searcher]
        out.extend(seg.searcher for seg in self._tails)
        return out

    @property
    def searcher(self) -> StackedSearcher | None:
        """The single merged searcher. Consumers that are not tier-aware
        (aggs, collapse, ESQL, suggest, …) read this; when a tail tier
        exists it is merged into a fresh base first — the analog of a
        force-merge ahead of an operation the tiered form can't serve."""
        if self._hydrate is not None:
            h, self._hydrate = self._hydrate, None
            h()
        if self._tail is not None:
            self._merge_tiers()
        return self._searcher

    @searcher.setter
    def searcher(self, value):
        self._searcher = value

    def refresh(self, mesh=None):
        from ..common import faults
        from ..monitoring.refresh_profile import profile_refresh

        faults.check("refresh.build", index=self.name)
        if self._hydrate is not None:
            h, self._hydrate = self._hydrate, None
            h()
        if self._searcher is not None and not self._pending and not self._dirty:
            return  # nothing written since the last refresh
        if self._can_refresh_incremental():
            with profile_refresh(self, "incremental"):
                self._refresh_incremental()
        else:
            with profile_refresh(self, "full"):
                self._refresh_full(mesh)
        self._dirty = False
        self._dirty_since = None
        self._last_refresh = time.monotonic()
        self.counters["refresh_total"] = self.counters.get("refresh_total", 0) + 1

    def _invalidate_request_cache(self):
        """Drop every shard-request-cache entry of the searchers about to
        be replaced (refresh/merge): the new searcher gets a fresh token,
        so the old entries are unreachable — this returns their memory to
        the breaker instead of waiting for LRU churn. Called only AFTER
        the replacement pack passed breaker admission: on a trip the old
        searcher stays live and its entries stay valid."""
        from ..cache import request_cache

        rc = request_cache()
        for s in self.tier_searchers():
            rc.invalidate_searcher(s.cache_token)

    def tier_stats(self) -> dict:
        """Current (base, tail) tier sizes and the tail-tier doc fraction
        — the fraction of visible docs served by the exact-scan tail
        instead of the precomputed base tiers (impact codes, IVF tiles,
        dense split pairs). The standing write-path invariant: a
        write-heavy tenant that outruns merging grows this until recall
        and the exact-scan fraction degrade (ROADMAP item 2), which is
        exactly what the slo.write.tail_fraction objective bounds."""
        base = sum(len(lst) for lst in self.shard_docs)
        dead = (getattr(self._searcher.sp, "dead_count", 0)
                if self._searcher is not None else 0)
        base_live = max(base - dead, 0)
        tail = len(self._tail_docs)
        total = base_live + tail
        return {
            "base_docs": int(base_live),
            "tail_docs": int(tail),
            "tail_fraction": (round(tail / total, 6) if total else 0.0),
            "segments": len(self._tails),
        }

    def refresh_lag_ms(self) -> float:
        """Milliseconds the oldest unrefreshed write has been waiting for
        visibility; 0 when every write is searchable."""
        if self._dirty_since is None:
            return 0.0
        return (time.monotonic() - self._dirty_since) * 1000.0

    def _can_refresh_incremental(self) -> bool:
        if self._searcher is None or self._base_stats is None:
            return False
        if getattr(self._searcher, "_pinned", False):
            # a scroll/PIT context pinned this exact searcher: its live
            # bitmap and stats are part of an immutable snapshot — rebuild
            # a fresh base instead of mutating it in place
            return False
        base_n = sum(len(lst) for lst in self.shard_docs)
        projected = len(self._tail_docs) + len(self._pending)
        # tail growth bound: beyond ~10% of the base, merge (rebuild) —
        # the analog of Lucene's merge policy folding small segments in
        return projected <= max(256, base_n // 10)

    def _merge_tiers(self):
        """Fold every tier into a fresh sealed base WITHOUT changing
        search visibility: rebuilds from exactly the currently-visible
        docs (live base docs + tail docs), leaving pending unrefreshed
        writes pending. Used when a non-tier-aware feature needs one
        merged view (the major merge; `_merge_tail_segments` is the
        LSM minor fold that leaves the base sealed).

        Atomicity contract (PR 15 satellite): every build step runs
        into locals; searcher/tier state mutates only after the new
        pack passed breaker admission — an injected `refresh.build`
        fault (stage=merge) or a real build failure leaves the old
        tiers fully serving."""
        from ..common import faults
        from ..monitoring.refresh_profile import (
            build_stage, profile_refresh, refresh_stage)
        from ..parallel.stacked import build_stacked_pack_routed, route_docs

        faults.check("refresh.build", index=self.name, stage="merge")
        base = self._searcher
        visible = []
        for s, lst in enumerate(self.shard_docs):
            for d, (doc_id, src) in enumerate(lst):
                if base.sp.live[s, d]:
                    visible.append((doc_id, src))
        visible.extend(sorted(self._tail_docs.items()))
        with profile_refresh(self, "merge"), \
                build_stage("build.merge", docs=len(visible),
                            nbytes=self._base_nbytes):
            with refresh_stage("route"):
                routed = self._route_docs(visible)
            sp = build_stacked_pack_routed(
                routed, self.mappings, **self._shard_build_args(base.mesh))
            self._account_packs(sp.nbytes(), base.mesh)
            searcher = StackedSearcher(sp, mesh=base.mesh)
            # ---- atomic install: nothing above touched serving state
            self._invalidate_request_cache()
            self._searcher = searcher
            self.shard_docs = routed
            self._tails = []
            self._tail_pos = {}
            self._tail_docs = {}
            self._base_pos = {
                doc_id: (s, d)
                for s, lst in enumerate(routed)
                for d, (doc_id, _src) in enumerate(lst)
            }
            self._base_stats = (
                {f: dict(st) for f, st in sp.field_stats.items()},
                dict(sp.global_df),
            )
            self._base_nbytes = sp.nbytes()

    def _account_packs(self, nbytes: int, mesh) -> None:
        """Admit `nbytes` of this index's packs before they ship. The
        breaker budgets one device: a mesh spreads the pack's equal-shaped
        shards over its "shards" axis, so each device holds one share;
        without a mesh the whole pack sits on the first device."""
        if self._breaker_account is not None:
            spread = mesh.shape["shards"] if mesh is not None else 1
            self._breaker_account(-(-nbytes // spread))

    def _refresh_full(self, mesh=None):
        """Rebuild everything from live docs (a full merge: one sealed base,
        no tail, stats reset to live-only)."""
        from ..monitoring.refresh_profile import refresh_stage
        from ..parallel.stacked import build_stacked_pack_routed, route_docs

        live_docs = [(i, e.source) for i, e in self.docs.items() if e.alive]
        # one routing pass: the same per-shard (id, source) lists drive both
        # pack building and hit-id resolution, and double as the point-in-time
        # _source snapshot (the analog of stored fields in a sealed segment)
        with refresh_stage("route"):
            routed = self._route_docs(live_docs)
        if mesh is None:
            mesh = (self._searcher.mesh if self._searcher is not None
                    else make_mesh(self.num_shards))
        sp = build_stacked_pack_routed(routed, self.mappings,
                                       **self._shard_build_args(mesh))
        # admission control BEFORE shipping to the device: on trip, the
        # old searcher stays live (HierarchyCircuitBreakerService analog)
        self._account_packs(sp.nbytes(), mesh)
        self._invalidate_request_cache()
        self._searcher = StackedSearcher(sp, mesh=mesh)
        self.shard_docs = routed
        self._tails = []
        self._tail_pos = {}
        self._tail_docs = {}
        self._pending.clear()
        self._base_pos = {
            doc_id: (s, d)
            for s, lst in enumerate(routed)
            for d, (doc_id, _src) in enumerate(lst)
        }
        self._base_stats = (
            {f: dict(st) for f, st in sp.field_stats.items()},
            dict(sp.global_df),
        )
        self._base_nbytes = sp.nbytes()

    def _combined_override(self, tails: list | None = None) -> dict:
        """Combined scoring statistics across every tier: base stats AT
        BUILD (dead docs included, like Lucene until merge) + each tail
        segment's stats at its own build. `tails` overrides the live
        segment list so merge/refresh can compute the post-install
        stats before mutating any state (the atomicity contract)."""
        if tails is None:
            tails = self._tails
        fs = {f: dict(st) for f, st in self._base_stats[0].items()}
        gdf = dict(self._base_stats[1])
        for seg in tails:
            for f, st in seg.stats[0].items():
                g = fs.setdefault(f, {"sum_dl": 0.0, "doc_count": 0})
                g["sum_dl"] += st["sum_dl"]
                g["doc_count"] += st["doc_count"]
            for key, v in seg.stats[1].items():
                gdf[key] = gdf.get(key, 0) + v
        return {"field_stats": fs, "global_df": gdf}

    def _install_combined_stats(self, override: dict | None = None):
        """Install the combined stats override on every tier and re-derive
        the stats-dependent device structures: base dense tfn + impact
        code blocks (one elementwise device pass each — never a host
        rebuild). Every PRE-EXISTING searcher bumps its stats epoch so
        cached results keyed on the old statistics die; a segment whose
        resident codes already derive from `override` (the one built
        this refresh) skips its redundant pass."""
        base = self._searcher
        if override is None:
            override = self._combined_override()
        base.sp.stats_override = override
        base.bump_epoch(stats=True)
        base.refresh_dense_tfn()
        base.refresh_impacts()
        for seg in self._tails:
            sp = seg.searcher.sp
            if getattr(sp, "_impact_basis", None) is override \
                    and sp.stats_override is override:
                continue  # fresh segment: derived at construction
            sp.stats_override = override
            seg.searcher.bump_epoch(stats=True)
            seg.searcher.refresh_impacts()

    def _refresh_incremental(self):
        """Refresh proportional to the docs written SINCE THE LAST
        refresh (PR 15): flip live bits for superseded/deleted docs in
        whichever tier holds the old copy (base or an older tail
        segment), pack ONLY the new docs as a fresh sealed tail segment,
        and re-score every tier under the combined statistics (deleted
        docs keep counting in df/avgdl until a merge — Lucene
        segment-stats behavior). The old two-tier model rebuilt the
        whole tail union every refresh; segments make refresh O(new
        docs), with background merges bounding the segment count."""
        from ..monitoring.refresh_profile import refresh_stage
        from ..parallel.stacked import build_stacked_pack_routed, route_docs

        base = self._searcher
        new_docs: dict[str, dict] = {}
        flipped_segs: set[int] = set()
        for did in self._pending:
            e = self.docs.get(did)
            pos = self._base_pos.get(did)
            if pos is not None:
                s, d = pos
                if base.sp.live[s, d]:
                    base.sp.shards[s].live[d] = False
                    base.sp.live[s, d] = False
                    base.sp.dead_count = getattr(base.sp, "dead_count", 0) + 1
            tpos = self._tail_pos.pop(did, None)
            if tpos is not None:
                g, s, d = tpos
                seg = self._tails[g]
                if seg.searcher.sp.live[s, d]:
                    seg.searcher.sp.shards[s].live[d] = False
                    seg.searcher.sp.live[s, d] = False
                    flipped_segs.add(g)
            if e is not None and e.alive:
                new_docs[did] = e.source
                self._tail_docs[did] = e.source
            else:
                self._tail_docs.pop(did, None)
        self._pending.clear()
        base.update_live()
        for g in sorted(flipped_segs):
            self._tails[g].searcher.update_live()
        if not new_docs:
            # delete/supersede-only refresh: the live flips above are the
            # whole visibility change — no empty segment, no stats drift
            # (dead docs keep counting until a merge, so the frozen
            # per-tier stats are already correct)
            return
        with refresh_stage("route"):
            routed = self._route_docs(sorted(new_docs.items()))
        seg_sp = build_stacked_pack_routed(
            routed, self.mappings, dense_min_df=1 << 62,
            **self._shard_build_args(base.mesh))
        self._account_packs(
            self._base_nbytes
            + sum(seg.nbytes for seg in self._tails) + seg_sp.nbytes(),
            base.mesh)
        ordinal = len(self._tails)
        seg = _TailSegment(
            searcher=None, shard_docs=routed,
            pos={doc_id: (s, d)
                 for s, lst in enumerate(routed)
                 for d, (doc_id, _src) in enumerate(lst)},
            stats=({f: dict(st) for f, st in seg_sp.field_stats.items()},
                   dict(seg_sp.global_df)),
            nbytes=seg_sp.nbytes(),
        )
        # the NEW combined stats are installed on the pack before its
        # searcher exists, so construction-time impact derivation sees
        # them; the segment joins the tier list only once fully built
        override = self._combined_override(self._tails + [seg])
        seg_sp.stats_override = override
        seg.searcher = StackedSearcher(seg_sp, mesh=base.mesh)
        self._tails.append(seg)
        for doc_id, p in seg.pos.items():
            self._tail_pos[doc_id] = (ordinal, *p)
        self._install_combined_stats(override)
        # LSM merge policy: beyond the segment bound, fold the tail
        # segments in the background (a low-priority serving tenant when
        # the front end is up; inline otherwise)
        if self.merge_pending():
            self._schedule_tail_merge()

    # ---- LSM tail-segment merging (PR 15) --------------------------------

    def _shard_build_args(self, mesh) -> dict:
        """How `build_stacked_pack_routed` builds this index's shards: as
        many at once as the dynamic `indexing.refresh.shard_builders` says
        (unset: one a shard, as far as the host has cores), each shard's
        device stages on the device of `mesh` that will hold it."""
        builders = None
        try:
            if self.engine is not None:
                builders = self.engine.settings.get(
                    "indexing.refresh.shard_builders")
        except Exception:  # noqa: BLE001 - default for standalone indices
            pass
        devices = None
        if mesh is not None:
            grid = np.asarray(mesh.devices).reshape(self.num_shards, -1)
            devices = list(grid[:, 0])
        return {"shard_builders": builders, "devices": devices}

    def max_tail_segments(self) -> int:
        """Segment-count bound before a tail fold is scheduled (dynamic
        `indexing.tiers.max_segments`; the Lucene merge-policy analog)."""
        try:
            if self.engine is not None:
                return max(1, int(self.engine.settings.get(
                    "indexing.tiers.max_segments") or 4))
        except Exception:  # noqa: BLE001 - default for standalone indices
            pass
        return 4

    def merge_pending(self) -> bool:
        return len(self._tails) > self.max_tail_segments()

    def _schedule_tail_merge(self):
        """Route the fold through the engine's serving queue (background
        DEVICE merge as a low-weight tenant under the PR-6 weighted-RR
        admission); standalone indices fold inline. Merge failures are
        swallowed and counted — the atomic-install contract means a
        failed fold leaves every segment serving."""
        if self.engine is not None:
            self.engine.schedule_tail_merge(self)
            return
        try:
            self._merge_tail_segments()
        except Exception:  # noqa: BLE001 - fold is housekeeping
            self.counters["merge_failures"] = (
                self.counters.get("merge_failures", 0) + 1)

    def _merge_tail_segments(self) -> bool:
        """The LSM minor merge: fold every tail segment into ONE fresh
        sealed segment WITHOUT touching the base — superseded duplicate
        copies drop out (the union `_tail_docs` is the fold's input), so
        the combined stats tighten back toward truth.

        Atomic or not at all (PR 15 satellite): the whole build runs
        into locals; tier state swaps only after breaker admission. An
        injected `refresh.build` (stage=merge) fault — or any build
        failure — leaves the old segments fully serving, and a later
        fold retries."""
        from ..common import faults
        from ..monitoring.refresh_profile import (
            build_stage, profile_refresh, refresh_stage)
        from ..parallel.stacked import build_stacked_pack_routed

        base = self._searcher
        if base is None or len(self._tails) < 2:
            return False
        # ctx stage "segment_merge": substring-matchable as either
        # `match=merge` (any merge kind) or `match=segment_merge` (the
        # swallowed background-fold path only — what the tier-1 advisory
        # write-path stage injects)
        faults.check("refresh.build", index=self.name,
                     stage="segment_merge")
        visible = sorted(self._tail_docs.items())
        old_nbytes = sum(seg.nbytes for seg in self._tails)
        with profile_refresh(self, "segment_merge"), \
                build_stage("build.segment_merge", docs=len(visible),
                            nbytes=old_nbytes):
            with refresh_stage("route"):
                routed = self._route_docs(visible)
            sp = build_stacked_pack_routed(
                routed, self.mappings, dense_min_df=1 << 62,
                **self._shard_build_args(base.mesh))
            self._account_packs(self._base_nbytes + sp.nbytes(), base.mesh)
            merged = _TailSegment(
                searcher=None, shard_docs=routed,
                pos={doc_id: (s, d)
                     for s, lst in enumerate(routed)
                     for d, (doc_id, _src) in enumerate(lst)},
                stats=({f: dict(st) for f, st in sp.field_stats.items()},
                       dict(sp.global_df)),
                nbytes=sp.nbytes(),
            )
            override = self._combined_override([merged])
            sp.stats_override = override
            merged.searcher = StackedSearcher(sp, mesh=base.mesh)
            # ---- atomic install: nothing above touched serving state
            from ..cache import request_cache

            rc = request_cache()
            for seg in self._tails:
                rc.invalidate_searcher(seg.searcher.cache_token)
            self._tails = [merged]
            self._tail_pos = {doc_id: (0, s, d)
                              for doc_id, (s, d) in merged.pos.items()}
            self._install_combined_stats(override)
        self.counters["segment_merge_total"] = (
            self.counters.get("segment_merge_total", 0) + 1)
        return True

    def _maybe_refresh(self):
        if self._searcher is None:  # safety; construction always refreshes
            self.refresh()
            return
        if not self._dirty:
            return
        from ..utils.durations import parse_duration_seconds

        try:
            secs = parse_duration_seconds(self.settings.get("refresh_interval", "1s"), 1.0)
        except IllegalArgumentError:
            secs = 1.0
        if secs is None:  # "-1": only explicit refresh
            return
        if time.monotonic() - self._last_refresh >= secs:
            self.refresh()

    def _resolve_top_hits(self, aggregations):
        """Replace top_hits (shard, docid) placeholders with real hit
        envelopes (the fetch sub-search of
        search/aggregations/metrics/TopHitsAggregator.java)."""
        if not aggregations:
            return

        def walk(obj):
            if isinstance(obj, dict):
                inner = obj.get("hits")
                if isinstance(inner, dict) and isinstance(inner.get("hits"), list):
                    resolved = []
                    for h in inner["hits"]:
                        if isinstance(h, dict) and h.pop("_resolve_top_hit", False):
                            doc_id, src = self.shard_docs[h.pop("_shard")][h.pop("_doc")]
                            resolved.append({
                                "_index": self.name, "_id": doc_id,
                                "_score": h["_score"], "_source": src,
                            })
                        else:
                            resolved.append(h)
                    inner["hits"] = resolved
                for v in obj.values():
                    walk(v)
            elif isinstance(obj, list):
                for v in obj:
                    walk(v)

        walk(aggregations)

    def _apply_script_fields(self, hits: list, script_fields: dict | None):
        """script_fields: {name: {"script": ...}} evaluated over the hits'
        source values host-side (the fetch sub-phase analog,
        search/fetch/subphase/ScriptFieldsPhase.java) with the same compiled
        expression engine the device scoring path uses."""
        if not script_fields or not hits:
            return
        from ..script import compile_script

        for name, spec in script_fields.items():
            spec = spec.get("script", spec) if isinstance(spec, dict) else spec
            cs = compile_script(spec)
            env = {}
            for f in cs.fields:
                vals = []
                for h in hits:
                    v = h.get("_source", {}).get(f, 0)
                    if isinstance(v, str):
                        from ..index.mappings import parse_date_to_millis

                        try:
                            v = parse_date_to_millis(v)
                        except Exception:
                            v = 0
                    vals.append(float(v) if isinstance(v, (int, float, bool)) else 0.0)
                env[f] = np.asarray(vals, np.float32)
            scores = np.asarray(
                [h.get("_score") or 0.0 for h in hits], np.float32
            )
            out = np.asarray(cs.evaluate(env, score=scores))
            for h, v in zip(hits, out):
                h.setdefault("fields", {})[name] = [float(v)]

    def search(
        self, query=None, size=10, from_=0, aggs=None, knn=None,
        sort=None, search_after=None, script_fields=None,
        collapse=None, rescore=None, runtime_mappings=None,
        track_total_hits=None,
    ):
        self._maybe_refresh()
        if self.engine is not None and (knn is not None or query is not None):
            from ..inference import resolve_query_vector_builders

            svc = self.engine.inference
            query = resolve_query_vector_builders(query, svc)
            knn = resolve_query_vector_builders(knn, svc)
        self.counters["query_total"] = self.counters.get("query_total", 0) + 1
        from ..telemetry import TRACER, record_search_slowlog

        _t_search0 = time.monotonic()
        _trace_ctx = TRACER.span("executeQueryPhase", index=self.name)
        _trace_span = _trace_ctx.__enter__()
        try:
            def _dispatch():
                # injection only on the engine-backed data plane (a
                # standalone EsIndex has no recovery service to stage
                # the degradation)
                from ..common import faults

                faults.check("device.dispatch", index=self.name)
                return self._search_inner(
                    query=query, size=size, from_=from_, aggs=aggs,
                    knn=knn, sort=sort, search_after=search_after,
                    script_fields=script_fields, collapse=collapse,
                    rescore=rescore, runtime_mappings=runtime_mappings,
                    track_total_hits=track_total_hits,
                )

            if self.engine is None:
                return self._search_inner(
                    query=query, size=size, from_=from_, aggs=aggs,
                    knn=knn, sort=sort, search_after=search_after,
                    script_fields=script_fields, collapse=collapse,
                    rescore=rescore, runtime_mappings=runtime_mappings,
                    track_total_hits=track_total_hits,
                )
            # device-failure graceful degradation (PR 14): a
            # RESOURCE_EXHAUSTED at any arm evicts recoverable caches,
            # halves the serving wave with a recovery ramp, and re-runs
            # this one search on the exact/XLA arm instead of 500ing
            from ..common.resilience import run_with_device_recovery

            return run_with_device_recovery(
                self.engine, _dispatch, where="dispatch")
        finally:
            if runtime_mappings:
                self.searcher.remove_runtime_fields(list(runtime_mappings))
            _trace_ctx.__exit__(None, None, None)
            took_ms = (time.monotonic() - _t_search0) * 1000
            self.counters["query_time_ms"] = (
                self.counters.get("query_time_ms", 0) + int(took_ms))
            record_search_slowlog(
                self.name, self.settings, took_ms,
                json.dumps(query)[:512] if query is not None else "{}",
            )

    def _search_inner(
        self, query=None, size=10, from_=0, aggs=None, knn=None,
        sort=None, search_after=None, script_fields=None,
        collapse=None, rescore=None, runtime_mappings=None,
        track_total_hits=None,
    ):
        if collapse is not None and rescore is not None:
            raise IllegalArgumentError("cannot use [collapse] in conjunction with [rescore]")
        # track_total_hits (reference: SearchSourceBuilder.trackTotalHitsUpTo):
        # every count here is exact, so true, a threshold N and the default
        # all answer {value: <count>, relation: "eq"}; false omits hits.total

        # ---- tiered fast path: base + tail searched separately, merged at
        # this coordinator (the per-segment search of the reference). Falls
        # through (auto-merging via the searcher property) for features the
        # tiered form doesn't serve.
        if (self._tail is not None and not aggs and sort is None
                and knn is None and collapse is None and rescore is None
                and not runtime_mappings and search_after is None
                and not script_fields):
            node = self._tier_node(query)
            if node is not None:
                return self._search_tiered(node, size, from_,
                                           track_total_hits,
                                           raw_query=query)
        m_eff = None
        if runtime_mappings:
            import copy

            from ..index.mappings import FieldType

            m_eff = copy.copy(self.mappings)
            m_eff.fields = dict(self.mappings.fields)
            for nm, spec in runtime_mappings.items():
                if not isinstance(spec, dict) or "script" not in spec:
                    raise IllegalArgumentError(
                        f"runtime field [{nm}] requires a [script]"
                    )
                rtype = spec.get("type", "double")
                self.searcher.ensure_runtime_field(nm, rtype, spec["script"])
                ftype = {"long": "long", "double": "double",
                         "date": "date", "boolean": "boolean"}.get(rtype)
                m_eff.fields[nm] = FieldType(name=nm, type=ftype, index=False)
        from ..aggs.pipeline import apply_pipeline_aggs, strip_pipeline_aggs
        from ..query.sort import is_score_only, parse_sort

        # pipeline aggs are host-side post-reduction transforms; the device
        # only ever sees the stripped tree (reference behavior: pipeline
        # aggregators run at coordinator reduce, search/aggregations/pipeline/)
        aggs_request = aggs
        aggs, had_pipeline = strip_pipeline_aggs(aggs)
        aggs = aggs or None

        sort_fields = parse_sort(sort)
        if not is_score_only(sort_fields):
            if knn is not None:
                raise IllegalArgumentError("knn with field sort is not supported")
            if collapse is not None or rescore is not None:
                raise IllegalArgumentError(
                    "collapse/rescore with field sort is not supported"
                )
            hits_raw, total, aggregations = self.searcher.search_sorted(
                query, sort_fields, size=size, from_=from_,
                search_after=search_after, aggs=aggs, mappings=m_eff,
            )
            hits = []
            for s, d, values in hits_raw:
                doc_id, src = self.shard_docs[s][d]
                hits.append({
                    "_index": self.name,
                    "_id": doc_id,
                    "_score": None,
                    "_source": src,
                    "sort": values,
                })
            self._apply_script_fields(hits, script_fields)
            if had_pipeline and aggregations is not None:
                apply_pipeline_aggs(aggs_request, aggregations)
            self._resolve_top_hits(aggregations)
            hits_obj = {
                "total": {"value": total, "relation": "eq"},
                "max_score": None,
                "hits": hits,
            }
            if track_total_hits is False:
                del hits_obj["total"]  # reference omits hits.total entirely
            return {
                "hits": hits_obj,
                **({"aggregations": aggregations} if aggregations is not None else {}),
            }
        if search_after is not None:
            raise IllegalArgumentError(
                "search_after requires an explicit sort on fields"
            )
        if knn is not None:
            # knn section: standalone -> knn hits; with a query -> union with
            # scores summed where a doc appears in both (reference behavior:
            # SearchSourceBuilder knn + query combination)
            from ..query.dsl import parse_knn, parse_query
            from ..query.nodes import BoolNode, PinnedScoresNode

            knn_bodies = knn if isinstance(knn, list) else [knn]
            knn_nodes = [parse_knn(k, self.mappings) for k in knn_bodies]
            self._apply_knn_settings(knn_nodes)
            knn_only = query is None
            k_total = sum(kn.k for kn in knn_nodes)
            if (knn_only and self._tail is not None and not aggs
                    and not had_pipeline and collapse is None
                    and rescore is None and m_eff is None
                    and not script_fields):
                # tiered knn: the base tier rides its ANN index, the tail
                # tier (docs since the last rebuild — too small to have
                # one) is scanned EXACTLY, and the coordinator merges —
                # incremental refresh never forces a base rebuild and
                # never degrades recall (the ANN exact-tail contract)
                def _tier_node():
                    nodes = [parse_knn(k, self.mappings)
                             for k in knn_bodies]
                    self._apply_knn_settings(nodes)
                    return (nodes[0] if len(nodes) == 1 else
                            BoolNode(should=nodes, minimum_should_match=1))

                eff_size = min(size, max(k_total - from_, 0))
                k = max(eff_size + from_, 1)
                tails = list(self._tails)
                rb = self._knn_exec(self._searcher, _tier_node(), k)
                rts = [self._knn_exec(seg.searcher, _tier_node(), k)
                       for seg in tails]
                out = self._tiered_merge(
                    rb, rts, eff_size, from_, track_total_hits,
                    [seg.shard_docs for seg in tails])
                if track_total_hits is not False:
                    tv = out["hits"]["total"]
                    tv["value"] = min(tv["value"], k_total)
                return out
            if not knn_only:
                # hybrid: each knn section first retrieves its GLOBAL top k
                # (per-shard candidates, cross-shard re-selection), and only
                # those score-docs join the user query as a should clause
                # (reference behavior: KnnScoreDocQueryBuilder rewrite)
                qnode = parse_query(query, self.mappings)
                S = self.searcher.sp.S
                pinned = []
                for kn in knn_nodes:
                    kres = self._knn_exec(self.searcher, kn, kn.k)
                    per_shard = [([], []) for _ in range(S)]
                    for s, d, sc in zip(kres.doc_shards, kres.doc_ids, kres.scores):
                        per_shard[s][0].append(int(d))
                        per_shard[s][1].append(float(sc))
                    pinned.append(PinnedScoresNode(per_shard=[
                        (np.asarray(ids, np.int32), np.asarray(scs, np.float32))
                        for ids, scs in per_shard
                    ]))
                query = BoolNode(should=[qnode, *pinned], minimum_should_match=1)
            elif len(knn_nodes) == 1:
                query = knn_nodes[0]
            else:
                query = BoolNode(should=knn_nodes, minimum_should_match=1)
            if knn_only:
                # each shard contributes up to k candidates; the global result
                # is the top k overall (KnnSearchBuilder.java:44 semantics)
                size = min(size, max(k_total - from_, 0))
        collapse_keys = None
        if collapse is not None:
            cfld = collapse.get("field") if isinstance(collapse, dict) else collapse
            if not cfld:
                raise IllegalArgumentError("no [field] specified for collapse")
            res = self.searcher.search_collapse(query, cfld, size=size, from_=from_)
            collapse_keys = getattr(res, "collapse_keys", None)
            if aggs:
                # aggs compute over the pre-collapse match set (reference
                # behavior: collapsing only affects the hit list)
                res_a = self.searcher.search(query, size=1, aggs=aggs)
                res.aggregations = res_a.aggregations
        elif rescore is not None:
            specs = rescore if isinstance(rescore, list) else [rescore]
            windows = [int(sp.get("window_size", 10)) for sp in specs]
            k_fetch = max(size + from_, max(windows))
            res = self.searcher.search(query, size=k_fetch, from_=0, aggs=aggs,
                                       mappings=m_eff)
            order = list(zip(res.doc_shards, res.doc_ids, res.scores))
            for spec, w in zip(specs, windows):
                q2 = (spec.get("query") or {})
                rq = q2.get("rescore_query")
                if rq is None:
                    raise IllegalArgumentError("rescore requires [rescore_query]")
                qw = float(q2.get("query_weight", 1.0))
                rw = float(q2.get("rescore_query_weight", 1.0))
                mode = q2.get("score_mode", "total")
                win = order[:w]
                if not win:
                    continue
                sh = np.asarray([x[0] for x in win], np.int32)
                di = np.asarray([x[1] for x in win], np.int32)
                s2, ok2 = self.searcher.scores_at(rq, sh, di)
                combined = []
                for (s_, d_, s1), sc2, k2 in zip(win, s2, ok2):
                    a, b = qw * float(s1), rw * float(sc2)
                    if not k2:
                        c = a
                    elif mode == "total":
                        c = a + b
                    elif mode == "multiply":
                        c = a * b
                    elif mode == "avg":
                        c = (a + b) / 2.0
                    elif mode == "max":
                        c = max(a, b)
                    elif mode == "min":
                        c = min(a, b)
                    else:
                        raise IllegalArgumentError(f"unsupported rescore score_mode [{mode}]")
                    combined.append(c)
                rescored = sorted(
                    zip(win, combined), key=lambda t: -t[1]
                )
                order = [(s_, d_, c) for (s_, d_, _), c in rescored] + order[w:]
            order = order[from_: from_ + size]
            res.doc_shards = np.asarray([x[0] for x in order], np.int32)
            res.doc_ids = np.asarray([x[1] for x in order], np.int32)
            res.scores = np.asarray([x[2] for x in order], np.float32)
            res.max_score = float(order[0][2]) if order else None
        else:
            res = self.searcher.search(query, size=size, from_=from_, aggs=aggs,
                                       mappings=m_eff)
            if knn is not None and self._knn_mark_starved(
                    query, len(res.doc_ids) + from_, size + from_):
                # filtered ANN retrieval could not reach k: re-run with
                # the marked nodes recompiled onto the exact scan
                res = self.searcher.search(query, size=size, from_=from_,
                                           aggs=aggs, mappings=m_eff)
        if knn is not None and knn_only:
            res.total = min(res.total, k_total)
        return self._format_generic_hits(
            res, track_total_hits, aggs_request, had_pipeline,
            script_fields=script_fields, collapse=collapse,
            collapse_keys=collapse_keys,
        )

    def _format_generic_hits(self, res, track_total_hits,
                             aggs_request=None, had_pipeline=False,
                             script_fields=None, collapse=None,
                             collapse_keys=None) -> dict:
        """Turn a StackedResult into the response body `_search_inner`
        returns — shared by the solo path and the serving wave lanes so a
        coalesced request's response is built by the identical code."""
        from ..telemetry import TRACER

        with TRACER.span("engine.collect"):
            from ..aggs.pipeline import apply_pipeline_aggs

            hits = []
            for i, (s, d, score) in enumerate(zip(res.doc_shards, res.doc_ids, res.scores)):
                doc_id, src = self.shard_docs[s][d]
                h = {
                    "_index": self.name,
                    "_id": doc_id,
                    "_score": float(score),
                    "_source": src,
                }
                if collapse_keys is not None and i < len(collapse_keys):
                    cfld = collapse.get("field") if isinstance(collapse, dict) else collapse
                    h["fields"] = {cfld: [collapse_keys[i]]}
                hits.append(h)
            self._apply_script_fields(hits, script_fields)
            if had_pipeline and res.aggregations is not None:
                apply_pipeline_aggs(aggs_request, res.aggregations)
            self._resolve_top_hits(res.aggregations)
            hits_obj = {
                "total": {"value": res.total, "relation": "eq"},
                "max_score": res.max_score,
                "hits": hits,
            }
            if track_total_hits is False:
                del hits_obj["total"]  # reference omits hits.total entirely
            return {
                "hits": hits_obj,
                **({"aggregations": res.aggregations} if res.aggregations is not None else {}),
            }

    # ---- knn / ANN -------------------------------------------------------

    def _apply_knn_settings(self, knn_nodes):
        """Fill per-node nprobe from the dynamic `index.knn.nprobe`
        setting when the request body did not pin one (0 = auto: probes
        sized to cover ~num_candidates vectors)."""
        try:
            np_default = int(self.settings.get("knn.nprobe") or 0)
        except (TypeError, ValueError):
            np_default = 0
        if np_default > 0:
            for kn in knn_nodes:
                if kn.nprobe is None:
                    kn.nprobe = np_default

    @staticmethod
    def _knn_nodes_of(node):
        from ..query.nodes import BoolNode, KnnNode

        if isinstance(node, KnnNode):
            return [node]
        if isinstance(node, BoolNode):
            return [c for c in node.should if isinstance(c, KnnNode)]
        return []

    def _knn_mark_starved(self, node, hits_found: int, window: int) -> bool:
        """Filtered/thresholded knn on the ANN path that could not fill
        the requested window is 'starved': the oversampled candidate
        pool may have been eaten by the filter. Flip those nodes to
        force_exact (recompiles onto the full scan) and report whether a
        re-run is needed — the ONLY case the ANN path falls back."""
        starved = [
            kn for kn in self._knn_nodes_of(node)
            if getattr(kn, "_ann", None) is not None
            and (kn.filter_node is not None
                 or kn.similarity_threshold is not None)
        ]
        if not starved or hits_found >= min(window, sum(
                kn.k for kn in self._knn_nodes_of(node)) or window):
            return False
        for kn in starved:
            kn.force_exact = True
        return True

    def _knn_exec(self, searcher, node, k: int):
        """Search one knn node tree with the starved-filter escalation."""
        res = searcher.search(node, size=k)
        if self._knn_mark_starved(node, len(res.doc_ids), k):
            res = searcher.search(node, size=k)
        return res

    def _tier_node(self, query):
        """Parse `query` once and return the node if it can be evaluated per
        tier and merged (every node scores docs independently of other docs'
        identities), else None. Nodes that resolve documents across the
        index at prepare time (knn candidates, more-like-this by id,
        percolate, pinned ids, nested host sets) must see the merged
        index."""
        from ..query.dsl import parse_query
        from ..query.nodes import (
            BoolNode, ConstantScoreNode, DisMaxNode, ExistsNode,
            ExpandedTermsNode, MatchAllNode, MatchNoneNode, PhraseNode,
            RangeNode, TermNode, TermsNode,
        )

        safe = (TermNode, MatchAllNode, MatchNoneNode, RangeNode, TermsNode,
                ExistsNode, PhraseNode, ExpandedTermsNode)

        def ok(node):
            if isinstance(node, BoolNode):
                return all(ok(c) for grp in (node.must, node.filter,
                                             node.should, node.must_not)
                           for c in grp)
            if isinstance(node, ConstantScoreNode):
                return ok(node.child)
            if isinstance(node, DisMaxNode):
                return all(ok(c) for c in node.children)
            return isinstance(node, safe)

        try:
            node = parse_query(query, self.mappings)
        except Exception:  # noqa: BLE001 - let the normal path raise it
            return None
        return node if ok(node) else None

    def _search_tiered(self, node, size, from_, track_total_hits,
                       raw_query=None) -> dict:
        # each tier parses/prepares its own copy of the query immediately
        # before its own execution, so per-searcher prepare state
        # (dense-tier routing) never crosses tiers. The RAW DSL dict is
        # preferred over the pre-parsed node: plain-dict requests are what
        # the shard request cache can key, so the hot tiered path stays
        # cacheable per tier
        q = raw_query if isinstance(raw_query, dict) or raw_query is None \
            else node
        k = max(size + from_, 1)
        rb = self._searcher.search(q, size=k)
        from ..telemetry import time_kernel

        # snapshot the segment list: a background fold may swap
        # self._tails while the per-segment programs run
        tails = list(self._tails)
        rts = []
        for seg in tails:
            with time_kernel("sparse.tail_scan", tier="tail", queries=1,
                             num_docs=(seg.searcher.sp.S
                                       * seg.searcher.sp.n_max)):
                rts.append(seg.searcher.search(q, size=k))
        return self._tiered_merge(rb, rts, size, from_, track_total_hits,
                                  [seg.shard_docs for seg in tails])

    def _tiered_merge(self, rb, rts, size, from_, track_total_hits,
                      tail_shard_docs) -> dict:
        """Coordinator merge of the base + N tail-segment tier results —
        shared by the solo tiered path and the serving wave's tiered
        lane. `rts` is one result per tail segment, in segment order;
        `tail_shard_docs` is each segment's routed doc lists CAPTURED AT
        DISPATCH — a background fold may replace the live segment list
        before this merge runs, and (shard, docid) coordinates only mean
        anything against the lists the programs actually scanned."""
        from ..telemetry import TRACER

        with TRACER.span("engine.collect"):
            rows = []
            for tier, r in enumerate((rb, *rts)):
                for rank, (s, d, sc) in enumerate(
                        zip(r.doc_shards, r.doc_ids, r.scores)):
                    rows.append((-float(sc), tier, rank, int(s), int(d)))
            # (score desc, tier asc, per-tier rank asc) = Lucene TopDocs.merge
            # order with segment shards indexed after base shards
            rows.sort()
            hits = []
            for negsc, tier, _rank, s, d in rows[from_: from_ + size]:
                docs = (self.shard_docs if tier == 0
                        else tail_shard_docs[tier - 1])
                doc_id, src = docs[s][d]
                hits.append({"_index": self.name, "_id": doc_id,
                             "_score": -negsc, "_source": src})
            value = rb.total + sum(r.total for r in rts)
            max_score = max(
                (x for x in (rb.max_score, *(r.max_score for r in rts))
                 if x is not None), default=None)
            hits_obj = {"total": {"value": value, "relation": "eq"},
                        "max_score": max_score, "hits": hits}
            if track_total_hits is False:
                del hits_obj["total"]
            return {"hits": hits_obj}

    # ---- serving waves ---------------------------------------------------

    # kwargs the wave lanes serve; anything else falls back to solo search
    _WAVE_UNSUPPORTED = ("sort", "search_after", "script_fields", "collapse",
                         "rescore", "runtime_mappings")

    def search_wave_begin(self, entries: list[dict]) -> dict:
        """Serving front end: begin one coalesced wave of independent
        search requests against this index. Lane assignment per entry:

          * term lane — a pure single-field term disjunction (match /
            term / bool-should-of-terms) with no aggs packs into ONE
            batched msearch program per (field, k), padded to the
            compiled power-of-two batch tier and dispatched DEFERRED
            (parallel/sharded msearch_wave_begin — PR 11: the merged
            one-program route, fetched with the rest of the wave).
            Scores agree with the compiled-plan path to ~1e-5 (fp
            summation order) and are byte-identical between coalesced
            and solo waves.
          * generic lane — any other wave-eligible request (aggs, knn-
            only, filtered aliases) runs its OWN compiled program, all
            dispatched before any fetch (StackedSearcher.search_many) —
            byte-identical to solo execution by construction.
          * tiered lane — when the whole wave is tier-capable on a
            (base, tail) index, both tiers' programs batch and merge
            per entry exactly like `_search_tiered`.
          * fallback — anything surprising runs the full solo `search()`.

        Device outputs are left UNFETCHED: `search_wave_fetch` (engine-
        state-free) pulls them, possibly on a completer thread while the
        engine thread plans the next wave (the serving double buffer);
        `search_wave_finish` builds the responses. -> a wave job dict."""
        import numpy as _np

        from ..query.dsl import parse_query
        from ..serving.coalesce import term_disjunction_of
        from ..telemetry import TRACER

        n = len(entries)
        job = {"entries": entries, "slots": [None] * n, "fmt": [None] * n,
               "lanes": [], "term_lanes": [], "tiered": None,
               "t0": time.monotonic(),
               "meta": {"wave_size": n, "term_packed": 0, "term_waves": [],
                        # nanoseconds of the members' query parsing, which
                        # the service hands back to them as `engine.parse`
                        "parse_ns": 0,
                        # host-transition accounting (PR 11): one
                        # dispatch phase + one combined fetch per wave
                        # is the contract; extras (escalations, agg
                        # pass 2, starved-knn reruns) are counted here
                        "transitions": {"dispatch": 0, "fetch": 0}}}
        with TRACER.span("servingWaveDispatch", index=self.name, entries=n,
                         spmd=getattr(self._searcher, "_exec", "vmap")
                         if self._searcher is not None else "vmap"):
            self._maybe_refresh()
            kinds = [None] * n
            for i, e in enumerate(entries):
                self.counters["query_total"] = (
                    self.counters.get("query_total", 0) + 1)
                try:
                    if any(e.get(kk) is not None
                           for kk in self._WAVE_UNSUPPORTED) or (
                            e.get("knn") is not None
                            and e.get("query") is not None):
                        kinds[i] = "fallback"
                    else:
                        kinds[i] = "wave"
                except Exception as ex:  # noqa: BLE001 - per-entry envelope
                    job["slots"][i] = ("error", ex)
            # fallback entries first: a non-tier-capable solo search may
            # merge (base, tail) tiers, and the wave lanes must see the
            # post-merge state exactly like solo sequential execution
            for i, e in enumerate(entries):
                if kinds[i] != "fallback":
                    continue
                try:
                    job["slots"][i] = ("resp", self.search(**e))
                except Exception as ex:  # noqa: BLE001
                    job["slots"][i] = ("error", ex)
            wave_ix = [i for i in range(n)
                       if kinds[i] == "wave" and job["slots"][i] is None]
            # per-entry effective kwargs + format context
            plans = {}
            for i in wave_ix:
                e = entries[i]
                try:
                    query, knn = e.get("query"), e.get("knn")
                    if self.engine is not None and (knn is not None
                                                    or query is not None):
                        from ..inference import resolve_query_vector_builders

                        svc = self.engine.inference
                        query = resolve_query_vector_builders(query, svc)
                        knn = resolve_query_vector_builders(knn, svc)
                    size = int(e.get("size", 10))
                    from_ = int(e.get("from_", 0))
                    plans[i] = {"query": query, "knn": knn, "size": size,
                                "from_": from_,
                                "tth": e.get("track_total_hits"),
                                "aggs": e.get("aggs")}
                except Exception as ex:  # noqa: BLE001
                    job["slots"][i] = ("error", ex)
            wave_ix = [i for i in wave_ix if job["slots"][i] is None]
            # tiered lane: only when EVERY wave entry is tier-capable (a
            # single generic entry would merge the tiers when run solo)
            tiered_nodes = {}
            if self._tails and wave_ix:
                for i in wave_ix:
                    p = plans[i]
                    if p["aggs"] or p["knn"] is not None:
                        tiered_nodes = None
                        break
                    nd = self._tier_node(p["query"])
                    if nd is None:
                        tiered_nodes = None
                        break
                    tiered_nodes[i] = nd
            else:
                tiered_nodes = None
            if tiered_nodes:
                reqs = []
                for i in wave_ix:
                    p = plans[i]
                    q = (p["query"] if isinstance(p["query"], dict)
                         or p["query"] is None else tiered_nodes[i])
                    k = max(p["size"] + p["from_"], 1)
                    reqs.append(dict(query=q, size=k, from_=0,
                                     aggs=None, mappings=None))
                    job["fmt"][i] = p
                segs = list(self._tails)
                job["tiered"] = {
                    "ix": wave_ix,
                    "base": (self._searcher,
                             self._searcher.search_many_begin(reqs)),
                    # one batched program per tail segment, all dispatched
                    # here and pulled by the wave's single combined fetch;
                    # shard_docs captured NOW — a background fold may swap
                    # the live segment list before this wave finishes
                    "tails": [
                        (seg.searcher, seg.searcher.search_many_begin(
                            [dict(r) for r in reqs]))
                        for seg in segs
                    ],
                    "tail_shard_docs": [seg.shard_docs for seg in segs],
                }
                return self._wave_mark_dispatched(job)
            if not wave_ix:
                return self._wave_mark_dispatched(job)
            searcher = self.searcher  # merges tiers when present, like solo
            # term lane extraction (packs into one batched program per
            # (field, k)); everything else goes generic
            term_groups: dict[tuple, list] = {}
            generic_ix, generic_reqs = [], []
            for i in wave_ix:
                p = plans[i]
                spec = None
                if (not p["aggs"] and p["knn"] is None
                        and isinstance(p["query"], dict)
                        and searcher is not None and searcher.sp.n_max > 0):
                    t_parse = time.perf_counter_ns()
                    try:
                        spec = term_disjunction_of(
                            parse_query(p["query"], self.mappings))
                    except Exception:  # noqa: BLE001 - generic lane raises it
                        spec = None
                    job["meta"]["parse_ns"] += (time.perf_counter_ns()
                                                - t_parse)
                if spec is not None:
                    fld, terms = spec
                    k = max(p["size"] + p["from_"], 1)
                    term_groups.setdefault((fld, k), []).append((i, terms))
                    job["fmt"][i] = p
                    continue
                # generic (incl. knn-only): replicate _search_inner's
                # eligible prologue
                try:
                    aggs_request = p["aggs"]
                    from ..aggs.pipeline import strip_pipeline_aggs

                    aggs, had_pipeline = strip_pipeline_aggs(aggs_request)
                    aggs = aggs or None
                    query, size = p["query"], p["size"]
                    knn_clamp = None
                    if p["knn"] is not None:
                        from ..query.dsl import parse_knn
                        from ..query.nodes import BoolNode

                        knn = p["knn"]
                        knn_nodes = [
                            parse_knn(kn, self.mappings)
                            for kn in (knn if isinstance(knn, list)
                                       else [knn])
                        ]
                        self._apply_knn_settings(knn_nodes)
                        k_total = sum(kn.k for kn in knn_nodes)
                        query = (knn_nodes[0] if len(knn_nodes) == 1 else
                                 BoolNode(should=knn_nodes,
                                          minimum_should_match=1))
                        size = min(size, max(k_total - p["from_"], 0))
                        knn_clamp = k_total
                    generic_ix.append(i)
                    generic_reqs.append(dict(
                        query=query, size=size, from_=p["from_"],
                        aggs=aggs, mappings=None))
                    job["fmt"][i] = {**p, "aggs_request": aggs_request,
                                     "had_pipeline": had_pipeline,
                                     "knn_clamp": knn_clamp,
                                     "knn_query": (query if knn_clamp
                                                   is not None else None),
                                     "eff_size": size, "eff_aggs": aggs}
                except Exception as ex:  # noqa: BLE001
                    job["slots"][i] = ("error", ex)
            if generic_ix:
                job["lanes"].append({
                    "ix": generic_ix, "searcher": searcher,
                    "state": searcher.search_many_begin(generic_reqs),
                })
            # term groups DISPATCH here and fetch with the rest of the
            # wave (PR 11): under the pjit model each (field, k) group
            # is ONE merged SPMD program whose outputs join the wave's
            # single combined device_get — the term lane no longer
            # blocks the scheduler thread inside begin. Response
            # building moved to search_wave_finish.
            for (fld, k), members in sorted(term_groups.items()):
                try:
                    from ..parallel.sharded import msearch_wave_begin

                    st = msearch_wave_begin(
                        searcher, fld, [t for _, t in members], k)
                    job["term_lanes"].append(
                        {"fld": fld, "k": k, "members": members, "st": st})
                except Exception as ex:  # noqa: BLE001
                    for i, _terms in members:
                        job["slots"][i] = ("error", ex)
        return self._wave_mark_dispatched(job)

    @staticmethod
    def _wave_mark_dispatched(job: dict) -> dict:
        """Count the wave's single program-launch phase: every lane's
        programs are in flight, nothing fetched — ONE host→device
        transition regardless of how many programs launched."""
        pending = any(lane["state"].get("pending")
                      for lane in job["lanes"])
        t = job.get("tiered")
        if t is not None:
            pending = pending or bool(t["base"][1].get("pending")) \
                or any(bool(st.get("pending")) for _s, st in t["tails"])
        for tl in job.get("term_lanes", ()):
            m = tl["st"].get("merged")
            if m is not None and m.get("pending") is not None:
                pending = True
        if pending:
            from ..telemetry import host_transition

            host_transition("dispatch")
            job["meta"]["transitions"]["dispatch"] += 1
        return job

    def search_wave_fetch(self, job: dict) -> None:
        """Pull the wave's pending device outputs — ONE combined blocking
        `device_get` across every lane (generic, tiered base+tail, and
        the PR-11 deferred term lanes), so the whole wave costs a single
        host←device round-trip however many programs it dispatched.
        Touches no engine host state — runs on the serving completer
        thread while the engine thread begins the next wave
        (double-buffered pipelining)."""
        states = [lane["state"] for lane in job["lanes"]]
        t = job.get("tiered")
        if t is not None:
            states += [t["base"][1]] + [st for _s, st in t["tails"]]
        merged = [tl["st"].get("merged")
                  for tl in job.get("term_lanes", ())]
        merged = [m for m in merged
                  if m is not None and m.get("host") is None
                  and m.get("pending") is not None]
        pend_states = [s for s in states if s.get("pending")]
        for s in states:
            if not s.get("pending"):
                s["host"] = []
        if not pend_states and not merged:
            return
        import jax

        from ..common import faults
        from ..telemetry import host_transition, time_kernel

        faults.check("device.fetch", index=self.name, op="wave")

        sp = getattr(self._searcher, "sp", None)
        fields = dict(tier="wave",
                      shards=(sp.S if sp is not None else 1),
                      queries=sum(len(s.get("requests", ()))
                                  for s in pend_states) + len(merged),
                      k=max([m["fields"].get("k", 10) for m in merged]
                            or [10]),
                      num_docs=(sp.S * sp.n_max if sp is not None else 0))
        pending = ([s["pending"] for s in pend_states]
                   + [m["pending"] for m in merged])
        # counted as the solo path's fetch is: device arrays pulled
        from ..telemetry import metrics

        n_arrays = len(jax.tree_util.tree_leaves(pending))
        metrics.counter_inc("es.search.fetch.buffers", n_arrays)
        metrics.counter_inc("es.search.fetch.leaves", n_arrays)
        with time_kernel("serving.wave_program", **fields):
            host = jax.device_get(pending)
        hi = iter(host)
        for s in pend_states:
            s["host"] = next(hi)
        for m in merged:
            m["host"] = next(hi)
        host_transition("fetch")
        job["meta"]["transitions"]["fetch"] += 1

    def search_wave_finish(self, job: dict) -> list:
        """Finalize a fetched wave -> per-entry response dict (or the
        entry's exception object) in entry order. Engine thread only:
        response building reads shard docs and stores cache entries."""
        from ..telemetry import TRACER, record_search_slowlog

        with TRACER.span("servingWaveFinalize", index=self.name,
                         entries=len(job["entries"])):
            for lane in job["lanes"]:
                results = lane["searcher"].search_many_finish(
                    lane["state"], raise_errors=False)
                for i, res in zip(lane["ix"], results):
                    if isinstance(res, Exception):
                        job["slots"][i] = ("error", res)
                        continue
                    p = job["fmt"][i]
                    try:
                        if p.get("knn_clamp") is not None:
                            # starved filtered-ANN retrieval re-runs solo
                            # on the exact scan (same escalation as
                            # _search_inner, so wave == solo results)
                            if self._knn_mark_starved(
                                    p["knn_query"],
                                    len(res.doc_ids) + p["from_"],
                                    p["eff_size"] + p["from_"]):
                                tr = job["meta"]["transitions"]
                                tr["dispatch"] += 1
                                tr["fetch"] += 1
                                res = lane["searcher"].search(
                                    p["knn_query"], size=p["eff_size"],
                                    from_=p["from_"], aggs=p["eff_aggs"])
                            res.total = min(res.total, p["knn_clamp"])
                        job["slots"][i] = ("resp", self._format_generic_hits(
                            res, p["tth"],
                            p.get("aggs_request"), p.get("had_pipeline"),
                        ))
                    except Exception as ex:  # noqa: BLE001
                        job["slots"][i] = ("error", ex)
            # deferred term lanes (PR 11): finish the merged programs and
            # build responses here, after the wave's single fetch
            import numpy as _np

            for tl in job.get("term_lanes", ()):
                members = tl["members"]
                fld, k = tl["fld"], tl["k"]
                try:
                    from ..parallel.sharded import msearch_wave_finish

                    (v, sh, dc, tt), tier = msearch_wave_finish(tl["st"])
                    job["meta"]["term_packed"] += len(members)
                    job["meta"]["term_waves"].append(
                        (len(members), int(tier)))
                    for row, (i, _terms) in enumerate(members):
                        p = job["fmt"][i]
                        nvalid = int(_np.isfinite(v[row]).sum())
                        take = list(range(min(nvalid, k)))[
                            p["from_"]: p["size"] + p["from_"]]
                        hits = []
                        for j in take:
                            doc_id, src = self.shard_docs[
                                int(sh[row][j])][int(dc[row][j])]
                            hits.append({"_index": self.name,
                                         "_id": doc_id,
                                         "_score": float(v[row][j]),
                                         "_source": src})
                        hits_obj = {
                            "total": {"value": int(tt[row]),
                                      "relation": "eq"},
                            "max_score": (float(v[row][0]) if nvalid
                                          else None),
                            "hits": hits,
                        }
                        if p["tth"] is False:
                            del hits_obj["total"]
                        job["slots"][i] = ("resp", {"hits": hits_obj})
                except Exception as ex:  # noqa: BLE001
                    for i, _terms in members:
                        job["slots"][i] = ("error", ex)
            t = job.get("tiered")
            if t is not None:
                base = t["base"][0].search_many_finish(
                    t["base"][1], raise_errors=False)
                tails = [s.search_many_finish(st, raise_errors=False)
                         for s, st in t["tails"]]
                for pos, i in enumerate(t["ix"]):
                    rb = base[pos]
                    rts = [tl[pos] for tl in tails]
                    err = next((r for r in (rb, *rts)
                                if isinstance(r, Exception)), None)
                    if err is not None:
                        job["slots"][i] = ("error", err)
                        continue
                    p = job["fmt"][i]
                    try:
                        job["slots"][i] = ("resp", self._tiered_merge(
                            rb, rts, p["size"], p["from_"],
                            p["tth"], t["tail_shard_docs"]))
                    except Exception as ex:  # noqa: BLE001
                        job["slots"][i] = ("error", ex)
            # extra device rounds taken during finish (fused escalation,
            # two-pass aggs) roll into the wave's transition meta —
            # counted, never hidden
            tr = job["meta"]["transitions"]
            extra_states = [lane["state"] for lane in job["lanes"]]
            extra_states += [tl["st"].get("merged")
                             for tl in job.get("term_lanes", ())]
            if t is not None:
                extra_states += [t["base"][1]] + [st for _s, st
                                                  in t["tails"]]
            for s in extra_states:
                if s is None:
                    continue
                tr["dispatch"] += s.pop("extra_dispatches", 0)
                tr["fetch"] += s.pop("extra_fetches", 0)
            took_ms = (time.monotonic() - job["t0"]) * 1000
            out = []
            for i, slot in enumerate(job["slots"]):
                if slot is None:  # cannot happen; fail loudly per entry
                    slot = ("error",
                            RuntimeError("serving wave lost an entry"))
                kind, payload = slot
                if kind == "resp":
                    # the wave wall IS each member's service time; slowlog
                    # and query_time attribute it per entry
                    self.counters["query_time_ms"] = (
                        self.counters.get("query_time_ms", 0)
                        + int(took_ms))
                    q = job["entries"][i].get("query")
                    record_search_slowlog(
                        self.name, self.settings, took_ms,
                        json.dumps(q)[:512] if q is not None else "{}")
                out.append(payload)
        return out

    def search_wave(self, entries: list[dict]) -> list:
        """Convenience: begin + fetch + finish in one call (bench/tests;
        the serving scheduler drives the three stages separately)."""
        job = self.search_wave_begin(entries)
        self.search_wave_fetch(job)
        return self.search_wave_finish(job)

    def count(self, query=None) -> int:
        self._maybe_refresh()
        if self._tails:
            node = self._tier_node(query)
            if node is not None:
                q = query if isinstance(query, dict) or query is None \
                    else node
                return self._searcher.count(q) + sum(
                    seg.searcher.count(q) for seg in self._tails)
        return self.searcher.count(query)

    def explain(self, doc_id: str, query=None) -> dict:
        """Score breakdown for one document (reference behavior:
        action/explain/TransportExplainAction.java — runs the query against
        the single shard holding the doc and renders Explanation). The TPU
        path re-scores with the query filtered to the doc id; per-clause
        detail comes from scoring each top-level clause the same way."""
        if self.get_doc(doc_id) is None:
            raise DocumentMissingError(f"[{doc_id}]: document missing", index=self.name)
        self._maybe_refresh()
        from ..query.dsl import parse_query

        def score_of(q):
            wrapped = {
                "bool": {
                    "must": [q if q is not None else {"match_all": {}}],
                    "filter": [{"ids": {"values": [doc_id]}}],
                }
            }
            # explain's per-clause breakdown must be exact BM25, never
            # the quantized impact tier (query/nodes.mark_exact — the
            # impact escalation contract)
            from ..query.nodes import mark_exact

            node = mark_exact(parse_query(wrapped, self.mappings))
            res = self.searcher.search(node, size=1)
            if res.total == 0:
                return None
            return float(res.scores[0])

        top = score_of(query)
        if top is None:
            return {
                "_id": doc_id, "matched": False,
                "explanation": {"value": 0.0, "description": "no matching term", "details": []},
            }
        details = []
        # per-clause detail for bool queries: score each scoring clause alone
        if isinstance(query, dict) and "bool" in query:
            b = query["bool"]
            clauses = (b.get("must") or []) + (b.get("should") or [])
            if not isinstance(clauses, list):
                clauses = [clauses]
            for c in clauses:
                s = score_of(c)
                if s is not None:
                    details.append({
                        "value": s,
                        "description": f"clause {json.dumps(c, separators=(',', ':'))[:120]}",
                        "details": [],
                    })
        return {
            "_id": doc_id, "matched": True,
            "explanation": {
                "value": top,
                "description": "sum of:" if details else "score, computed from query",
                "details": details,
            },
        }

    def close(self):
        # index teardown (delete/close): its cached shard results can never
        # be served again — return their memory to the breaker now
        self._invalidate_request_cache()
        if self._wal is not None:
            self._wal.close()
            self._wal = None


class Engine:
    """Multi-index node engine (the analog of the per-node IndicesService,
    reference: indices/IndicesService registry of IndexShard instances)."""

    def __init__(self, data_path: str | None = None):
        from ..cluster.metadata import MetadataStore
        from ..ingest import IngestService
        from ..tasks import TaskManager

        from .contexts import ContextRegistry

        self.data_path = data_path
        self.indices: dict[str, EsIndex] = {}
        self.ingest = IngestService()
        self.ingest.engine = self  # enrich processors look policies up here
        from ..inference import InferenceService

        self.inference = InferenceService()
        self.tasks = TaskManager()
        from ..tasks.persistent import PersistentTasksService

        self.persistent = PersistentTasksService(self)
        self._security = None
        self._ml = None
        self._monitoring = None
        self._serving = None
        self._superpacks = None
        self._watcher = None
        self._slo = None
        self._profiler = None
        self._refresh_recorder = None
        self._esql_recorder = None
        self._device_degradation = None
        self._metering = None
        self.meta = MetadataStore(data_path)
        self.contexts = ContextRegistry()
        from ..common.breaker import CircuitBreakerService
        from ..common.settings import ClusterSettings, default_cluster_settings
        from ..snapshots import SnapshotService

        self.snapshots = SnapshotService(self)
        self.settings = ClusterSettings(default_cluster_settings(), data_path)
        self.breakers = CircuitBreakerService(limits={
            "total": self.settings.get("indices.breaker.total.limit"),
            "fielddata": self.settings.get("indices.breaker.fielddata.limit"),
            "request": self.settings.get("indices.breaker.request.limit"),
            "model_inference": self.settings.get(
                "indices.breaker.model_inference.limit"),
            "esql.materialization": self.settings.get(
                "indices.breaker.esql.materialization.limit"),
        })
        for key, child in (("indices.breaker.total.limit", "total"),
                           ("indices.breaker.fielddata.limit", "fielddata"),
                           ("indices.breaker.request.limit", "request"),
                           ("indices.breaker.model_inference.limit",
                            "model_inference"),
                           ("indices.breaker.esql.materialization.limit",
                            "esql.materialization")):
            self.settings.add_consumer(
                key, lambda raw, c=child: self.breakers.set_limit(c, raw)
            )
        # shard request cache (cache/): bind THIS engine's request breaker
        # as the accounting sink (entries admitted earlier keep releasing
        # through whichever breaker charged them) and expose the dynamic
        # enable/size settings
        from ..cache import request_cache
        from ..common.settings import parse_bytes

        rc = self.request_cache = request_cache()

        def _rc_account(delta: int):
            if delta >= 0:
                self.breakers.add_estimate("request", delta, "request_cache")
            else:
                self.breakers.release("request", -delta)

        rc.bind_breaker(_rc_account)
        rc.set_enabled(self.settings.get("indices.requests.cache.enable"))
        rc.set_max_bytes(parse_bytes(
            self.settings.get("indices.requests.cache.size"),
            self.breakers.total))
        self.settings.add_consumer(
            "indices.requests.cache.enable", rc.set_enabled)
        self.settings.add_consumer(
            "indices.requests.cache.size",
            lambda raw: rc.set_max_bytes(
                parse_bytes(raw, self.breakers.total)))
        # shared blob cache for mounted searchable snapshots, byte-
        # accounted under the request breaker (frozen-tier RAM budget)
        from ..snapshots.blobcache import SharedBlobCache

        def _cache_breaker(delta: int):
            if delta >= 0:
                self.breakers.add_estimate(
                    "request", delta, "searchable_snapshot_cache")
            else:
                self.breakers.release("request", -delta)

        self.blob_cache = SharedBlobCache(breaker=_cache_breaker)
        if data_path:
            os.makedirs(os.path.join(data_path, "indices"), exist_ok=True)
            for name in sorted(os.listdir(os.path.join(data_path, "indices"))):
                d = os.path.join(data_path, "indices", name)
                if os.path.isdir(d) and os.path.exists(os.path.join(d, "meta.json")):
                    self.indices[name] = EsIndex.open(
                        name, d, breaker_account=self._pack_accounter(name)
                    )
        # self-monitoring (monitoring/): dynamic enable/interval consumers
        # route through the lazy property; a persisted enabled=true starts
        # collection at boot (after index recovery, so the first tick sees
        # the recovered indices)
        self.settings.add_consumer(
            "xpack.monitoring.collection.enabled",
            lambda v: self.monitoring.set_enabled(v))
        self.settings.add_consumer(
            "xpack.monitoring.collection.interval",
            lambda v: self.monitoring.set_interval(v))
        if self.settings.get("xpack.monitoring.collection.enabled"):
            self.monitoring.start()
        # serving front end (serving/): dynamic consumers route through
        # the lazy property so a node serving no coalesced traffic never
        # builds the scheduler threads
        self.settings.add_consumer(
            "serving.enabled", lambda v: self.serving.set_enabled(v))
        for key, attr in (("serving.max_wave", "set_max_wave"),
                          ("serving.coalesce.max_wait", "set_max_wait"),
                          ("serving.queue.max_depth", "set_queue_depth"),
                          ("serving.tenant.weights", "set_tenant_weights"),
                          ("serving.merge.weight", "set_merge_weight"),
                          ("serving.flight_recorder.size",
                           "set_flight_recorder_size")):
            self.settings.add_consumer(
                key, lambda v, a=attr: getattr(self.serving, a)(v))
        if self.settings.get("serving.enabled"):
            self.serving.set_enabled(True)

        def _wave_min_tier(v):
            from ..ops.batched import BatchTermSearcher

            BatchTermSearcher.WAVE_MIN_TIER = \
                1 << (max(int(v), 1) - 1).bit_length()

        self.settings.add_consumer("serving.wave.min_tier", _wave_min_tier)
        _wave_min_tier(self.settings.get("serving.wave.min_tier"))

        def _solo_min_rows_tier(v):
            from ..ops.batched import BatchTermSearcher
            from ..query import nodes

            nodes.MATCH_MIN_ROWS = BatchTermSearcher.pow2_tier(v)

        self.settings.add_consumer("search.solo.min_rows_tier",
                                   _solo_min_rows_tier)
        _solo_min_rows_tier(self.settings.get("search.solo.min_rows_tier"))
        # adaptive execution planner (PR 18, planner/): push the dynamic
        # knobs into the process-wide planner singleton — the dispatch
        # sites consult it on every arm choice, so a settings update
        # takes effect on the next wave
        from ..planner import execution_planner

        def _planner_settings(_v=None):
            execution_planner().configure(
                enabled=bool(self.settings.get("planner.enabled")),
                alpha=float(self.settings.get("planner.ema.alpha")),
                knn_target_ms=float(
                    self.settings.get("planner.knn.target_ms")),
                cache_min_recompute_us=float(
                    self.settings.get("planner.cache.min_recompute_us")))

        for key in ("planner.enabled", "planner.ema.alpha",
                    "planner.knn.target_ms",
                    "planner.cache.min_recompute_us"):
            self.settings.add_consumer(key, _planner_settings)
        _planner_settings()
        # per-tenant metering (PR 19, tenancy/metering.py): the fair-
        # share knobs route through the lazy serving property (firing
        # only on dynamic updates — a node serving no traffic never
        # builds the scheduler), the ledger bound through the lazy meter
        def _fairshare_settings(_v=None):
            self.serving.configure_fairshare(
                enabled=self.settings.get("planner.tenant.fairshare"),
                budget_ms_per_s=self.settings.get(
                    "slo.tenant.device_ms_per_s"),
                min_factor=self.settings.get(
                    "planner.tenant.fairshare.min_factor"))

        for key in ("planner.tenant.fairshare",
                    "planner.tenant.fairshare.min_factor",
                    "slo.tenant.device_ms_per_s"):
            self.settings.add_consumer(key, _fairshare_settings)
        self.settings.add_consumer(
            "metering.tenant.top_k",
            lambda v: self.metering.set_top_k(v))
        # scheduled watcher (xpack/watcher.py): a persisted watcher-driver
        # task resumes its ticker at boot, so watches keep firing after a
        # node restart without any request touching the watcher surface
        self.settings.add_consumer(
            "xpack.watcher.enabled", self._watcher_enabled_changed)
        if self.settings.get("xpack.watcher.enabled") and any(
                t.get("name") == "watcher" and not t.get("stopped")
                for t in getattr(self.meta, "persistent_tasks", {}).values()):
            from ..xpack.watcher import ensure_executor

            ensure_executor(self)

    def _watcher_enabled_changed(self, value) -> None:
        if not value:
            self.persistent.stop_ticker()
        elif any(t.get("name") == "watcher" and not t.get("stopped")
                 for t in getattr(self.meta, "persistent_tasks", {}).values()):
            from ..xpack.watcher import ensure_executor

            ensure_executor(self)

    @property
    def security(self):
        from ..security import SecurityService

        if self._security is None:
            self._security = SecurityService(self)
        return self._security

    @property
    def ml(self):
        """ML subsystem (ml/): lazy like security — jobs/datafeeds live in
        cluster metadata, so a node serving no ML traffic never builds the
        service. First access registers the persistent-task executor."""
        from ..ml import MlService

        if self._ml is None:
            self._ml = MlService(self)
            self.settings.add_consumer(
                "xpack.ml.state_repository_path",
                lambda _v: self._ml.invalidate_repo_cache())
        return self._ml

    @property
    def monitoring(self):
        """Self-monitoring pipeline (monitoring/): lazy — built on first
        access or when xpack.monitoring.collection.enabled flips on (the
        __init__ consumers route through this property)."""
        from ..monitoring import MonitoringService

        if self._monitoring is None:
            self._monitoring = MonitoringService(self)
        return self._monitoring

    @property
    def serving(self):
        """Continuous-batching serving front end (serving/): lazy — the
        admission queue + wave scheduler between REST and the executor."""
        from ..serving import ServingService

        if self._serving is None:
            self._serving = ServingService(self)
        return self._serving

    @property
    def superpacks(self):
        """Tenant superpacks (tenancy/): lazy — the size-class-bucketed
        shared device layouts serving many small tenant indices from one
        compiled tenant-gather program family (PR 17)."""
        from ..tenancy import SuperpackManager

        if self._superpacks is None:
            self._superpacks = SuperpackManager(self)
        return self._superpacks

    @property
    def watcher(self):
        """Scheduled alerting (xpack/watcher.py): lazy — watches live in
        cluster metadata; building the service registers the persistent-
        task executor and the post-tick export flush."""
        from ..xpack.watcher import WatcherExecutor, WatcherService

        if self._watcher is None:
            self._watcher = WatcherService(self)
            if "watcher" not in self.persistent.executors:
                self.persistent.register_executor("watcher", WatcherExecutor())
            self.persistent.post_tick_hooks.append(
                self._watcher.flush_exports)
        return self._watcher

    @property
    def slo(self):
        """SLO engine (monitoring/slo.py): lazy — objectives come from
        dynamic settings, evaluation reads the live registry/device
        state."""
        from ..monitoring.slo import SloEngine

        if self._slo is None:
            self._slo = SloEngine(self)
        return self._slo

    @property
    def profiler(self):
        """Bounded jax.profiler capture service (monitoring/profiler.py):
        lazy — built on the first REST/watcher capture request; trace
        dirs are pruned by the monitoring CleanerService."""
        from ..monitoring.profiler import ProfilerService

        if self._profiler is None:
            self._profiler = ProfilerService(self)
        return self._profiler

    @property
    def device_degradation(self):
        """Device-OOM graceful degradation (common/resilience.py, PR 14):
        lazy — built at the first RESOURCE_EXHAUSTED; owns the staged
        response (cache eviction, serving-wave halving + recovery ramp)
        and the degradation event log."""
        from ..common.resilience import DeviceDegradation

        if self._device_degradation is None:
            self._device_degradation = DeviceDegradation(self)
        return self._device_degradation

    @property
    def metering(self):
        """Per-tenant resource ledger (tenancy/metering.py, PR 19):
        per-engine — like the refresh recorder, in-process multi-node
        fixtures must never mix nodes' tenants. Fed by the serving
        waves' exact apportioned shares; read by `_nodes/stats`,
        `GET /_tenants/stats`, the TSDB collector, and the SLO engine."""
        from ..tenancy.metering import TenantMeter

        if self._metering is None:
            try:
                top_k = int(self.settings.get("metering.tenant.top_k"))
            except Exception:  # noqa: BLE001 - engines without the setting
                top_k = 16
            self._metering = TenantMeter(top_k=top_k)
        return self._metering

    def tenant_stats(self) -> dict:
        """The `tenants` section (`_nodes/stats`, `GET /_tenants/stats`):
        the metering ledger joined with the point-in-time per-tenant
        state the ledger doesn't own — superpack HBM-resident bytes per
        lane (exact: the member's share of its shared pack) and
        request-cache bytes held per superpack lane (exact per lane;
        non-superpack cache bytes are not tenant-scoped and stay
        unattributed — see DIVERGENCES.md 'Tenant metering')."""
        from ..tenancy.metering import normalize_tenant

        out = self.metering.stats()
        rows = out["tenants"]
        mgr = self._superpacks
        if mgr is not None:
            try:
                cache_by_member = mgr.cache_bytes_per_member()
                for name in mgr.member_names():
                    t = normalize_tenant(name)
                    row = rows.get(t)
                    if row is None:
                        continue
                    ms = mgr.member_stats(name) or {}
                    row["superpack_hbm_bytes"] = int(
                        ms.get("hbm_bytes_per_tenant", 0))
                    row.setdefault("cache", {})["bytes_held"] = int(
                        cache_by_member.get(name, 0))
            except Exception:  # noqa: BLE001 - stats must never fail
                pass
        return out

    @property
    def refresh_recorder(self):
        """Write-path RefreshProfile ring (monitoring/refresh_profile.py,
        PR 13): per-engine so in-process multi-node fixtures never mix
        nodes' refresh histories. Sized by the dynamic
        `indexing.profile.size` setting."""
        from ..monitoring.refresh_profile import RefreshRecorder

        if self._refresh_recorder is None:
            size = self.settings.get("indexing.profile.size") or 256
            self._refresh_recorder = RefreshRecorder(size)
            self.settings.add_consumer(
                "indexing.profile.size",
                self._refresh_recorder.set_size)
        return self._refresh_recorder

    @property
    def esql_recorder(self):
        """ESQL query-profile ring (esql/profile.py, PR 20): per-engine
        for the same reason as the refresh recorder — in-process
        multi-node fixtures must never mix nodes' query streams."""
        from ..esql.profile import EsqlRecorder

        if self._esql_recorder is None:
            self._esql_recorder = EsqlRecorder()
        return self._esql_recorder

    def indexing_stats(self) -> dict:
        """The `_nodes/stats` `indexing` section: refresh/merge counts +
        cumulative stage millis from the recorder, plus the CURRENT
        node-wide tail fraction and refresh lag computed from the live
        index state (not the last profile — a node idle since its last
        refresh still reports its true lag). Hidden/system indices are
        excluded from the tail/lag aggregation so the monitoring
        pipeline's own 1s-refresh indices never mask a user-index
        breach."""
        base = tail = 0
        lag = 0.0
        per_index = {}
        for name, idx in self.indices.items():
            if name.startswith(".") or idx.settings.get("hidden"):
                continue
            try:
                t = idx.tier_stats()
            except Exception:  # noqa: BLE001 - stats must never fail
                continue
            base += t["base_docs"]
            tail += t["tail_docs"]
            lag = max(lag, idx.refresh_lag_ms())
            if t["tail_docs"]:
                per_index[name] = t
        total = base + tail
        out = self.refresh_recorder.indexing_stats()
        out["tail_fraction"] = round(tail / total, 6) if total else 0.0
        out["tail_docs"] = tail
        out["base_docs"] = base
        out["refresh_lag_ms"] = round(lag, 3)
        from ..native import get_lib

        # which index accumulator this process loaded (the pure-Python
        # fallback of a failed g++ build is ~4x slower and silent)
        out["accumulator"] = "native" if get_lib() is not None else "python"
        if per_index:
            out["tail_by_index"] = per_index
        from ..telemetry import metrics

        metrics.gauge_set("es.indexing.tail_fraction", out["tail_fraction"])
        metrics.gauge_set("es.indexing.refresh_lag_ms", out["refresh_lag_ms"])
        return out

    def serving_if_enabled(self):
        """The serving service iff coalescing is enabled — without
        building the service just to learn it's off (the per-request hot
        path check)."""
        if self._serving is not None:
            return self._serving if self._serving.enabled else None
        if self.settings.get("serving.enabled"):
            return self.serving
        return None

    def superpacks_if_enabled(self):
        """The superpack manager iff tenant superpacks are on — without
        building it just to learn they're off (checked once per wave)."""
        from ..tenancy import superpack_enabled

        if not superpack_enabled(self.settings):
            return None
        return self.superpacks

    def schedule_tail_merge(self, idx) -> bool:
        """Schedule one LSM tail-segment fold for `idx` (PR 15). With
        the serving front end up, the DEVICE merge rides the serving
        queue as the low-weight `_merge` internal tenant under the PR-6
        weighted-RR admission — heavy indexing and heavy search share
        the chip through ONE scheduler, under the existing breakers and
        `slo.write.*` floors; otherwise the fold runs inline. Merge
        failures are swallowed and counted (`merge_failures`): the
        atomic-install contract means a failed fold leaves every
        segment serving and a later refresh reschedules.

        -> True when a background merge was queued (or already is)."""
        def _fold_inline():
            try:
                idx._merge_tail_segments()
            except Exception:  # noqa: BLE001 - fold is housekeeping
                idx.counters["merge_failures"] = (
                    idx.counters.get("merge_failures", 0) + 1)

        svc = self.serving_if_enabled()
        if svc is None:
            _fold_inline()
            return False
        if idx._merge_inflight:
            return True
        idx._merge_inflight = True
        try:
            fut = svc.submit_merge(lambda: idx._merge_tail_segments(),
                                   index=idx.name)
        except Exception:  # noqa: BLE001 - shed/stopped front end
            idx._merge_inflight = False
            _fold_inline()
            return False

        def _done(f):
            idx._merge_inflight = False
            try:
                err = f.exception()
            except Exception:  # noqa: BLE001 - cancelled future
                err = None
            if err is not None:
                idx.counters["merge_failures"] = (
                    idx.counters.get("merge_failures", 0) + 1)

        fut.add_done_callback(_done)
        return True

    def _pack_accounter(self, name: str):
        return lambda n: self.breakers.set_steady(
            "fielddata", name, n, label=f"index [{name}] packs"
        )

    def _dir_for(self, name: str) -> str | None:
        if not self.data_path:
            return None
        return os.path.join(self.data_path, "indices", name)

    def create_index(self, name: str, mappings: dict | None = None,
                     settings: dict | None = None, aliases: dict | None = None) -> EsIndex:
        if name in self.indices:
            raise IndexAlreadyExistsError(name)
        if name in self.meta.aliases:
            raise IllegalArgumentError(
                f"an alias with the name [{name}] already exists"
            )
        if not name or name != name.lower() or name.startswith(("_", "-", "+")):
            raise IllegalArgumentError(f"invalid index name [{name}]")
        # composable index templates apply first, request body overlays
        # (reference behavior: MetadataCreateIndexService applies the matched
        # v2 template's resolved settings/mappings/aliases under the request)
        from ..cluster.metadata import deep_merge

        composed = self.meta.compose_for_index(name)
        if composed:
            tset = dict(composed.get("settings") or {})
            if "index" in tset:
                tset.update(tset.pop("index"))
            tset = {k.removeprefix("index."): v for k, v in tset.items()}
            settings = deep_merge(tset, settings or {})
            mappings = deep_merge(composed.get("mappings") or {}, mappings or {})
            aliases = {**(composed.get("aliases") or {}), **(aliases or {})}
        m = Mappings(mappings or {})
        # validate aliases BEFORE creating the index so a bad alias leaves no
        # half-created state behind
        for alias, props in (aliases or {}).items():
            if not alias or alias in ("_all", "*") or alias in self.indices or alias == name:
                raise IllegalArgumentError(f"invalid alias name [{alias}]")
            if isinstance(props, dict) and props.get("filter"):
                from ..query.dsl import parse_query

                parse_query(props["filter"], m)
        settings = dict(settings or {})
        settings.setdefault("creation_date", int(time.time() * 1000))
        # resolve named synonym sets (PUT /_synonyms/{set}) into the
        # analyzer filter specs before the index builds its registry
        for fspec in ((settings.get("analysis") or {}).get("filter") or {}).values():
            if isinstance(fspec, dict) and fspec.get("synonyms_set"):
                rules = self.meta.extras.get("synonym_sets", {}).get(
                    fspec["synonyms_set"])
                if rules is None:
                    raise IllegalArgumentError(
                        f"synonyms set [{fspec['synonyms_set']}] not found")
                fspec["_resolved_set"] = list(rules)
        idx = EsIndex(name, m, settings, self._dir_for(name),
                      breaker_account=self._pack_accounter(name))
        idx.engine = self
        self.indices[name] = idx
        for alias, props in (aliases or {}).items():
            self.meta.put_alias(name, alias, props)
        return idx

    def get_index(self, name: str) -> EsIndex:
        idx = self.indices.get(name)
        if idx is None:
            raise IndexNotFoundError(name)
        return idx

    def resolve_write_index(self, name: str) -> str:
        """Alias/data-stream → its write index; concrete names pass
        through."""
        if name in self.meta.data_streams:
            return self.meta.data_streams[name]["indices"][-1]
        if name in self.meta.aliases and name not in self.indices:
            return self.meta.write_index_of(name)
        return name

    def resolve_search(self, expression, ignore_unavailable: bool = False,
                       allow_no_indices: bool = True) -> list[tuple[EsIndex, dict | None]]:
        """Resolve an index expression to [(index, alias_filter)]."""
        targets = self.meta.search_targets(
            expression, list(self.indices), ignore_unavailable, allow_no_indices
        )
        explicit = set()
        if isinstance(expression, str):
            explicit = {p for p in expression.split(",")
                        if p and "*" not in p and "?" not in p}
        elif isinstance(expression, (list, tuple)):
            explicit = {p for p in expression if "*" not in p and "?" not in p}
        out = []
        for n, f in targets:
            idx = self.get_index(n)
            if idx.settings.get("closed"):
                from ..utils.errors import IndexClosedError

                if n in explicit:
                    # a concretely named closed index is an error (ES default
                    # forbid_closed_indices); wildcard matches skip silently
                    raise IndexClosedError(f"closed index [{n}]")
                continue
            out.append((idx, f))
        return out

    def index_health(self, name: str) -> str:
        """Per-index health derived from searcher/replica state (PR 9 —
        the `/_cluster/health`, `_cat/*` rows and the health report's
        shards_availability indicator all read THIS, so they can never
        disagree): red when the index has no live searcher (it cannot
        serve), yellow when replica copies are configured but this
        single-process engine has no second node to assign them to
        (reference ClusterHealthStatus semantics), green otherwise."""
        idx = self.indices.get(name)
        if idx is None:
            return "red"
        if idx._searcher is None and idx._tail is None:
            return "red"
        try:
            replicas = int(idx.settings.get("number_of_replicas") or 0)
        except (TypeError, ValueError):
            replicas = 0
        return "yellow" if replicas > 0 else "green"

    def cluster_health(self, expression: str | None = None) -> dict:
        """ES-shaped cluster health over this engine's indices (the
        reference's TransportClusterHealthAction counts). Per-index
        sections ride the `indices` key; REST decides whether to expose
        them (`level=indices`)."""
        names = sorted(self.indices)
        if expression:
            try:
                names = sorted(idx.name for idx, _f in
                               self.resolve_search(expression))
            except Exception:  # noqa: BLE001 - unknown index: empty scope
                names = []
        per_index = {}
        active = unassigned_replicas = red_shards = 0
        for n in names:
            idx = self.indices[n]
            h = self.index_health(n)
            try:
                replicas = int(idx.settings.get("number_of_replicas") or 0)
            except (TypeError, ValueError):
                replicas = 0
            if h == "red":
                red_shards += idx.num_shards
            else:
                active += idx.num_shards
            unassigned_replicas += replicas * idx.num_shards
            per_index[n] = {
                "status": h,
                "number_of_shards": idx.num_shards,
                "number_of_replicas": replicas,
                "active_shards": 0 if h == "red" else idx.num_shards,
                "unassigned_shards": (replicas * idx.num_shards
                                      + (idx.num_shards if h == "red" else 0)),
            }
        from ..xpack.health import worst_status

        status = worst_status(v["status"] for v in per_index.values())
        total = active + red_shards + unassigned_replicas
        return {
            "cluster_name": "elasticsearch-tpu",
            "status": status,
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "active_primary_shards": active,
            "active_shards": active,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": unassigned_replicas + red_shards,
            "active_shards_percent_as_number": (
                100.0 if total == 0 else round(100.0 * active / total, 1)),
            "indices": per_index,
        }

    def get_or_autocreate(self, name: str) -> EsIndex:
        """Auto-create on first write, like the reference's
        action.auto_create_index default (TransportBulkAction auto-create).
        A name matching a data_stream template auto-creates the stream
        (reference behavior: TransportBulkAction data-stream auto-create)."""
        if name not in self.indices and name not in self.meta.aliases \
                and name not in self.meta.data_streams:
            from .lifecycle import _matching_ds_template, create_data_stream

            if _matching_ds_template(self, name) is not None:
                create_data_stream(self, name)
        name = self.resolve_write_index(name)
        if name not in self.indices:
            return self.create_index(name)
        return self.indices[name]

    def delete_index(self, name: str):
        if name in self.meta.aliases and name not in self.indices:
            raise IllegalArgumentError(
                f"The provided expression [{name}] matches an alias, specify the "
                "corresponding concrete indices instead."
            )
        idx = self.get_index(name)
        idx.close()
        del self.indices[name]
        if self._superpacks is not None:
            # free the lane + drop ONLY this tenant's cache entries
            self._superpacks.evict(name)
        self.meta.drop_index(name)
        self.breakers.set_steady("fielddata", name, 0)
        d = self._dir_for(name)
        if d and os.path.isdir(d):
            import shutil

            shutil.rmtree(d)

    # ---- alias management (reference: TransportIndicesAliasesAction) -----

    def update_aliases(self, actions: list[dict]):
        """POST /_aliases action list: add / remove / remove_index."""
        parsed = []
        for a in actions:
            if not isinstance(a, dict) or len(a) != 1:
                raise IllegalArgumentError("malformed alias action")
            (kind, body), = a.items()
            if kind not in ("add", "remove", "remove_index"):
                raise IllegalArgumentError(f"unknown alias action [{kind}]")
            idx_expr = body.get("indices", body.get("index"))
            if idx_expr is None:
                raise IllegalArgumentError("alias action requires an index")
            names = self.meta.resolve_expression(idx_expr, list(self.indices))
            if kind == "remove_index":
                parsed.append((kind, names, None, body))
                continue
            aliases = body.get("aliases", body.get("alias"))
            if aliases is None:
                raise IllegalArgumentError("alias action requires an alias")
            if isinstance(aliases, str):
                aliases = [aliases]
            parsed.append((kind, names, aliases, body))
        # validate everything first, then apply — the whole action list is one
        # atomic cluster-state update in the reference
        # (TransportIndicesAliasesAction submits a single state task)
        import fnmatch as _fn

        from ..query.dsl import parse_query

        staged_adds: set[tuple[str, str]] = set()
        for kind, names, aliases, body in parsed:
            if kind == "remove_index":
                continue
            for alias in aliases:
                if kind == "add":
                    if not alias or alias in ("_all", "*"):
                        raise IllegalArgumentError(f"invalid alias name [{alias}]")
                    if alias in self.indices:
                        raise IllegalArgumentError(
                            f"an index exists with the same name as the alias [{alias}]"
                        )
                    for n in names:
                        if body.get("filter"):
                            parse_query(body["filter"], self.indices[n].mappings)
                        staged_adds.add((n, alias))
                elif body.get("must_exist", True):
                    for n in names:
                        present = any(
                            _fn.fnmatchcase(a, alias) and n in members
                            for a, members in self.meta.aliases.items()
                        ) or any(
                            _fn.fnmatchcase(a, alias) and n == i
                            for i, a in staged_adds
                        )
                        if not present:
                            raise ResourceNotFoundError(
                                f"aliases [{alias}] missing on index [{n}]"
                            )
        for kind, names, aliases, body in parsed:
            for n in names:
                if kind == "remove_index":
                    self.delete_index(n)
                    continue
                for alias in aliases:
                    if kind == "add":
                        self.meta.put_alias(n, alias, {
                            "filter": body.get("filter"),
                            "is_write_index": body.get("is_write_index"),
                            "routing": body.get("routing"),
                        })
                    else:
                        self.meta.remove_alias(n, alias, must_exist=False)
        return {"acknowledged": True}

    # ---- multi-index search (scatter/gather across indices) --------------

    def remote_clusters(self) -> dict[str, str]:
        """{alias: http_url} from cluster.remote.<alias>.seeds settings
        (reference behavior: transport/RemoteClusterService.java:63 — here
        the seed IS the remote's HTTP endpoint, since HTTP is the
        transport)."""
        out = {}
        for store in (self.settings.persistent, self.settings.transient):
            for key, raw in store.items():
                if not key.startswith("cluster.remote.") or raw is None:
                    continue
                rest = key[len("cluster.remote."):]
                alias, _, leaf = rest.partition(".")
                if leaf not in ("seeds", "proxy_address", "url"):
                    continue
                seed = raw[0] if isinstance(raw, list) and raw else raw
                if isinstance(seed, str) and seed:
                    if not seed.startswith("http"):
                        seed = f"http://{seed}"
                    out[alias] = seed
        return out

    def _search_remote(self, url: str, index_expr: str, alias: str, kwargs) -> dict:
        """One remote sub-search over HTTP (the CCS fan-out leg,
        TransportSearchAction.java:693-760)."""
        import urllib.request

        body = {}
        if kwargs.get("query") is not None:
            body["query"] = kwargs["query"]
        body["size"] = kwargs.get("size", 10) + kwargs.get("from_", 0)
        req = urllib.request.Request(
            f"{url}/{index_expr}/_search", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        for h in out["hits"]["hits"]:
            h["_index"] = f"{alias}:{h['_index']}"
        return out

    def search_multi(self, expression, *, ignore_unavailable=False,
                     allow_no_indices=True, **kwargs):
        """Search over an index expression. One concrete unfiltered target
        uses the index path directly; multiple targets fan out and merge at
        this coordinator (reference behavior: TransportSearchAction shards
        span all resolved indices; merge in SearchPhaseController). Parts
        like `remote:index` fan out to registered remote clusters (CCS)."""
        if isinstance(expression, str) and ":" in expression:
            remotes = self.remote_clusters()
            local_parts, remote_parts = [], []
            for part in expression.split(","):
                alias, _, rest = part.partition(":")
                if rest and alias in remotes:
                    remote_parts.append((alias, remotes[alias], rest))
                else:
                    local_parts.append(part)
            if remote_parts:
                if kwargs.get("aggs") or kwargs.get("knn") or kwargs.get("sort"):
                    raise IllegalArgumentError(
                        "cross-cluster search supports query/size only"
                    )
                subs = []
                if local_parts:
                    subs.append(self.search_multi(
                        ",".join(local_parts),
                        ignore_unavailable=ignore_unavailable,
                        allow_no_indices=allow_no_indices, **kwargs))
                for alias, url, rest in remote_parts:
                    subs.append(self._search_remote(url, rest, alias, kwargs))
                size = kwargs.get("size", 10)
                from_ = kwargs.get("from_", 0)
                all_hits = [h for r in subs for h in r["hits"]["hits"]]
                all_hits.sort(key=lambda h: (-(h["_score"] or 0.0),
                                             h["_index"], h["_id"]))
                totals = [r["hits"]["total"] for r in subs
                          if "total" in r["hits"]]
                max_scores = [r["hits"]["max_score"] for r in subs
                              if r["hits"].get("max_score") is not None]
                hits_obj = {
                    "max_score": max(max_scores) if max_scores else None,
                    "hits": all_hits[from_:from_ + size],
                }
                if len(totals) == len(subs):
                    hits_obj["total"] = {
                        "value": sum(t["value"] for t in totals),
                        "relation": ("gte" if any(
                            t.get("relation") == "gte" for t in totals)
                            else "eq"),
                    }
                return {
                    "hits": hits_obj,
                    "_clusters": {
                        "total": len(remote_parts) + (1 if local_parts else 0),
                        "successful": len(subs), "skipped": 0,
                    },
                }
        targets = self.resolve_search(expression, ignore_unavailable, allow_no_indices)
        if not targets:
            return {
                "hits": {"total": {"value": 0, "relation": "eq"},
                         "max_score": None, "hits": []},
            }

        def with_filter(query, alias_filter):
            if alias_filter is None:
                return query
            if query is None:
                return {"bool": {"filter": [alias_filter]}}
            return {"bool": {"must": [query], "filter": [alias_filter]}}

        if len(targets) == 1:
            idx, alias_filter = targets[0]
            kw = dict(kwargs)
            kw["query"] = with_filter(kw.get("query"), alias_filter)
            return idx.search(**kw)

        if kwargs.get("aggs"):
            raise IllegalArgumentError(
                "aggregations over multiple indices are not supported yet; "
                "target a single concrete index"
            )
        if kwargs.get("knn"):
            raise IllegalArgumentError(
                "knn over multiple indices is not supported yet"
            )
        size = kwargs.get("size", 10)
        from_ = kwargs.get("from_", 0)
        sub_results = []
        skipped_shards = 0
        failed_shards = 0
        shard_failures: list[dict] = []
        from ..common import faults
        from ..search.canmatch import can_match

        node_name = getattr(self.tasks, "node", "node-0")
        for idx, alias_filter in targets:
            kw = dict(kwargs)
            kw["query"] = with_filter(kw.get("query"), alias_filter)
            kw["size"] = size + from_
            kw["from_"] = 0
            # can-match pre-filter: a required range outside the index's
            # column bounds skips the whole index's shards (the reference's
            # CanMatchPreFilterSearchPhase, at index granularity — shards
            # of one index run as one SPMD program)
            if not can_match(idx, kw["query"]):
                skipped_shards += idx.num_shards
                continue
            # honest partial results (PR 14): one index's failure becomes
            # a _shards.failures entry, not the whole request's death —
            # the fan-out unit here is the index (its shards run as one
            # SPMD program), so the failure granularity matches it. The
            # REST layer decides partial-vs-fail from
            # allow_partial_search_results.
            try:
                faults.check("shard.search", index=idx.name,
                             node=node_name)
                sub_results.append(idx.search(**kw))
            except IllegalArgumentError:
                raise  # a malformed request is the caller's 400, not a
                # shard failure to paper over
            except Exception as ex:  # noqa: BLE001 - per-shard envelope
                failed_shards += idx.num_shards
                shard_failures.append({
                    "shard": 0, "index": idx.name, "node": node_name,
                    "reason": {"type": type(ex).__name__.lower(),
                               "reason": str(ex)[:512]},
                })
        if shard_failures and not sub_results:
            # every target failed: no partial to serve (the reference's
            # all-shards-failed SearchPhaseExecutionException)
            from ..utils.errors import SearchPhaseExecutionError

            raise SearchPhaseExecutionError(
                "all shards failed: " + "; ".join(
                    f"[{f['index']}] {f['reason']['reason']}"
                    for f in shard_failures),
                failures=shard_failures)
        # merge: total sums; hits re-sorted globally (score desc, or the
        # explicit sort's transformed keys which each sub-search returns in
        # hit["sort"]) — the coordinator-side TopDocs.merge of the reference
        from ..query.sort import parse_sort, is_score_only

        sort_fields = parse_sort(kwargs.get("sort"))
        all_hits = [h for r in sub_results for h in r["hits"]["hits"]]
        if is_score_only(sort_fields):
            all_hits.sort(key=lambda h: (-(h["_score"] or 0.0), h["_index"], h["_id"]))
        else:
            def key(h):
                # each field key is (missing_rank, value) so None (missing
                # field) orders per the sort's missing policy without ever
                # comparing across types
                ks = []
                for v, sf in zip(h["sort"], sort_fields):
                    if v is None:
                        rank = -1 if sf.missing == "_first" else 1
                        ks.append((rank, 0))
                    elif isinstance(v, str):
                        ks.append((0, _StrKey(v, sf.desc)))
                    elif isinstance(v, bool) or not isinstance(v, (int, float)):
                        ks.append((0, _StrKey(str(v), sf.desc)))
                    else:
                        ks.append((0, -v if sf.desc else v))
                return ks
            all_hits.sort(key=key)
        cfld = (kwargs.get("collapse") or {}).get("field") if isinstance(
            kwargs.get("collapse"), dict) else kwargs.get("collapse")
        if cfld:
            # cross-index group dedupe: keep the best hit per collapse key
            # (each sub-search already collapsed within its index)
            seen_keys = set()
            deduped = []
            for h in all_hits:
                ck = (h.get("fields") or {}).get(cfld, [None])[0]
                marker = ("null",) if ck is None else ("k", ck)
                if marker in seen_keys:
                    continue
                seen_keys.add(marker)
                deduped.append(h)
            all_hits = deduped
        totals = [r["hits"]["total"] for r in sub_results if "total" in r["hits"]]
        max_scores = [r["hits"]["max_score"] for r in sub_results
                      if r["hits"]["max_score"] is not None]
        hits_obj = {
            "max_score": max(max_scores) if max_scores else None,
            "hits": all_hits[from_:from_ + size],
        }
        if len(totals) == len(sub_results):
            hits_obj["total"] = {
                "value": sum(t["value"] for t in totals),
                "relation": ("gte" if any(
                    t.get("relation") == "gte" for t in totals) else "eq"),
            }
        out = {"hits": hits_obj, "skipped_shards": skipped_shards}
        if shard_failures:
            out["failed_shards"] = failed_shards
            out["shard_failures"] = shard_failures
            from ..common.resilience import node_resilience
            from ..telemetry import metrics

            node_resilience(node_name).count("partial_responses")
            metrics.counter_inc("es.resilience.partial_responses")
        return out

    # ---- scroll / point-in-time ------------------------------------------

    def _pins_for(self, expression) -> list:
        from .contexts import _Pin

        pins = []
        for idx, _ in self.resolve_search(expression):
            idx._maybe_refresh()
            searcher = idx.searcher  # merges any tail: pins are single-tier
            searcher._pinned = True  # incremental refresh must not mutate it
            pins.append(_Pin(idx.name, searcher, idx.shard_docs))
        return pins

    def open_pit(self, expression, keep_alive) -> str:
        """POST /{index}/_pit (reference: TransportOpenPointInTimeAction —
        opens reader contexts on every shard and returns a composite id)."""
        from .contexts import encode_pit_id

        ctx = self.contexts.open(self._pins_for(expression), keep_alive)
        return encode_pit_id(ctx.id)

    def close_pit(self, pit_id: str) -> bool:
        from .contexts import decode_pit_id

        return self.contexts.close(decode_pit_id(pit_id))

    def search_pit(self, pit_id: str, keep_alive=None, **kwargs):
        from .contexts import decode_pit_id, pinned

        ctx = self.contexts.get(decode_pit_id(pit_id), keep_alive)
        expression = ",".join(p.index_name for p in ctx.pins)
        with pinned(self, ctx):
            res = self.search_multi(expression, **kwargs)
        res["pit_id"] = pit_id
        return res

    def scroll_search(self, expression, scroll, **kwargs):
        """Initial ?scroll= search: pins the snapshot, returns page 1 and a
        scroll id (reference behavior: scroll reader contexts in
        SearchService; continuation via TransportSearchScrollAction)."""
        from .contexts import pinned

        pins = self._pins_for(expression)
        request = dict(kwargs)
        # scroll clients page until they've read hits.total: totals must be
        # exact, never a pruned lower bound (the reference rejects
        # track_total_hits in a scroll context and counts exactly)
        request["track_total_hits"] = True
        kwargs = request
        ctx = self.contexts.open(pins, scroll, request=request)
        with pinned(self, ctx):
            res = self.search_multi(expression, **kwargs)
        ctx.cursor = int(kwargs.get("from_") or 0) + len(res["hits"]["hits"])
        res["_scroll_id"] = ctx.id
        return res

    def continue_scroll(self, scroll_id: str, scroll=None):
        from .contexts import pinned

        ctx = self.contexts.get(scroll_id, scroll)
        kwargs = dict(ctx.request or {})
        kwargs["from_"] = ctx.cursor
        expression = ",".join(p.index_name for p in ctx.pins)
        with pinned(self, ctx):
            res = self.search_multi(expression, **kwargs)
        ctx.cursor += len(res["hits"]["hits"])
        res["_scroll_id"] = ctx.id
        return res

    def clear_scroll(self, scroll_ids) -> int:
        if scroll_ids in ("_all", None):
            return self.contexts.close_all()
        if isinstance(scroll_ids, str):
            scroll_ids = [scroll_ids]
        return sum(1 for sid in scroll_ids if self.contexts.close(sid))

    # ---- update / by-query ops / reindex ---------------------------------

    def update_doc_api(self, index_name: str, doc_id: str, body: dict,
                       pipeline: str | None = None) -> dict:
        """POST /{index}/_update/{id}: doc merge, scripted update, upsert,
        doc_as_upsert, detect_noop (reference behavior:
        action/update/UpdateHelper.java prepare/prepareUpdateScriptRequest)."""
        idx = self.get_or_autocreate(index_name)
        if idx.ts_mode is not None:
            raise IllegalArgumentError(
                f"update is not supported because the destination index "
                f"[{index_name}] is in time series mode")
        e = idx.docs.get(doc_id)
        exists = e is not None and e.alive
        doc = body.get("doc")
        script = body.get("script")
        if doc is not None and script is not None:
            raise IllegalArgumentError("can't provide both script and doc")
        if doc is None and script is None:
            raise IllegalArgumentError("script or doc is missing")
        if not exists:
            if body.get("doc_as_upsert") and doc is not None:
                r = idx.index_doc(doc_id, dict(doc))
                return {**r, "result": "created"}
            upsert = body.get("upsert")
            if upsert is None:
                raise DocumentMissingError(f"[{doc_id}]: document missing",
                                           index=idx.name)
            if script is not None and body.get("scripted_upsert"):
                from ..script.update import UpdateScript

                src = dict(upsert)
                op = UpdateScript(script).apply(src)
                if op == "noop":
                    return {"_id": doc_id, "result": "noop",
                            "_version": 0, "_seq_no": -1}
                if op == "delete":
                    return {"_id": doc_id, "result": "noop",
                            "_version": 0, "_seq_no": -1}
                r = idx.index_doc(doc_id, src)
            else:
                r = idx.index_doc(doc_id, dict(upsert))
            return {**r, "result": "created"}
        if script is not None:
            from ..script.update import UpdateScript

            src = json.loads(json.dumps(e.source))
            op = UpdateScript(script).apply(src)
            if op == "noop":
                return {"_id": doc_id, "result": "noop",
                        "_version": e.version, "_seq_no": e.seq_no}
            if op == "delete":
                r = idx.delete_doc(doc_id)
                return {**r, "result": "deleted"}
            r = idx.index_doc(doc_id, src)
            return r
        merged = {**e.source, **doc}
        if body.get("detect_noop", True) and merged == e.source:
            return {"_id": doc_id, "result": "noop",
                    "_version": e.version, "_seq_no": e.seq_no}
        return idx.index_doc(doc_id, merged)

    def _matching_ids(self, idx: EsIndex, query, alias_filter=None,
                      max_docs=None) -> list[str]:
        if alias_filter is not None:
            query = ({"bool": {"filter": [alias_filter]}} if query is None
                     else {"bool": {"must": [query], "filter": [alias_filter]}})
        n = idx.count(query)
        if n == 0:
            return []
        size = n if max_docs is None else min(n, max_docs)
        res = idx.search(query=query, size=size)
        return [h["_id"] for h in res["hits"]["hits"]]

    def delete_by_query(self, expression, query=None, max_docs=None,
                        refresh=False, task=None, **res_kw) -> dict:
        """POST /{index}/_delete_by_query (reference behavior:
        reindex module AbstractAsyncBulkByScrollAction over scroll+bulk).
        `task` is polled cooperatively per doc, the analog of the
        reference's per-scroll-batch cancellation checks."""
        t0 = time.monotonic()
        deleted = 0
        total = 0
        for idx, alias_filter in self.resolve_search(expression, **res_kw):
            remaining = None if max_docs is None else max_docs - deleted
            if remaining is not None and remaining <= 0:
                break
            ids = self._matching_ids(idx, query, alias_filter, remaining)
            total += len(ids)
            for i in ids:
                if task is not None:
                    task.ensure_not_cancelled()
                idx.delete_doc(i)
                deleted += 1
            if refresh and ids:
                idx.refresh()
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False, "total": total, "deleted": deleted,
            "batches": 1 if total else 0, "version_conflicts": 0,
            "noops": 0, "failures": [],
        }

    def update_by_query(self, expression, query=None, script=None,
                        max_docs=None, refresh=False, pipeline=None,
                        task=None, **res_kw) -> dict:
        """POST /{index}/_update_by_query: re-index matching docs, optionally
        transformed by an update script and/or ingest pipeline."""
        from ..script.update import UpdateScript

        t0 = time.monotonic()
        us = UpdateScript(script) if script is not None else None
        updated = 0
        noops = 0
        deleted = 0
        total = 0
        for idx, alias_filter in self.resolve_search(expression, **res_kw):
            remaining = None if max_docs is None else max_docs - (updated + noops)
            if remaining is not None and remaining <= 0:
                break
            ids = self._matching_ids(idx, query, alias_filter, remaining)
            total += len(ids)
            for i in ids:
                if task is not None:
                    task.ensure_not_cancelled()
                e = idx.docs[i]
                src = json.loads(json.dumps(e.source))
                op = "index"
                if us is not None:
                    op = us.apply(src)
                if pipeline is not None:
                    src = self.ingest.execute(pipeline, src, index=idx.name, doc_id=i)
                    if src is None:
                        op = "delete"
                if op == "noop":
                    noops += 1
                    continue
                if op == "delete":
                    idx.delete_doc(i)
                    deleted += 1
                    continue
                idx.index_doc(i, src)
                updated += 1
            if refresh and ids:
                idx.refresh()
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False, "total": total, "updated": updated,
            "deleted": deleted, "batches": 1 if total else 0,
            "version_conflicts": 0, "noops": noops, "failures": [],
        }

    def reindex(self, body: dict, task=None) -> dict:
        """POST /_reindex {source: {index, query?}, dest: {index, pipeline?,
        op_type?}, script?, max_docs?} (reference: modules/reindex
        TransportReindexAction — scroll source, bulk into dest)."""
        from ..script.update import UpdateScript

        t0 = time.monotonic()
        source = body.get("source") or {}
        dest = body.get("dest") or {}
        if not source.get("index") or not dest.get("index"):
            raise IllegalArgumentError("reindex requires source.index and dest.index")
        if source.get("remote"):
            return self._reindex_from_remote(source, dest, body, t0)
        max_docs = body.get("max_docs")
        us = UpdateScript(body["script"]) if body.get("script") else None
        op_type = dest.get("op_type", "index")
        created = 0
        updated = 0
        noops = 0
        total = 0
        conflicts = 0
        proceed_on_conflict = body.get("conflicts") == "proceed"
        for idx, alias_filter in self.resolve_search(source["index"]):
            remaining = None if max_docs is None else max_docs - total
            if remaining is not None and remaining <= 0:
                break
            ids = self._matching_ids(idx, source.get("query"), alias_filter, remaining)
            dst = self.get_or_autocreate(dest["index"])
            for i in ids:
                if task is not None:
                    task.ensure_not_cancelled()
                total += 1
                src = json.loads(json.dumps(idx.docs[i].source))
                if us is not None:
                    op = us.apply(src)
                    if op == "noop":
                        noops += 1
                        continue
                if dest.get("pipeline"):
                    src = self.ingest.execute(dest["pipeline"], src,
                                              index=dst.name, doc_id=i)
                    if src is None:
                        noops += 1
                        continue
                try:
                    r = dst.index_doc(i, src, op_type=op_type)
                except VersionConflictError:
                    if proceed_on_conflict:
                        conflicts += 1
                        continue
                    raise
                if r["result"] == "created":
                    created += 1
                else:
                    updated += 1
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False, "total": total, "created": created,
            "updated": updated, "deleted": 0, "batches": 1 if total else 0,
            "version_conflicts": conflicts, "noops": noops,
            "retries": {"bulk": 0, "search": 0}, "failures": [],
        }

    def _reindex_from_remote(self, source: dict, dest: dict, body: dict, t0) -> dict:
        """Reindex from a remote cluster over HTTP (reference behavior:
        modules/reindex remote reindex via the low-level REST client)."""
        import urllib.request

        host = source["remote"].get("host")
        if not host:
            raise IllegalArgumentError("source.remote requires [host]")
        if not host.startswith("http"):
            host = f"http://{host}"
        req_body = {"size": min(int(body.get("max_docs") or 10000), 10000)}
        if source.get("query") is not None:
            req_body["query"] = source["query"]
        req = urllib.request.Request(
            f"{host}/{source['index']}/_search",
            data=json.dumps(req_body).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            out = json.loads(resp.read())
        dst = self.get_or_autocreate(dest["index"])
        created = 0
        updated = 0
        for h in out["hits"]["hits"]:
            r = dst.index_doc(h["_id"], h["_source"])
            if r["result"] == "created":
                created += 1
            else:
                updated += 1
        return {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": False, "total": created + updated,
            "created": created, "updated": updated, "deleted": 0,
            "batches": 1, "version_conflicts": 0, "noops": 0,
            "retries": {"bulk": 0, "search": 0}, "failures": [],
        }

    # ---- mget / field_caps ----------------------------------------------

    def mget(self, items: list[tuple[str, str]]) -> list[dict]:
        """items: [(index, id)] -> ES mget doc envelopes (realtime, like
        TransportShardMultiGetAction over the version map)."""
        out = []
        for index_name, doc_id in items:
            try:
                idx = self.get_index(self.resolve_write_index(index_name))
            except (IndexNotFoundError, IllegalArgumentError) as ex:
                out.append({
                    "_index": index_name, "_id": doc_id,
                    "error": {"type": ex.type, "reason": ex.reason},
                })
                continue
            got = idx.get_doc(doc_id)
            if got is None:
                out.append({"_index": idx.name, "_id": doc_id, "found": False})
            else:
                out.append({"_index": idx.name, "found": True, **got})
        return out

    def field_caps(self, expression, fields="*") -> dict:
        """Union field schema over resolved indices (reference behavior:
        action/fieldcaps/TransportFieldCapabilitiesAction.java:68 — merge of
        per-index FieldCapabilitiesIndexResponses)."""
        import fnmatch as _fn

        targets = self.resolve_search(expression)
        pats = fields.split(",") if isinstance(fields, str) else list(fields)
        caps: dict[str, dict[str, dict]] = {}
        per_type_indices: dict[tuple[str, str], list[str]] = {}
        for idx, _ in targets:
            for name, ft in idx.mappings.fields.items():
                if not any(_fn.fnmatchcase(name, p) for p in pats):
                    continue
                searchable = bool(ft.index)
                aggregatable = bool(ft.doc_values) and ft.type != "text"
                caps.setdefault(name, {}).setdefault(ft.type, {
                    "type": ft.type,
                    "metadata_field": False,
                    "searchable": searchable,
                    "aggregatable": aggregatable,
                })
                per_type_indices.setdefault((name, ft.type), []).append(idx.name)
        # a field mapped to >1 type across indices lists which indices hold
        # each type, like the reference response
        for name, by_type in caps.items():
            if len(by_type) > 1:
                for t, body in by_type.items():
                    body["indices"] = sorted(per_type_indices[(name, t)])
        return {
            "indices": [i.name for i, _ in targets],
            "fields": caps,
        }

    def close_index(self, name: str) -> dict:
        """POST /{index}/_close (reference behavior:
        MetadataIndexStateService — closed indices reject reads/writes but
        keep their data)."""
        idx = self.get_index(name)
        idx.settings["closed"] = True
        idx._persist_meta()
        return {"acknowledged": True, "shards_acknowledged": True,
                "indices": {name: {"closed": True}}}

    def open_index(self, name: str) -> dict:
        idx = self.get_index(name)
        idx.settings.pop("closed", None)
        idx._persist_meta()
        return {"acknowledged": True, "shards_acknowledged": True}

    def add_block(self, name: str, block: str) -> dict:
        if block not in ("write", "read_only", "read", "metadata"):
            raise IllegalArgumentError(f"unknown block [{block}]")
        idx = self.get_index(name)
        idx.settings[f"blocks.{block}"] = True
        idx._persist_meta()
        return {"acknowledged": True, "shards_acknowledged": True,
                "indices": [{"name": name, "blocked": True}]}

    def clone_index(self, source: str, target: str) -> dict:
        """POST /{index}/_clone/{target} (reference behavior:
        TransportResizeAction — requires a write block on the source)."""
        src = self.get_index(source)
        if not (src.settings.get("blocks.write") or src.settings.get("blocks.read_only")):
            raise IllegalArgumentError(
                f"index [{source}] must be read-only to clone (add a write block)"
            )
        if target in self.indices:
            raise IndexAlreadyExistsError(target)
        settings = {k: v for k, v in src.settings.items()
                    if not k.startswith("blocks.") and k not in ("closed", "creation_date")}
        self.create_index(target, mappings=src.mappings.to_dict(), settings=settings)
        dst = self.indices[target]
        for doc_id, e in src.docs.items():
            if e.alive:
                dst.index_doc(doc_id, e.source)
        return {"acknowledged": True, "shards_acknowledged": True, "index": target}

    def suggest_multi(self, expression, body: dict) -> dict:
        """Suggest over an index expression; single concrete target only
        (cross-index suggest merge is not supported yet)."""
        from ..search.suggest import run_suggest

        targets = self.resolve_search(expression or "_all", allow_no_indices=True)
        if len(targets) != 1:
            raise IllegalArgumentError(
                "suggest over multiple indices is not supported; target one index"
            )
        return run_suggest(targets[0][0], body)

    def count_multi(self, expression, query=None, failures=None,
                    **res_kw) -> int:
        """`failures`: optional list the caller owns — per-index count
        failures are appended there (honest `_shards` accounting at the
        REST layer, PR 14) instead of killing the whole count; with no
        list given the first failure raises as before."""
        from ..common import faults

        targets = self.resolve_search(expression, **res_kw)
        total = 0
        node_name = getattr(self.tasks, "node", "node-0")
        for idx, alias_filter in targets:
            q = query
            if alias_filter is not None:
                q = {"bool": {"filter": [alias_filter]}} if q is None else \
                    {"bool": {"must": [q], "filter": [alias_filter]}}
            try:
                faults.check("shard.search", index=idx.name,
                             node=node_name, op="count")
                total += idx.count(q)
            except IllegalArgumentError:
                raise
            except Exception as ex:  # noqa: BLE001 - per-shard envelope
                if failures is None:
                    raise
                failures.append({
                    "shard": 0, "index": idx.name, "node": node_name,
                    "reason": {"type": type(ex).__name__.lower(),
                               "reason": str(ex)[:512]},
                })
        return total

    def resolve_pipelines(self, idx, pipeline: str | None = None
                          ) -> tuple[str | None, str | None]:
        """Resolve the (request pipeline | default_pipeline) +
        final_pipeline chain for one index ONCE — the per-(index,
        request) hoist: a 10k-doc _bulk reads the settings once instead
        of four setting lookups per item (reference behavior:
        IngestService resolves pipelines per bulk shard request, not
        per doc). -> (first, final), either None when nothing applies."""
        settings = idx.settings if idx is not None else {}
        first = pipeline if pipeline not in (None, "_none") else None
        if first is None and pipeline != "_none":
            dp = (settings.get("default_pipeline")
                  or settings.get("index.default_pipeline"))
            if dp and dp != "_none":
                first = dp
        final = (settings.get("final_pipeline")
                 or settings.get("index.final_pipeline"))
        if not final or final == "_none":
            final = None
        return first, final

    def run_pipelines_resolved(self, index_name: str, source: dict,
                               first: str | None, final: str | None,
                               doc_id: str | None = None):
        """Apply an already-resolved pipeline chain to one doc. Returns
        the transformed source, or None if a drop processor fired."""
        for name in (first, final):
            if not name:
                continue
            source = self.ingest.execute(name, source, index=index_name,
                                         doc_id=doc_id)
            if source is None:
                return None
        return source

    def run_pipelines(self, index_name: str, source: dict,
                      pipeline: str | None = None, doc_id: str | None = None):
        """Apply request/default pipeline then final_pipeline (reference
        behavior: IngestService.executeBulkRequest + the
        index.default_pipeline / index.final_pipeline settings). Returns the
        transformed source, or None if a drop processor fired."""
        first, final = self.resolve_pipelines(
            self.indices.get(index_name), pipeline)
        return self.run_pipelines_resolved(index_name, source, first, final,
                                           doc_id)

    def bulk(self, operations: list,
             pipeline: str | None = None):
        """operations: (action, index, id, source[, routing]). Returns
        per-item results; failures are per-item, not transactional
        (reference behavior: TransportShardBulkAction.java:308
        executeBulkItemRequest).

        PR 16 front door: write-alias resolution and pipeline-settings
        lookups are cached per (raw index name, request), and runs of
        consecutive index/create items sharing a pipeline chain execute
        through IngestService.execute_batch — one registry lookup + one
        ingest timestamp per run instead of per doc — while every
        per-item error envelope and result stays identical to the
        per-doc path (asserted by tests/test_ingest.py)."""
        from ..telemetry import metrics
        from ..utils.errors import ElasticsearchTpuError

        metrics.counter_inc("es.bulk.requests")
        items: list = []
        errors = False
        name_cache: dict = {}   # raw name -> (concrete index name, EsIndex)
        pipe_cache: dict = {}   # concrete name -> (first, final)

        def _item_error(action, index_name, doc_id, ex):
            nonlocal errors
            errors = True
            if isinstance(ex, ElasticsearchTpuError):
                err = {"type": ex.type, "reason": ex.reason}
                status = ex.status
            else:
                err = {"type": "exception", "reason": str(ex)}
                status = 500
            return {action: {"_index": index_name, "_id": doc_id,
                             "status": status, "error": err}}

        # pass 1: resolve targets + pipeline chains, validate ts-mode
        resolved: list = []  # per op: (action, name, idx, doc_id, source,
        #                               err_item | None)
        for op_tuple in operations:
            action, index_name, doc_id, source = op_tuple[:4]
            routing = op_tuple[4] if len(op_tuple) > 4 else None
            try:
                # resolve write alias + target index once per raw name so
                # ingest pipeline settings and item results both see the
                # concrete index without per-doc lookups
                cached = name_cache.get(index_name)
                if cached is None:
                    concrete = self.resolve_write_index(index_name)
                    cached = name_cache[index_name] = (
                        concrete, self.get_or_autocreate(concrete))
                index_name, idx = cached
                if idx.ts_mode is not None:
                    if routing is not None:
                        raise IllegalArgumentError(
                            f"specifying routing is not supported because "
                            f"the destination index [{index_name}] is in "
                            f"time series mode")
                    if action == "update":
                        raise IllegalArgumentError(
                            f"update is not supported because the "
                            f"destination index [{index_name}] is in time "
                            f"series mode")
                if index_name not in pipe_cache:
                    pipe_cache[index_name] = self.resolve_pipelines(
                        idx, pipeline)
                resolved.append((action, index_name, idx, doc_id, source,
                                 None))
            except Exception as ex:  # noqa: BLE001 - per-item envelope
                resolved.append((action, index_name, None, doc_id, source,
                                 _item_error(action, index_name, doc_id,
                                             ex)))

        # pass 2: batched pipeline execution over consecutive
        # index/create runs sharing one (index, chain); outcomes are
        # per-doc (dict | None dropped | Exception), never a raised error
        transformed: dict[int, object] = {}
        i = 0
        n = len(resolved)
        while i < n:
            action, index_name, idx, doc_id, source, err = resolved[i]
            chain = pipe_cache.get(index_name, (None, None))
            if (err is not None or action not in ("index", "create")
                    or chain == (None, None)):
                i += 1
                continue
            j = i
            while (j < n and resolved[j][5] is None
                   and resolved[j][0] in ("index", "create")
                   and resolved[j][1] == index_name):
                j += 1
            outs = self.ingest.execute_batch(
                chain, [resolved[k][4] for k in range(i, j)],
                index=index_name,
                doc_ids=[resolved[k][3] for k in range(i, j)])
            for k, out in zip(range(i, j), outs):
                transformed[k] = out
            i = j

        # pass 3: apply, in original order, with per-item envelopes. Every
        # item's record goes to its index's WAL as it is applied; each
        # touched WAL is synced once, after the last item and before the
        # response is built, so nothing is acknowledged unsynced
        with contextlib.ExitStack() as wal_syncs:
            for _name, idx in name_cache.values():
                wal_syncs.enter_context(idx.wal_sync_deferred())
            for k, (action, index_name, idx, doc_id, source, err) in (
                    enumerate(resolved)):
                if err is not None:
                    items.append(err)
                    continue
                try:
                    if action in ("index", "create"):
                        if k in transformed:
                            source = transformed[k]
                            if isinstance(source, Exception):
                                raise source
                        if source is None:  # dropped by pipeline
                            items.append({action: {
                                "_index": index_name, "_id": doc_id,
                                "result": "noop", "status": 200,
                            }})
                            continue
                        r = idx.index_doc(doc_id, source, op_type=action)
                        status = 201 if r["result"] == "created" else 200
                        items.append({action: {"_index": index_name, **r,
                                               "status": status}})
                    elif action == "delete":
                        r = idx.delete_doc(doc_id)
                        items.append({action: {"_index": index_name, **r,
                                               "status": 200}})
                    elif action == "update":
                        if not isinstance(source, dict) or not isinstance(
                                source.get("doc"), dict):
                            raise IllegalArgumentError(
                                "update action requires a [doc] object")
                        e = idx.docs.get(doc_id)
                        if e is None or not e.alive:
                            raise DocumentMissingError(
                                f"[{doc_id}]: document missing")
                        merged = {**e.source, **source["doc"]}
                        r = idx.index_doc(doc_id, merged)
                        items.append({action: {"_index": index_name, **r,
                                               "status": 200}})
                    else:
                        raise IllegalArgumentError(
                            f"unknown bulk action [{action}]")
                except Exception as ex:  # per-item error envelope
                    items.append(_item_error(action, index_name, doc_id, ex))
        return {"errors": errors, "items": items}

    def close(self):
        self.persistent.stop_ticker()  # join the watch-scheduler thread
        if self._watcher is not None:
            self._watcher.flush_exports()  # queued alert/history docs
        if self._serving is not None:
            self._serving.stop()  # drain + join the scheduler threads
        if self._monitoring is not None:
            self._monitoring.stop()  # join the collection thread
        if self._profiler is not None:
            self._profiler.close()  # stop a still-open trace window
        if self._device_degradation is not None:
            self._device_degradation.close()  # cancel the recovery ramp
        if self._ml is not None:
            self._ml.shutdown()  # checkpoints open jobs' model state
        for idx in self.indices.values():
            idx.close()
