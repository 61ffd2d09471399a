"""SnapshotService: create/get/delete/restore snapshots over repositories.

Reference behavior: snapshots/SnapshotsService.java:138 (create/delete
orchestration, in-progress state), snapshots/SnapshotShardsService.java:71
(per-shard data capture), snapshots/RestoreService.java (restore into the
routing table with rename support), repositories/RepositoriesService.java
(registry of named repositories).

Orchestration is synchronous here (one host owns the engine); the
distributed variant rides the coordinator's cluster state like every other
metadata change. Data capture is incremental via content addressing
(repository.py) rather than Lucene file diffing — same contract, different
storage unit.
"""

from __future__ import annotations

import fnmatch
import json
import re
import time

from ..utils.errors import (
    IllegalArgumentError,
    IndexNotFoundError,
    ResourceAlreadyExistsError,
)
from .repository import (
    FsRepository,
    InvalidSnapshotNameError,
    Repository,
    RepositoryMissingError,
    SnapshotMissingError,
    chunk_docs,
)

_NAME_RE = re.compile(r"^[a-z0-9][a-z0-9_.-]*$")


class SnapshotService:
    def __init__(self, engine):
        self.engine = engine
        self.repositories: dict[str, dict] = {}  # name -> {type, settings}
        self._repos: dict[str, Repository] = {}

    # ---- repositories ----------------------------------------------------

    def put_repository(self, name: str, body: dict):
        rtype = body.get("type")
        settings = body.get("settings") or {}
        if rtype == "fs":
            repo = FsRepository(settings.get("location"))
        elif rtype == "s3":
            from .s3 import S3Repository

            repo = S3Repository(settings, keystore=self._keystore())
        else:
            raise IllegalArgumentError(
                f"repository type [{rtype}] does not exist (supported: fs, s3)"
            )
        # credentials never enter repository metadata: GET /_snapshot echoes
        # settings back to clients (the reference keeps S3 credentials
        # keystore-only for the same reason — S3ClientSettings.java)
        public = {k: v for k, v in settings.items()
                  if k not in ("access_key", "secret_key", "session_token")}
        self.repositories[name] = {"type": rtype, "settings": public}
        self._repos[name] = repo
        return {"acknowledged": True}

    def _keystore(self):
        """The node keystore (cli/keystore.py), if one exists under the
        engine's data path — the s3.client.default.* secure settings
        source."""
        import os

        data_path = getattr(self.engine, "data_path", None)
        if not data_path:
            return None
        path = os.path.join(data_path, "elasticsearch.keystore")
        if not os.path.exists(path):
            return None
        from ..cli.keystore import Keystore

        try:
            return Keystore.load(path)
        except Exception:  # noqa: BLE001 - wrong password etc: no keystore
            return None

    def get_repository(self, name: str | None = None) -> dict:
        if name in (None, "_all", "*"):
            return dict(self.repositories)
        if name not in self.repositories:
            raise RepositoryMissingError(f"[{name}] missing")
        return {name: self.repositories[name]}

    def delete_repository(self, name: str):
        if name not in self.repositories:
            raise RepositoryMissingError(f"[{name}] missing")
        del self.repositories[name]
        del self._repos[name]
        return {"acknowledged": True}

    def _repo(self, name: str) -> Repository:
        repo = self._repos.get(name)
        if repo is None:
            raise RepositoryMissingError(f"[{name}] missing")
        return repo

    # ---- snapshots -------------------------------------------------------

    def create_snapshot(self, repo_name: str, snap_name: str,
                        indices="*", include_global_state=True,
                        include_packs=True) -> dict:
        repo = self._repo(repo_name)
        if not _NAME_RE.match(snap_name or ""):
            raise InvalidSnapshotNameError(
                f"[{repo_name}:{snap_name}] Invalid snapshot name: must be lowercase"
            )
        # root lock held across check-then-append: concurrent snapshot
        # creations from several gateway nodes serialize instead of
        # losing root-index updates (round-4 CLUSTER_SKIP race)
        with repo.root_lock():
            return self._create_snapshot_locked(
                repo, repo_name, snap_name, indices, include_global_state,
                include_packs)

    def _create_snapshot_locked(self, repo, repo_name, snap_name, indices,
                                include_global_state, include_packs):
        root = repo.load_root()
        if any(s["snapshot"] == snap_name for s in root["snapshots"]):
            raise ResourceAlreadyExistsError(
                f"[{repo_name}:{snap_name}] snapshot with the same name already exists"
            )
        t0 = time.time()
        targets = self.engine.resolve_search(indices)
        index_meta = {}
        for idx, _ in targets:
            docs = [
                {"id": i, "source": e.source, "version": e.version, "seq_no": e.seq_no}
                for i, e in sorted(idx.docs.items())
                if e.alive
            ]
            chunks = [repo.put_blob(c) for c in chunk_docs(docs)]
            index_meta[idx.name] = {
                "mappings": idx.mappings.to_dict(),
                "settings": idx.settings,
                "doc_count": len(docs),
                "chunks": chunks,
                "aliases": self.engine.meta.aliases_of(idx.name),
            }
            packs = (self._snapshot_packs(idx, repo)
                     if include_packs else None)
            if packs is not None:
                index_meta[idx.name]["packs"] = packs
        snap = {
            "snapshot": snap_name,
            "uuid": f"{repo_name}-{snap_name}-{int(t0 * 1000)}",
            "state": "SUCCESS",
            "indices": index_meta,
            "include_global_state": bool(include_global_state),
            "global_state": (
                {
                    "index_templates": dict(self.engine.meta.index_templates),
                    "component_templates": dict(self.engine.meta.component_templates),
                    "ingest_pipelines": dict(self.engine.ingest.pipelines),
                }
                if include_global_state
                else None
            ),
            "start_time_in_millis": int(t0 * 1000),
            "end_time_in_millis": int(time.time() * 1000),
            "version": "8.14.0-tpu",
        }
        repo.write(f"snap-{snap_name}.json", json.dumps(snap).encode())
        root["snapshots"].append({"snapshot": snap_name, "state": "SUCCESS",
                                  "indices": sorted(index_meta)})
        repo.store_root(root)
        return self._render(snap)

    @staticmethod
    def _render(snap: dict) -> dict:
        n = sum(1 for _ in snap["indices"])
        return {
            "snapshot": snap["snapshot"],
            "uuid": snap["uuid"],
            "state": snap["state"],
            "indices": sorted(snap["indices"]),
            "include_global_state": snap["include_global_state"],
            "start_time_in_millis": snap["start_time_in_millis"],
            "end_time_in_millis": snap["end_time_in_millis"],
            "duration_in_millis": snap["end_time_in_millis"] - snap["start_time_in_millis"],
            "shards": {"total": n, "failed": 0, "successful": n},
            "failures": [],
        }

    def _load_snap(self, repo: Repository, snap_name: str) -> dict:
        if not repo.exists(f"snap-{snap_name}.json"):
            raise SnapshotMissingError(f"[{snap_name}] is missing")
        return json.loads(repo.read(f"snap-{snap_name}.json"))

    def get_snapshots(self, repo_name: str, pattern: str = "_all") -> list[dict]:
        repo = self._repo(repo_name)
        root = repo.load_root()
        names = [s["snapshot"] for s in root["snapshots"]]
        if pattern not in ("_all", "*"):
            wanted = pattern.split(",")
            matched = [n for n in names
                       if any(fnmatch.fnmatchcase(n, w) for w in wanted)]
            if not matched and not any("*" in w or "?" in w for w in wanted):
                raise SnapshotMissingError(f"[{pattern}] is missing")
            names = matched
        return [self._render(self._load_snap(repo, n)) for n in names]

    def delete_snapshot(self, repo_name: str, snap_name: str):
        repo = self._repo(repo_name)
        with repo.root_lock():
            return self._delete_snapshot_locked(repo, repo_name, snap_name)

    def _delete_snapshot_locked(self, repo, repo_name, snap_name):
        snap = self._load_snap(repo, snap_name)
        root = repo.load_root()
        root["snapshots"] = [s for s in root["snapshots"]
                             if s["snapshot"] != snap_name]
        repo.store_root(root)
        repo.delete(f"snap-{snap_name}.json")
        # blob GC: drop chunks referenced by no remaining snapshot
        # (the reference's stale-blob cleanup on delete,
        # BlobStoreRepository cleanup of unreferenced blobs)
        live: set[str] = set()
        for s in root["snapshots"]:
            live.update(snap_chunks(self._load_snap(repo, s["snapshot"])))
        for digest in set(snap_chunks(snap)) - live:
            repo.delete(f"blobs/{digest}")
        return {"acknowledged": True}

    # ---- restore ---------------------------------------------------------

    def restore_snapshot(self, repo_name: str, snap_name: str,
                         body: dict | None = None) -> dict:
        body = body or {}
        repo = self._repo(repo_name)
        snap = self._load_snap(repo, snap_name)
        indices = body.get("indices", "*")
        if isinstance(indices, str):
            indices = [p for p in indices.split(",") if p]
        rename_pattern = body.get("rename_pattern")
        rename_replacement = body.get("rename_replacement")
        targets = [
            n for n in snap["indices"]
            if any(fnmatch.fnmatchcase(n, p) or n == p for p in indices)
        ]
        # concrete (non-wildcard) names must exist in the snapshot; an empty
        # wildcard expansion is fine (reference: RestoreService index resolution)
        for p in indices:
            if "*" not in p and "?" not in p and p not in snap["indices"]:
                raise IndexNotFoundError(p)
        restored = []
        for name in sorted(targets):
            meta = snap["indices"][name]
            new_name = name
            if rename_pattern and rename_replacement is not None:
                new_name = re.sub(rename_pattern, rename_replacement, name)
            if new_name in self.engine.indices:
                raise IllegalArgumentError(
                    f"cannot restore index [{new_name}] because an open index with "
                    "same name already exists in the cluster. Either close or delete "
                    "the existing index or restore the index under a different name"
                )
            idx = self.engine.create_index(
                new_name, meta["mappings"], dict(meta["settings"]),
                aliases=meta.get("aliases") if body.get("include_aliases", True) else None,
            )
            for digest in meta["chunks"]:
                for d in json.loads(repo.get_blob(digest)):
                    idx.index_doc(d["id"], d["source"])
            idx.refresh()
            restored.append(new_name)
        if body.get("include_global_state") and snap.get("global_state"):
            gs = snap["global_state"]
            self.engine.meta.index_templates.update(gs.get("index_templates", {}))
            self.engine.meta.component_templates.update(gs.get("component_templates", {}))
            self.engine.meta.save()
            for pid, cfg in gs.get("ingest_pipelines", {}).items():
                self.engine.ingest.put_pipeline(pid, cfg)
        return {
            "snapshot": {
                "snapshot": snap_name,
                "indices": restored,
                "shards": {"total": len(restored), "failed": 0,
                           "successful": len(restored)},
            }
        }

    def _snapshot_packs(self, idx, repo) -> dict | None:
        """Snapshot the index's sealed base packs as content-addressed
        COMPONENT blobs (index/packio.py) plus order-aligned per-shard doc
        lists, so `_mount` can rebuild the searcher without re-indexing
        (reference: the frozen tier mounts Lucene files from the repo,
        SharedBlobCacheService.java:68). Returns None when the live
        searcher cannot represent the doc set (mid-recovery, hydration
        pending, ...) — the doc chunks then remain the restore source."""
        import hashlib

        from ..index.packio import serialize_pack
        from .repository import CHUNK_DOCS

        from ..parallel.stacked import build_stacked_pack_routed

        try:
            if idx._hydrate is not None:
                return None  # an unhydrated mount: blobs already exist
            # Build a FRESH pack purely for serialization — never touch
            # the live searcher: a snapshot must not refresh or merge as
            # a side effect (refresh_interval=-1 relies on writes staying
            # invisible). The build is a pure function of the alive doc
            # set (sorted), so an unchanged corpus re-serializes to
            # byte-identical components and deduplicates to zero.
            live_docs = [(i, e.source)
                         for i, e in sorted(idx.docs.items()) if e.alive]
            routed = idx._route_docs(live_docs)
            sp_packs = build_stacked_pack_routed(routed, idx.mappings).shards

            # stage every payload in memory FIRST: a mid-serialization
            # failure must not leave orphaned component blobs that no
            # manifest references (GC only frees referenced digests)
            staged: dict[str, bytes] = {}

            def stage(payload: bytes) -> str:
                digest = hashlib.sha256(payload).hexdigest()
                staged[digest] = payload
                return digest

            shard_mans = [serialize_pack(p, stage) for p in sp_packs]
            doc_chunks = []
            for lst in routed:
                digests = []
                # ORDER-PRESERVING chunking (pack docid d == list position
                # d), sharing repository.py's chunk size + compact form
                for off in range(0, len(lst), CHUNK_DOCS):
                    buf = []
                    for doc_id, source in lst[off:off + CHUNK_DOCS]:
                        e = idx.docs.get(doc_id)
                        buf.append({"id": doc_id, "source": source,
                                    "version": getattr(e, "version", 1),
                                    "seq_no": getattr(e, "seq_no", 0)})
                    digests.append(stage(json.dumps(
                        buf, separators=(",", ":"), sort_keys=True
                    ).encode()))
                doc_chunks.append(digests)
            for payload in staged.values():
                repo.put_blob(payload)
            return {"shards": shard_mans, "docs": doc_chunks}
        except Exception:  # noqa: BLE001 - components are an optimization
            return None

    # ---- searchable snapshots (frozen tier) ------------------------------

    def mount_snapshot(self, repo_name: str, snap_name: str,
                       body: dict) -> dict:
        """Mount a snapshotted index as a read-only searchable-snapshot
        index (reference: x-pack searchable-snapshots `_mount` +
        SharedBlobCacheService.java:68). The mount itself moves NO data:
        index metadata comes from the snapshot manifest; the doc-chunk
        blobs are demand-fetched through the engine's shared LRU blob
        cache on the FIRST search (lazy hydration), so a cold mount is
        instant, a cold search pays the object-store round trips once,
        and every re-mount hits RAM. The mounted index carries
        blocks.write (the reference's searchable-snapshot indices are
        likewise read-only)."""
        from ..utils.errors import IllegalArgumentError

        body = body or {}
        name = body.get("index")
        if not name:
            raise IllegalArgumentError("[index] is required")
        repo = self._repo(repo_name)
        snap = self._load_snap(repo, snap_name)
        if name not in snap["indices"]:
            raise IndexNotFoundError(name)
        new_name = body.get("renamed_index") or name
        if new_name in self.engine.indices:
            raise IllegalArgumentError(
                f"cannot mount index [{new_name}] because an open index "
                "with same name already exists in the cluster")
        meta = snap["indices"][name]
        settings = dict(meta["settings"])
        settings.update(body.get("index_settings") or {})
        settings["store.type"] = "snapshot"
        settings["store.snapshot.repository_name"] = repo_name
        settings["store.snapshot.snapshot_name"] = snap_name
        idx = self.engine.create_index(new_name, meta["mappings"], settings)
        idx.settings["blocks.write"] = True
        cache = self.engine.blob_cache
        chunks = list(meta["chunks"])
        packs = meta.get("packs")

        def fetch(digest):
            return cache.get_or_fetch(
                f"{repo_name}/{digest}",
                lambda: repo.get_blob(digest),
            )

        def hydrate_packs():
            """Pack-component mount: rebuild ShardPacks + the aligned doc
            lists straight from blobs — no per-doc re-indexing; first
            search cost = blob fetch + HBM upload (VERDICT r4 #7)."""
            from ..index.packio import deserialize_pack
            from ..parallel.sharded import StackedSearcher, make_mesh
            from ..parallel.stacked import StackedPack
            from ..engine.engine import _DocEntry

            shards = [deserialize_pack(man, fetch)
                      for man in packs["shards"]]
            routed = []
            max_seq = 0
            for s, digests in enumerate(packs["docs"]):
                lst = []
                for digest in digests:
                    for r in json.loads(fetch(digest)):
                        lst.append((r["id"], r["source"]))
                        if shards[s].live[len(lst) - 1]:
                            idx.docs[r["id"]] = _DocEntry(
                                r["source"], r.get("version", 1),
                                r.get("seq_no", 0), True)
                            max_seq = max(max_seq, r.get("seq_no", 0))
                routed.append(lst)
            sp = StackedPack(shards, idx.mappings)
            mesh = make_mesh(len(shards))
            # same admission control as every refresh-built searcher:
            # a frozen mount must not overcommit device memory
            idx._account_packs(sp.nbytes(), mesh)
            idx._searcher = StackedSearcher(sp, mesh=mesh)
            idx.shard_docs = routed
            idx._tail = None
            idx._tail_shard_docs = []
            idx._tail_docs = {}
            idx._pending.clear()
            idx._base_pos = {
                doc_id: (s, d)
                for s, lst in enumerate(routed)
                for d, (doc_id, _src) in enumerate(lst)
            }
            idx._base_stats = (
                {f: dict(st) for f, st in sp.field_stats.items()},
                dict(sp.global_df),
            )
            idx._base_nbytes = sp.nbytes()
            idx.seq_no = max(idx.seq_no, max_seq + 1)
            idx._dirty = False

        def hydrate_docs():
            idx.settings.pop("blocks.write", None)
            try:
                for digest in chunks:
                    for d in json.loads(fetch(digest)):
                        idx.index_doc(d["id"], d["source"])
                idx.refresh()
            finally:
                idx.settings["blocks.write"] = True

        idx._hydrate = hydrate_packs if packs else hydrate_docs
        return {
            "snapshot": {
                "snapshot": snap_name,
                "indices": [new_name],
                "shards": {"total": 1, "failed": 0, "successful": 1},
            }
        }

    def status(self, repo_name: str, snap_name: str) -> dict:
        repo = self._repo(repo_name)
        snap = self._load_snap(repo, snap_name)
        return {
            "snapshots": [{
                "snapshot": snap_name,
                "repository": repo_name,
                "state": snap["state"],
                "indices": {
                    n: {"shards_stats": {"done": 1, "failed": 0, "total": 1},
                        "stats": {"total": {"file_count": len(m["chunks"]),
                                            "size_in_bytes": 0}},
                        "doc_count": m["doc_count"]}
                    for n, m in snap["indices"].items()
                },
            }]
        }


def snap_chunks(snap: dict) -> list[str]:
    """Every blob digest a snapshot references (doc chunks + pack
    components) — the GC live-set."""
    from ..index.packio import manifest_digests

    out = []
    for im in snap["indices"].values():
        out.extend(im["chunks"])
        packs = im.get("packs")
        if packs:
            for man in packs["shards"]:
                out.extend(manifest_digests(man))
            for digests in packs["docs"]:
                out.extend(digests)
    return out
