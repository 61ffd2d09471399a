"""Pinned search contexts: scroll + point-in-time (PIT).

The reference keeps per-shard ReaderContexts with keep-alives for scroll
and PIT searches (reference behavior: search/SearchService.java:349 reader
contexts, createAndPutReaderContext / openReaderContext; scroll continues
from a pinned Lucene searcher, point-in-time ids resolve to the same).
Here a context pins the immutable (searcher, shard_docs) snapshot of one or
more indices so pagination is stable while writers refresh around it —
structurally identical to holding a Lucene reader open.
"""

from __future__ import annotations

import base64
import json
import secrets
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

from ..utils.errors import ElasticsearchTpuError, IllegalArgumentError
from ..utils.durations import parse_duration_seconds


class SearchContextMissingError(ElasticsearchTpuError):
    status = 404
    type = "search_context_missing_exception"


MAX_KEEP_ALIVE_S = 24 * 3600.0


def _keep_alive_seconds(keep_alive) -> float:
    if keep_alive is None:
        return 300.0
    secs = parse_duration_seconds(keep_alive, 300.0)
    if secs is None or secs <= 0:
        raise IllegalArgumentError(f"invalid keep_alive [{keep_alive}]")
    if secs > MAX_KEEP_ALIVE_S:
        raise IllegalArgumentError(
            f"Keep alive for request ({keep_alive}) is too large. It must be less than (1d)."
        )
    return secs


@dataclass
class _Pin:
    index_name: str
    searcher: object
    shard_docs: list


@dataclass
class SearchCtx:
    id: str
    pins: list[_Pin]
    expires_at: float
    # scroll cursor state (unused for PIT)
    request: dict | None = None
    cursor: int = 0
    keep_alive_s: float = 300.0
    extra: dict = field(default_factory=dict)


class ContextRegistry:
    """Host-side registry of live scroll/PIT contexts with lazy expiry
    (pruned on every access, like the reference's keep-alive reaper)."""

    def __init__(self):
        self._ctxs: dict[str, SearchCtx] = {}

    def prune(self):
        now = time.monotonic()
        for cid in [c for c, ctx in self._ctxs.items() if ctx.expires_at <= now]:
            del self._ctxs[cid]

    def open(self, pins: list[_Pin], keep_alive, request=None) -> SearchCtx:
        self.prune()
        secs = _keep_alive_seconds(keep_alive)
        raw = secrets.token_bytes(18)
        cid = base64.urlsafe_b64encode(raw).decode().rstrip("=")
        ctx = SearchCtx(
            id=cid, pins=pins, expires_at=time.monotonic() + secs,
            request=request, keep_alive_s=secs,
        )
        self._ctxs[cid] = ctx
        return ctx

    def get(self, cid: str, keep_alive=None) -> SearchCtx:
        self.prune()
        ctx = self._ctxs.get(cid)
        if ctx is None:
            raise SearchContextMissingError(f"No search context found for id [{cid}]")
        secs = _keep_alive_seconds(keep_alive) if keep_alive else ctx.keep_alive_s
        ctx.keep_alive_s = secs
        ctx.expires_at = time.monotonic() + secs
        return ctx

    def close(self, cid: str) -> bool:
        self.prune()
        return self._ctxs.pop(cid, None) is not None

    def close_all(self) -> int:
        n = len(self._ctxs)
        self._ctxs.clear()
        return n

    def __len__(self):
        self.prune()
        return len(self._ctxs)


@contextmanager
def pinned(engine, ctx: SearchCtx):
    """Swap each pinned index's live snapshot for the context's pinned one
    for the duration of a search. Engine work is serialized on one executor
    thread (rest/app.py), and a scroll or PIT search starts only once no
    solo search is with the completer (common/engine_thread), so the swap
    is not observable concurrently."""
    saved = []
    try:
        for pin in ctx.pins:
            idx = engine.indices.get(pin.index_name)
            if idx is None:
                from ..utils.errors import IndexNotFoundError

                raise IndexNotFoundError(pin.index_name)
            saved.append((idx, idx._searcher, idx.shard_docs, idx._dirty,
                          idx._tails, idx._tail_pos))
            idx._searcher = pin.searcher
            idx.shard_docs = pin.shard_docs
            # the pin predates any current tail segments: hide them so
            # pinned searches see exactly the snapshot (restored after)
            idx._tails = []
            idx._tail_pos = {}
            idx._dirty = False  # block _maybe_refresh while pinned
        yield
    finally:
        for idx, searcher, shard_docs, dirty, tails, tail_pos in saved:
            idx._searcher = searcher
            idx.shard_docs = shard_docs
            idx._tails = tails
            idx._tail_pos = tail_pos
            idx._dirty = dirty


def encode_pit_id(cid: str) -> str:
    return base64.urlsafe_b64encode(json.dumps({"cid": cid}).encode()).decode()


def decode_pit_id(pit_id: str) -> str:
    try:
        return json.loads(base64.urlsafe_b64decode(pit_id.encode()))["cid"]
    except Exception:
        raise IllegalArgumentError(f"invalid point-in-time id [{pit_id[:32]}...]")
