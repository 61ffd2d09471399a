"""Pythonic wrapper over the C++ index accumulator (packing.cpp).

One NativeAccumulator per in-flight shard pack build; owns the C++ builder
handle. Produces the flat-CSR form that index/pack.py's vectorized packer
consumes — identical to what the pure-Python fallback produces from its
dicts, so packs are bit-compatible either way.
"""

from __future__ import annotations

import ctypes

import numpy as np

from . import PackSizes, get_lib


class NativeAccumulator:
    def __init__(self):
        self.lib = get_lib()
        if self.lib is None:
            raise RuntimeError("native packing library unavailable")
        self.h = self.lib.builder_new()
        self.field_ids: dict[str, int] = {}

    def close(self):
        if self.h is not None:
            self.lib.builder_free(self.h)
            self.h = None

    __del__ = close

    def _fid(self, fld: str) -> int:
        fid = self.field_ids.get(fld)
        if fid is None:
            fid = self.field_ids[fld] = len(self.field_ids)
        return fid

    def add_text(self, fld: str, docid: int, text: str, pos_base: int) -> int:
        """ASCII standard-analyzer fast path; -1 = non-ASCII, caller must
        fall back to add_tokens with Python-analyzed tokens."""
        raw = text.encode("ascii", errors="surrogateescape") if text.isascii() else None
        if raw is None:
            return -1
        return self.lib.builder_add_text(
            self.h, self._fid(fld), docid, raw, len(raw), pos_base, 1
        )

    def add_texts(self, fld: str, docids: np.ndarray,
                  texts: list[str]) -> np.ndarray | None:
        """A whole burst through the ASCII fast path in one call, which
        holds no interpreter lock while it runs: `texts[k]` is a value of
        document `docids[k]` (int32; one document's values adjacent, their
        positions chained with the +100 gap as add_text's caller chains
        them). -> token counts a value, or None with nothing added where a
        value is not ASCII (the caller goes value by value)."""
        joined = "".join(texts)
        if not joined.isascii():
            return None
        off = np.zeros(len(texts) + 1, np.int64)
        np.cumsum(np.fromiter(map(len, texts), np.int64, count=len(texts)),
                  out=off[1:])
        counts = np.zeros(len(texts), np.int64)
        docids = np.ascontiguousarray(docids, np.int32)
        rc = self.lib.builder_add_texts(
            self.h, self._fid(fld), docids.ctypes.data_as(ctypes.c_void_p),
            joined.encode("ascii"), off.ctypes.data_as(ctypes.c_void_p),
            len(texts), 1, counts.ctypes.data_as(ctypes.c_void_p))
        return None if rc < 0 else counts

    def add_tokens(
        self, fld: str, docid: int, terms: list[str], positions: list[int] | None
    ):
        """Pre-tokenized path. positions[i] < 0 (or None list) skips the
        position key for that token."""
        if not terms:
            return
        n = len(terms)
        encoded = [t.encode("utf-8") for t in terms]
        buf = b"".join(encoded)
        lens = np.fromiter((len(e) for e in encoded), np.int32, count=n)
        pos = (
            np.full(n, -1, np.int64)
            if positions is None
            else np.asarray(positions, np.int64)
        )
        self.lib.builder_add_tokens(
            self.h, self._fid(fld), docid, buf,
            lens.ctypes.data_as(ctypes.c_void_p),
            pos.ctypes.data_as(ctypes.c_void_p), n,
        )

    def pack(self):
        """-> (keys, post_offsets, flat_docs, flat_tfs, pos_offsets, flat_pos)

        keys: list[(field, term)] sorted exactly like Python's
        sorted(postings.keys()); offsets are [T+1] int64 CSR directories.
        """
        names = sorted(self.field_ids)
        rank = np.zeros(max(len(self.field_ids), 1), np.uint32)
        for r, name in enumerate(names):
            rank[self.field_ids[name]] = r
        sizes = PackSizes()
        self.lib.builder_pack_sizes(
            self.h, rank.ctypes.data_as(ctypes.c_void_p), len(names),
            ctypes.byref(sizes),
        )
        T = sizes.n_terms
        term_buf = ctypes.create_string_buffer(max(sizes.term_bytes, 1))
        term_lens = np.zeros(max(T, 1), np.int32)
        term_fids = np.zeros(max(T, 1), np.uint32)
        post_offsets = np.zeros(T + 1, np.int64)
        flat_docs = np.zeros(max(sizes.n_postings, 1), np.int32)
        flat_tfs = np.zeros(max(sizes.n_postings, 1), np.float32)
        pos_offsets = np.zeros(T + 1, np.int64)
        flat_pos = np.zeros(max(sizes.n_positions, 1), np.int64)
        self.lib.builder_pack_fill(
            self.h, term_buf,
            term_lens.ctypes.data_as(ctypes.c_void_p),
            term_fids.ctypes.data_as(ctypes.c_void_p),
            post_offsets.ctypes.data_as(ctypes.c_void_p),
            flat_docs.ctypes.data_as(ctypes.c_void_p),
            flat_tfs.ctypes.data_as(ctypes.c_void_p),
            pos_offsets.ctypes.data_as(ctypes.c_void_p),
            flat_pos.ctypes.data_as(ctypes.c_void_p),
        )
        id_to_name = {v: k for k, v in self.field_ids.items()}
        keys = []
        off = 0
        raw = term_buf.raw
        for i in range(T):
            ln = int(term_lens[i])
            keys.append(
                (id_to_name[int(term_fids[i])], raw[off : off + ln].decode("utf-8"))
            )
            off += ln
        return (
            keys,
            post_offsets,
            flat_docs[: sizes.n_postings],
            flat_tfs[: sizes.n_postings],
            pos_offsets,
            flat_pos[: sizes.n_positions],
        )
