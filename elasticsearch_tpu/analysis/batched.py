"""Batch-vectorized text analysis (PR 16): killing the ingest analyze wall.

BENCH_r11 `build_profile` put `analyze` at 492 ms of the 684 ms text
build — a per-doc Python loop through `Analyzer.analyze()` that builds
one Token object per term. This module replaces that loop for refresh
bursts with three tiers, cheapest-first:

  - the *device* path packs ASCII standard-analyzer values into a padded
    [values, chars] uint8 tensor and runs classification, case folding
    and segmented polynomial term hashing as ONE jitted program
    (index/device_build.py `analyze_hash_device`); term ids are
    hash-based, with the representative string sliced back out of the
    value text per *unique* term (vocabulary-sized host work, not
    token-sized — DIVERGENCES "Vectorized ingest");
  - the *batched host* path runs each built-in tokenizer as one C-level
    regex pass per value (`findall`) plus numpy aggregation across the
    whole burst — no per-token Python frames, no Token objects;
  - the *host oracle* (`Analyzer.analyze`) stays the semantic ground
    truth: every path is asserted byte-identical to it — same terms,
    same positions (stopword gaps, multi-value +100 gap chaining,
    overlong-token splits), same field-length norms — and any value a
    fast path cannot prove it handles exactly (overlong tokens,
    non-ASCII bytes on device, multi-apostrophe runs) falls back to the
    oracle FOR THAT VALUE ONLY, so parity is structural, not
    probabilistic.

Mode gate: ES_TPU_ANALYZE = host | batched | device; unset means auto
(device when the analyzer qualifies, the burst clears
ES_TPU_ANALYZE_MIN bytes and device build is enabled; batched
otherwise). The shuffled tier-1 lane exports ES_TPU_ANALYZE=host so the
oracle path stays exercised end-to-end. The burst entry point
`analyze_burst` dispatches through `build_stage("build.analyze", ...)`
so the stage is costed (KERNEL_COSTS, bytes-based) and SLO-visible like
every other write-path kernel.
"""

from __future__ import annotations

import os
import unicodedata
from dataclasses import dataclass
from itertools import compress

import numpy as np

from .analyzers import (
    _TOKEN_CHARS_RE,
    _WORD_RE,
    Analyzer,
    KeywordAnalyzer,
    SimpleAnalyzer,
    StandardAnalyzer,
    StopAnalyzer,
    WhitespaceAnalyzer,
)

# values longer than this go to the host path even in device mode: one
# megabyte-sized outlier value would blow up the padded [values, chars]
# tensor for the whole burst
_DEVICE_VALUE_CAP = 8192


def analyze_mode() -> str:
    """ES_TPU_ANALYZE: host | batched | device; anything else -> auto."""
    v = os.environ.get("ES_TPU_ANALYZE", "").strip().lower()
    return v if v in ("host", "batched", "device") else "auto"


def analyze_device_min() -> int:
    """Burst bytes below which the device analyze kernel is not worth
    the dispatch + transfer (auto mode only; ES_TPU_ANALYZE=device
    forces the kernel regardless)."""
    try:
        return int(os.environ.get("ES_TPU_ANALYZE_MIN", str(1 << 16)))
    except ValueError:
        return 1 << 16


def _empty_i64() -> np.ndarray:
    return np.empty(0, np.int64)


def _obj_array(items: list) -> np.ndarray:
    arr = np.empty(len(items), object)
    if items:
        arr[:] = items
    return arr


@dataclass
class ValueTokens:
    """Flat token streams for one burst of text *values*, value-major —
    exactly the oracle's per-value emission order."""

    terms: np.ndarray      # object[T] emitted terms
    value_idx: np.ndarray  # int64[T] index into the burst's value list
    pos_pre: np.ndarray    # int64[T] within-value position (stopword gaps kept)
    last_pos: np.ndarray   # int64[V] max emitted position per value (-1: none)
    counts: np.ndarray     # int64[V] emitted tokens per value
    basis: str             # "host" | "device" — which path produced it


@dataclass
class BurstResult:
    """Per-document token streams for one burst of documents."""

    terms: np.ndarray      # object[T]
    doc_idx: np.ndarray    # int64[T] index into the burst's doc list
    positions: np.ndarray  # int64[T] global within-doc positions
    lengths: np.ndarray    # int64[D] emitted tokens per doc (field-length norm)
    basis: str


class BatchedAnalyzer:
    """Vectorized counterpart of one `Analyzer`. Holds no per-burst
    state, so it is safe to memoize per FieldType
    (Mappings.get_batched_analyzer); the memo is invalidated whenever
    the underlying analyzer object is rebuilt (analysis settings update
    / analysis_generation bump)."""

    def __init__(self, analyzer: Analyzer):
        self.analyzer = analyzer
        t = type(analyzer)
        self._regex = None
        self._nfc = False
        if t is StandardAnalyzer:
            self._regex, self._nfc = _WORD_RE, True
        elif t is WhitespaceAnalyzer:
            self._regex = _TOKEN_CHARS_RE["whitespace"]
        elif t in (SimpleAnalyzer, StopAnalyzer):
            self._regex = _TOKEN_CHARS_RE["letter"]
        self._keyword = t is KeywordAnalyzer
        self.lowercase = bool(analyzer.lowercase)
        self.stopwords = analyzer.stopwords
        self.max_token_length = int(analyzer.max_token_length)
        # the device kernel replicates exactly plain-`standard`
        # semantics: _WORD_RE tokens, lowercase, no stopwords, default
        # length cap — anything else analyzes on host
        self.device_eligible = (
            t is StandardAnalyzer
            and not analyzer.stopwords
            and analyzer.max_token_length == 255
        )

    # ---- per-value paths -------------------------------------------------

    def _oracle_value(self, v: str):
        """Ground truth: the reference per-token chain."""
        toks = self.analyzer.analyze(v)
        if not toks:
            return [], _empty_i64(), -1
        terms = [t.term for t in toks]
        # analyze() emits strictly increasing positions; last == max
        pos = np.fromiter(
            (t.position for t in toks), np.int64, count=len(toks))
        return terms, pos, int(pos[-1])

    def _keyword_value(self, v: str):
        if not v:
            return [], _empty_i64(), -1
        if len(v) > self.max_token_length:
            return self._oracle_value(v)  # overlong split
        return [v], np.zeros(1, np.int64), 0

    def _fast_value(self, v: str):
        """One C regex pass + C-driven map/compress — no per-token
        Python frames. Values with an overlong token fall back to the
        oracle (the split changes the emission structure)."""
        if self._nfc:
            v = unicodedata.normalize("NFC", v)
        toks = self._regex.findall(v)
        if not toks:
            return [], _empty_i64(), -1
        if max(map(len, toks)) > self.max_token_length:
            return self._oracle_value(v)
        if self.lowercase:
            toks = list(map(str.lower, toks))
        n = len(toks)
        sw = self.stopwords
        if sw:
            drop = np.fromiter(map(sw.__contains__, toks), np.bool_, count=n)
            if drop.any():
                keep = ~drop
                pos = np.flatnonzero(keep).astype(np.int64)
                if pos.size == 0:
                    return [], _empty_i64(), -1
                return list(compress(toks, keep)), pos, int(pos[-1])
        return toks, np.arange(n, dtype=np.int64), n - 1

    # ---- burst-of-values entry ------------------------------------------

    def analyze_values(self, values: list[str],
                       mode: str | None = None) -> ValueTokens:
        """All values of one burst -> flat token streams. Dispatch:
        host oracle (mode=host or non-fast-path analyzer), batched
        regex, or the device hash kernel with per-value fallback."""
        if mode is None:
            mode = analyze_mode()
        V = len(values)
        if V and self.device_eligible and mode in ("device", "auto"):
            use_device = mode == "device"
            if not use_device:
                from ..index import device_build as db

                # auto trips to the device kernel only on a real
                # accelerator: on the CPU backend the hash kernel's
                # gather/unique reshuffles lose to the batched-regex
                # host path at every burst size we measured (BENCH_NOTES
                # round 20), so auto-on-CPU = batched. ES_TPU_ANALYZE=
                # device still forces the kernel anywhere (parity tests).
                import jax

                use_device = (sum(map(len, values)) >= analyze_device_min()
                              and db.device_build_enabled()
                              and jax.default_backend() != "cpu")
            if use_device:
                out = self._device_values(values)
                if out is not None:
                    return out
        oracle_all = (mode == "host"
                      or (self._regex is None and not self._keyword))
        term_parts: list[list[str]] = []
        pos_parts: list[np.ndarray] = []
        last_pos = np.full(V, -1, np.int64)
        counts = np.zeros(V, np.int64)
        for i, v in enumerate(values):
            if oracle_all:
                terms, pos, lp = self._oracle_value(v)
            elif self._keyword:
                terms, pos, lp = self._keyword_value(v)
            else:
                terms, pos, lp = self._fast_value(v)
            if terms:
                term_parts.append(terms)
                pos_parts.append(pos)
                counts[i] = len(terms)
                last_pos[i] = lp
        flat: list[str] = []
        for part in term_parts:
            flat.extend(part)
        return ValueTokens(
            terms=_obj_array(flat),
            value_idx=np.repeat(np.arange(V, dtype=np.int64), counts),
            pos_pre=(np.concatenate(pos_parts) if pos_parts
                     else _empty_i64()),
            last_pos=last_pos,
            counts=counts,
            basis="host",
        )

    # ---- device path -----------------------------------------------------

    def _device_values(self, values: list[str]) -> ValueTokens | None:
        """Pack eligible (non-empty ASCII, capped-length) values into a
        padded byte tensor, run the jitted tokenize+hash kernel, slice
        representative strings per unique term, and merge per-value
        oracle fallbacks back in original value order. Returns None
        when the burst doesn't fit the kernel's transfer budget (caller
        degrades to the batched host path)."""
        from ..index import device_build as db

        V = len(values)
        ok = np.fromiter(
            (0 < len(v) <= _DEVICE_VALUE_CAP and v.isascii()
             for v in values),
            np.bool_, count=V)
        idx_dev = np.flatnonzero(ok)
        if idx_dev.size == 0:
            return None
        dev_vals = [values[i] for i in idx_dev]
        lens = np.fromiter(map(len, dev_vals), np.int64,
                           count=len(dev_vals))
        B, L = len(dev_vals), int(lens.max())
        chars = np.zeros((B, L), np.uint8)
        # row-major boolean scatter: valid slots fill from the
        # concatenated byte buffer in one vectorized assignment
        valid = np.arange(L)[None, :] < lens[:, None]
        chars[valid] = np.frombuffer(
            "".join(dev_vals).encode("ascii"), np.uint8)
        res = db.analyze_hash_device(chars, lens.astype(np.int32))
        if res is None:
            return None
        start, end, joiner, h1, h2 = res
        sr, sc = np.nonzero(start)
        er, ec = np.nonzero(end)
        # start/end masks pair 1:1 in row-major order (token segments
        # never nest); sr == er elementwise by construction
        tok_len = (ec - sc + 1).astype(np.int64)
        if er.size:
            jcum = np.cumsum(joiner, axis=1)
            njoin = jcum[er, ec] - jcum[er, sc]  # start is never a joiner
        else:
            njoin = np.zeros(0, np.int64)
        # _WORD_RE admits at most ONE apostrophe join per token and caps
        # length at 255; rows violating either re-analyze on host
        bad_rows = np.unique(er[(njoin > 1) | (tok_len > 255)])
        good = ~np.isin(er, bad_rows)
        g_er, g_sc, g_ec = er[good], sc[good], ec[good]
        # within-value ordinal == oracle position (no stopwords here)
        first_of_row = np.searchsorted(er, er)
        ordinal = (np.arange(er.size) - first_of_row)[good]
        # group by (h1, h2, len): hash-based term identity; the
        # representative string is sliced from the value text once per
        # UNIQUE term (.lower() is 1:1 on ASCII)
        gkey = np.stack(
            [h1[er, ec].astype(np.int64)[good],
             h2[er, ec].astype(np.int64)[good],
             tok_len[good]], axis=1)
        if gkey.shape[0]:
            _, rep, inv = np.unique(gkey, axis=0, return_index=True,
                                    return_inverse=True)
            reps = _obj_array([
                dev_vals[int(r)][int(s):int(e) + 1].lower()
                for r, s, e in zip(g_er[rep], g_sc[rep], g_ec[rep])])
            dev_terms = reps[inv.ravel()]
        else:
            dev_terms = _obj_array([])
        dev_val_idx = idx_dev[g_er]
        # per-value counts/last_pos for device-handled rows
        counts = np.zeros(V, np.int64)
        last_pos = np.full(V, -1, np.int64)
        row_counts = np.bincount(g_er, minlength=B)
        counts[idx_dev] = row_counts
        last_pos[idx_dev] = row_counts - 1
        # host fallback: ineligible values + rows the kernel flagged
        fb = np.zeros(V, np.bool_)
        fb[~ok] = True
        fb[idx_dev[bad_rows]] = True
        fb_terms: list[str] = []
        fb_val_parts: list[np.ndarray] = []
        fb_pos_parts: list[np.ndarray] = []
        for i in np.flatnonzero(fb):
            terms, pos, lp = self._fast_value(values[i])
            counts[i] = len(terms)
            last_pos[i] = lp
            if terms:
                fb_terms.extend(terms)
                fb_val_parts.append(np.full(len(terms), i, np.int64))
                fb_pos_parts.append(pos)
        if fb_terms:
            all_terms = np.concatenate([dev_terms, _obj_array(fb_terms)])
            all_val = np.concatenate(
                [dev_val_idx, np.concatenate(fb_val_parts)])
            all_pos = np.concatenate(
                [ordinal.astype(np.int64),
                 np.concatenate(fb_pos_parts)])
            # stable sort restores value order; a value's tokens come
            # from exactly one segment, so within-value order survives
            order = np.argsort(all_val, kind="stable")
            all_terms = all_terms[order]
            all_val = all_val[order]
            all_pos = all_pos[order]
        else:
            all_terms, all_val = dev_terms, dev_val_idx.astype(np.int64)
            all_pos = ordinal.astype(np.int64)
        return ValueTokens(all_terms, all_val, all_pos, last_pos, counts,
                           basis="device")


def analyze_burst(batched: BatchedAnalyzer, values: list[str],
                  value_doc: np.ndarray, n_docs: int,
                  mode: str | None = None) -> BurstResult:
    """Doc-level burst analysis: flat `values` with their doc index
    (doc-major sorted), positions chained with the +100 multi-value gap
    — byte-identical to PackBuilder.add_document's per-doc loop. The
    whole burst is ONE costed `build.analyze` dispatch (bytes-based
    KERNEL_COSTS entry), so mfu/bw attribution and the slo.write
    analyze floor see it like any other build kernel."""
    from ..monitoring.refresh_profile import build_stage

    if mode is None:
        mode = analyze_mode()
    V = len(values)
    value_doc = np.asarray(value_doc, np.int64)
    nbytes = sum(map(len, values))
    with build_stage("build.analyze", nbytes=nbytes, values=V,
                     docs=int(n_docs)):
        vt = batched.analyze_values(values, mode=mode)
        # per-value position bases: within-doc exclusive cumsum of
        # (last_emitted_pos + 1 + 100), the reference
        # position_increment_gap chaining
        inc = vt.last_pos + 101
        csum = np.cumsum(inc)
        excl = csum - inc
        first = np.ones(V, np.bool_)
        if V:
            first[1:] = value_doc[1:] != value_doc[:-1]
            group = np.cumsum(first) - 1
            base_v = excl - excl[first][group]
        else:
            base_v = excl
        positions = base_v[vt.value_idx] + vt.pos_pre
        doc_idx = value_doc[vt.value_idx]
        lengths = np.bincount(doc_idx, minlength=n_docs).astype(np.int64)
        return BurstResult(vt.terms, doc_idx, positions, lengths, vt.basis)
