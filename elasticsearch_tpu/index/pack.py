"""HBM-resident index pack format: blocked-CSR postings + columnar DocValues.

This is the TPU replacement for Lucene's on-disk segment format (reference
behavior: Lucene 9 postings/doc-values read through ES's codec layer,
server/.../index/codec/PerFieldMapperCodec.java:37). Design drivers
(SURVEY.md §7 hard part #1 — XLA wants static shapes):

- Postings are ragged per term; we store them as fixed-size BLOCK=128 rows in
  two dense matrices `post_docids`/`post_tfs` of shape [num_blocks, BLOCK],
  with a CSR directory `term_block_start[T+1]` mapping term-id -> row range.
  Row 0 is reserved as an all-padding block so query-time block lists can be
  padded with 0. Padding doc slots hold `num_docs` (a sentinel that scatters
  into a dead accumulator slot).
- Per-block `block_max_tf` / `block_min_len` support block-max pruning
  (the TPU analog of Lucene's block-max WAND skipping: whole blocks are
  masked out by an upper-bound score test instead of branchy skipping).
- Norms store the *dequantized* Lucene 1-byte doc length (smallfloat.py) so
  BM25 matches a CPU Elasticsearch bit-for-bit.
- DocValues are plain columns: int64/float32 values + presence mask, or
  sorted-ordinal int32 + host-side term dictionary for keywords (the analog
  of Lucene sorted-set doc values feeding
  GlobalOrdinalsStringTermsAggregator.java:61).
- Dense vectors are a row-major [N, dims] float32 matrix; exact scoring is a
  single MXU matmul (reference analog: index/codec/vectors/ HNSW formats —
  on TPU, brute-force matmul + top_k beats graph walks for shard-sized N).

All arrays build host-side in numpy; `to_device()` ships them to HBM once.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any

import numpy as np

from .mappings import (
    Mappings,
    TEXT_TYPES,
    KEYWORD_TYPES,
    IP_TYPES,
    INT_TYPES,
    FLOAT_TYPES,
    DATE_TYPES,
    DATE_NANOS_TYPES,
    BOOL_TYPES,
    VECTOR_TYPES,
    ip_sort_key,
)
from .smallfloat import quantize_lengths

BLOCK = 128  # TPU lane width; one postings block = one vector register row

# BM25 defaults baked into dense-tier tfn rows (reference behavior:
# index/similarity/SimilarityService.java:43-58 — BM25 k1=1.2, b=0.75)
BM25_K1 = 1.2
BM25_B = 0.75

# ---------------------------------------------------------------------------
# impact-scored sparse tier (BM25S, https://arxiv.org/pdf/2407.03618):
# per-(term, doc) BM25 contributions precomputed at index time and
# quantized to compact integer codes, so query time is a pure gather+sum
# over code blocks — no tf / doc-length / avgdl math in the hot path.
#
# Factorization (what lives where):
#   impact(t, d) = idf(t) · tfn(t, d),  tfn = tf / (tf + K(dl, avgdl))
#   code(t, d)   = round(tfn / ubf(t) · QMAX) ∈ [1, QMAX] for tf > 0
#   ubf(t)       = max_tf / (max_tf + k1·(1 − b))   — tfn's upper bound
#                  over ANY doc length (K ≥ k1·(1 − b)), so codes can
#                  never clip however avgdl drifts between refreshes
#   score(t, d)  = boost · idf(t) · ubf(t) / QMAX · code(t, d)
#
# idf stays a per-term query-time scalar (ONE host mul in prepare,
# sourced from ops/scoring.bm25_idf — the single idf implementation), so
# dfs-stats overrides flow into the impact weights with no rebuild; only
# avgdl drift requires re-deriving the codes (an elementwise device pass
# at refresh, parallel/sharded.StackedSearcher.refresh_impacts).
#
# Error model (documented, asserted in tests/test_impact.py): per query
# term the absolute score error is at most boost · idf · ubf / QMAX
# (codes round to the nearest level, half a level each way; the clamp to
# code ≥ 1 that preserves exact match/total semantics can round a
# sub-half-level impact up by at most one full level). Per-doc error is
# the sum over the query's impact-served terms. uint16 keeps this below
# f32 tie noise; int8 is the compact/coarse alternative.
# ---------------------------------------------------------------------------

IMPACT_QMAX = {"uint16": 65535, "int8": 127}
_IMPACT_NP_DTYPE = {"uint16": np.uint16, "int8": np.int8}


def impact_dtype_default() -> str:
    """Impact-code storage dtype: ES_TPU_IMPACT_DTYPE ∈ {uint16, int8}."""
    import os

    d = os.environ.get("ES_TPU_IMPACT_DTYPE", "uint16")
    return d if d in IMPACT_QMAX else "uint16"


def impact_term_ubf(term_block_start: np.ndarray, block_max_tf: np.ndarray,
                    k1: float = BM25_K1, b: float = BM25_B) -> np.ndarray:
    """[T] per-term tfn upper bound mtf/(mtf + k1·(1−b)) from the pack's
    block-max metadata — avgdl-independent, so the per-term code scale
    survives dfs-stats drift without clipping."""
    T = len(term_block_start) - 1
    if T <= 0:
        return np.zeros(0, np.float32)
    # every term owns >= 1 contiguous block row, so reduceat is exact
    mtf = np.maximum.reduceat(block_max_tf, term_block_start[:-1])
    return (mtf / np.maximum(mtf + k1 * (1.0 - b), 1e-9)).astype(np.float32)


def impact_row_terms(term_block_start: np.ndarray,
                     total_blocks: int) -> np.ndarray:
    """[total_blocks] term id of each postings block row (-1 for the
    reserved padding row 0 / rows past the directory)."""
    out = np.full(total_blocks, -1, np.int32)
    T = len(term_block_start) - 1
    if T > 0:
        counts = term_block_start[1:] - term_block_start[:-1]
        out[term_block_start[0]: term_block_start[T]] = np.repeat(
            np.arange(T, dtype=np.int32), counts)
    return out


def impact_row_params(
    row_terms: np.ndarray,          # [nb] int32 (-1 = padding)
    term_ubf: np.ndarray,           # [T] f32
    field_of_term: np.ndarray,      # [T] int
    avgdl_of_field: np.ndarray,     # [F] f64/f32 (effective stats)
    has_norms_of_field: np.ndarray,  # [F] bool
    qmax: int,
    k1: float = BM25_K1,
    b: float = BM25_B,
):
    """-> (k_base [nb], k_slope [nb], scale_inv [nb]) f32 per-row code
    parameters: K(dl) = k_base + k_slope·dl, code = tfn·scale_inv. The
    only stats-dependent piece is k_slope (k1·b/avgdl), recomputed from
    the EFFECTIVE field stats at every (re)derivation."""
    t = row_terms
    safe_t = np.maximum(t, 0)
    fcode = field_of_term[safe_t]
    hn = has_norms_of_field[fcode] & (t >= 0)
    k_base = np.where(hn, k1 * (1.0 - b), k1).astype(np.float32)
    k_slope = np.where(
        hn, k1 * b / np.maximum(avgdl_of_field[fcode], 1e-9), 0.0
    ).astype(np.float32)
    scale_inv = np.where(
        t >= 0, qmax / np.maximum(term_ubf[safe_t], 1e-9), 0.0
    ).astype(np.float32)
    return k_base, k_slope, scale_inv


def impact_codes_host(post_tfs: np.ndarray, post_dls: np.ndarray,
                      k_base: np.ndarray, k_slope: np.ndarray,
                      scale_inv: np.ndarray, qmax: int,
                      dtype: str) -> np.ndarray:
    """Quantized impact codes (numpy twin of the device derivation in
    parallel/sharded.StackedSearcher.refresh_impacts — the two are
    asserted equal by tests/test_impact.py). Shapes broadcast: per-row
    params [..., nb] against blocked lanes [..., nb, BLOCK]."""
    K = k_base[..., None] + k_slope[..., None] * post_dls
    tfn = post_tfs / (post_tfs + K)  # tf == 0 padding -> 0
    q = np.rint(tfn * scale_inv[..., None])
    q = np.clip(q, 1, qmax)  # tf > 0 must stay a match (code >= 1)
    q = np.where(post_tfs > 0, q, 0)
    return q.astype(_IMPACT_NP_DTYPE[dtype])

# Position keys: docid * POS_L + position, in blocked sorted int64 arrays.
# POS_L is a GLOBAL constant (not per-pack) so one traced phrase program
# serves every shard of a mesh. 2^17 positions per doc ~ Lucene's practical
# token limit; key range fits int64 with room for the +INF padding sentinel.
POS_L = 1 << 17
POS_INF = np.int64(1) << 62


def _parse_geo_point(v):
    """ES geo_point forms -> (lat, lon): {"lat","lon"} | "lat,lon" |
    [lon, lat] (GeoJSON order!) | {"type": "Point", "coordinates": [lon,lat]}
    (reference behavior: common/geo/GeoPoint.java parsing)."""
    try:
        if isinstance(v, dict):
            if "lat" in v and "lon" in v:
                return float(v["lat"]), float(v["lon"])
            if v.get("type", "").lower() == "point" and v.get("coordinates"):
                lon, lat = v["coordinates"][:2]
                return float(lat), float(lon)
            return None
        if isinstance(v, str):
            lat_s, lon_s = v.split(",", 1)
            return float(lat_s), float(lon_s)
        if isinstance(v, (list, tuple)) and len(v) >= 2:
            return float(v[1]), float(v[0])
    except (ValueError, TypeError):
        from ..utils.errors import MapperParsingError

        raise MapperParsingError(f"failed to parse geo_point value [{v!r}]")
    return None


def default_dense_min_df(n_docs: int) -> int:
    """df threshold above which a term moves to the dense tier. ~1 posting
    per 2 doc-chunks: dense rows then cost at most ~2x their CSR form."""
    return max(64, n_docs // 256)


def compute_tfn(
    tfs: np.ndarray, dls: np.ndarray | None, avgdl: float, has_norms: bool
) -> np.ndarray:
    """Host-side tf/(tf + K): the doc-length-normalized BM25 tf saturation."""
    if has_norms:
        K = BM25_K1 * (1.0 - BM25_B + BM25_B * dls / avgdl)
    else:
        K = BM25_K1
    return (tfs / (tfs + K)).astype(np.float32)


@dataclass
class DocValuesColumn:
    kind: str  # "int" | "float" | "ord"
    values: np.ndarray  # [N] int64 | float32 | int32 ordinals (-1 = missing)
    has_value: np.ndarray  # [N] bool
    ord_terms: list[str] | None = None  # sorted terms for kind == "ord"
    # terms-agg support for numeric columns: sorted unique values + per-doc
    # ordinal (the analog of Lucene sorted-numeric global ordinals)
    uniq_values: np.ndarray | None = None  # [V] int64
    uniq_ords: np.ndarray | None = None  # [N] int32 (-1 = missing)
    # column min/max over present values (static histogram bucket planning)
    vmin: float | int = 0
    vmax: float | int = 0
    # multi-valued keyword support: (doc, ordinal) pairs covering EVERY
    # value (the single-value arrays above keep first-value semantics for
    # sort/collapse); None when no doc has >1 value
    mv_pair_docs: np.ndarray | None = None  # [P] int32 sorted by doc
    mv_pair_ords: np.ndarray | None = None  # [P] int32


@dataclass
class VectorColumn:
    values: np.ndarray  # [N, dims] float32
    has_value: np.ndarray  # [N] bool
    similarity: str  # cosine | dot_product | l2_norm
    dims: int
    # optional device-resident ANN index (ann/index.build_ann output:
    # IVF partitions packed into padded cluster tiles + int8 tier)
    ann: dict | None = None
    # selection-scan tier for the ANN path (mapping index_options)
    ann_quant: str = "int8"


@dataclass
class ShardPack:
    """Immutable packed index for one shard (host-side numpy form)."""

    num_docs: int
    # postings
    post_docids: np.ndarray  # [num_blocks, BLOCK] int32; pad = num_docs
    post_tfs: np.ndarray  # [num_blocks, BLOCK] float32; pad = 0
    post_dls: np.ndarray  # [num_blocks, BLOCK] float32 doc length per posting; pad = 1
    term_block_start: np.ndarray  # [T+1] int32 (row ranges; row 0 reserved)
    term_df: np.ndarray  # [T] int32
    block_max_tf: np.ndarray  # [num_blocks] float32
    block_min_len: np.ndarray  # [num_blocks] float32 (min quantized dl in block)
    # term dictionary: (field, term) -> tid
    term_dict: dict[tuple[str, str], int]
    # norms per text field
    norms: dict[str, np.ndarray]  # field -> [N] float32 (dequantized lengths)
    # text-field presence (a value existed, even if it analyzed to 0 tokens)
    text_present: dict[str, np.ndarray]  # field -> [N] bool
    field_stats: dict[str, dict]  # field -> {sum_dl, doc_count} (exact, for avgdl)
    # columnar docvalues
    docvalues: dict[str, DocValuesColumn]
    vectors: dict[str, VectorColumn]
    live: np.ndarray  # [N] bool live-docs bitmap (deletes)
    # dense tier: terms with df >= dense_min_df stored as precomputed
    # tf/(tf+K) rows [V_dense, N] — scored on the MXU (matmul / elementwise)
    # with no gather or scatter. K bakes this pack's avgdl and BM25 defaults.
    dense_tfn: np.ndarray | None = None
    dense_dict: dict[tuple[str, str], int] = dc_field(default_factory=dict)
    # positions (phrase queries): blocked sorted int64 keys docid*POS_L+pos;
    # pad lanes = POS_INF; row 0 reserved all-padding (query lists 0-pad)
    pos_keys: np.ndarray | None = None  # [num_pos_blocks, BLOCK] int64
    term_pos_start: np.ndarray | None = None  # [T+1] int32 block row ranges
    term_pos_count: np.ndarray | None = None  # [T] int32 total positions
    # completion-suggester inputs, host-side only:
    # field -> sorted list of (input, weight, docid)
    completion: dict[str, list] = dc_field(default_factory=dict)
    # percolator queries, host-side only: field -> list of (docid, query_dict)
    percolator: dict[str, list] = dc_field(default_factory=dict)
    # impact-scored sparse tier (BM25S): quantized per-posting BM25
    # contributions aligned with post_docids, per-term tfn bounds, and the
    # quantization contract. None = tier absent (old manifests degrade to
    # the raw-postings scoring path).
    impact_codes: np.ndarray | None = None  # [num_blocks, BLOCK] u16|i8
    impact_ubf: np.ndarray | None = None  # [T] f32 per-term tfn bound
    impact_meta: dict | None = None  # {"dtype", "qmax", "k1", "b"}

    def dense_row_of(self, fld: str, term: str) -> int | None:
        return self.dense_dict.get((fld, term))

    @property
    def num_blocks(self) -> int:
        return self.post_docids.shape[0]

    @property
    def num_terms(self) -> int:
        return len(self.term_df)

    def avgdl(self, fld: str) -> float:
        st = self.field_stats.get(fld)
        if not st or st["doc_count"] == 0:
            return 1.0
        return st["sum_dl"] / st["doc_count"]

    def term_id(self, fld: str, term: str) -> int | None:
        return self.term_dict.get((fld, term))

    def term_blocks(self, fld: str, term: str) -> tuple[int, int, int]:
        """-> (block_row_start, n_blocks, df); (0, 0, 0) when term absent."""
        tid = self.term_dict.get((fld, term))
        if tid is None:
            return 0, 0, 0
        s = int(self.term_block_start[tid])
        e = int(self.term_block_start[tid + 1])
        return s, e - s, int(self.term_df[tid])

    def impact_served(self) -> bool:
        """Whether sparse terms can score from the impact tier's codes."""
        return (self.impact_codes is not None and self.impact_meta is not None
                and self.impact_ubf is not None)

    def impact_wscale(self, fld: str, term: str) -> float | None:
        """ubf(t)/QMAX — the per-term dequantization scale of the impact
        tier; the query-time term weight is boost · idf · this. None when
        the tier is absent or the term unknown (caller falls back to the
        raw-postings path)."""
        if (self.impact_codes is None or self.impact_meta is None
                or self.impact_ubf is None):
            return None
        tid = self.term_dict.get((fld, term))
        if tid is None:
            return None
        return float(self.impact_ubf[tid]) / self.impact_meta["qmax"]

    def term_pos_blocks(self, fld: str, term: str) -> tuple[int, int, int]:
        """-> (pos_block_row_start, n_blocks, n_positions); zeros if absent."""
        tid = self.term_dict.get((fld, term))
        if tid is None or self.term_pos_start is None:
            return 0, 0, 0
        s = int(self.term_pos_start[tid])
        e = int(self.term_pos_start[tid + 1])
        return s, e - s, int(self.term_pos_count[tid])

    def terms_for_field(self, fld: str) -> list[str]:
        """Sorted terms of one field (host-side term dictionary slice — the
        analog of Lucene's per-field FST enum, used by multi-term query
        expansion: prefix/wildcard/regexp/fuzzy). Cached per field."""
        cache = getattr(self, "_field_terms_cache", None)
        if cache is None:
            cache = self._field_terms_cache = {}
        terms = cache.get(fld)
        if terms is None:
            # term_dict iteration order is sorted (field, term): build() sorts
            terms = cache[fld] = [t for (f, t) in self.term_dict if f == fld]
        return terms


def _standard_fast_path(analyzer) -> bool:
    """The C accumulator's ASCII tokenizer is exactly this analyzer: the
    plain standard one, no stop words, 255-char tokens."""
    from ..analysis.analyzers import StandardAnalyzer

    return (type(analyzer) is StandardAnalyzer and not analyzer.stopwords
            and analyzer.max_token_length == 255)


class PackBuilder:
    """Accumulates parsed documents for one shard, then packs.

    The mutable in-memory form here plays the role of Lucene's IndexWriter
    RAM buffer (reference: index/engine/InternalEngine.java:1387 feeding
    IndexWriter.addDocuments); `build()` is the "refresh" that produces an
    immutable searchable pack.
    """

    def __init__(self, mappings: Mappings, use_native: bool | None = None):
        self.mappings = mappings
        # (field, term) -> {docid: tf}
        self.postings: dict[tuple[str, str], dict[int, int]] = {}
        # (field, term) -> {docid: [positions]} (phrase support)
        self.positions: dict[tuple[str, str], dict[int, list[int]]] = {}
        self.doc_field_lengths: dict[str, list[tuple[int, int]]] = {}
        # field -> (last_docid_seen, docs_with_field); docids arrive in order
        self.field_doc_counts: dict[str, list[int]] = {}
        self.docvalue_raw: dict[str, list[tuple[int, Any]]] = {}
        self.vector_raw: dict[str, list[tuple[int, list[float]]]] = {}
        self.completion_raw: dict[str, list[tuple[str, int, int]]] = {}
        self.percolator_raw: dict[str, list] = {}
        self.mv_extra_raw: dict[str, list] = {}  # extra keyword values beyond the first
        self.num_docs = 0
        # C++ accumulator owns the per-token hot loop when available
        # (native/packing.cpp); dict fallback otherwise. Packs are
        # bit-compatible either way (tests/test_native.py).
        self._native = None
        if use_native is not False:
            from .. import native as native_mod

            if native_mod.available():
                from ..native.accumulator import NativeAccumulator

                self._native = NativeAccumulator()
            elif use_native:
                raise RuntimeError("native packing requested but unavailable")

    def add_document(self, parsed: dict[str, list], doc_id: str | None = None,
                     skip_text: bool = False) -> int:
        """parsed = Mappings.parse_document output; returns local docid.
        doc_id, when given, is stored in the reserved `_id` ordinal column so
        ids queries/sorts run on device (the reference indexes _id as a
        keyword-like metadata field, index/mapper/IdFieldMapper.java).
        skip_text leaves indexed text fields to the caller — the
        batch-analysis path (add_documents_batch) routes them through
        one vectorized analyze dispatch per field instead."""
        docid = self.num_docs
        self.num_docs += 1
        if doc_id is not None:
            self.docvalue_raw.setdefault("_id", []).append((docid, str(doc_id)))
        for fld, values in parsed.items():
            ft = self.mappings.fields.get(fld)
            if ft is None:
                continue
            t = ft.type
            if t in TEXT_TYPES:
                if not ft.index or skip_text:
                    continue
                analyzer = ft.get_analyzer()
                if self._native is not None:
                    self._add_text_native(fld, docid, analyzer, values)
                    continue
                length = 0
                counts: dict[str, int] = {}
                pos_lists: dict[str, list[int]] = {}
                pos_base = 0
                for v in values:
                    last_pos = -1
                    for tok in analyzer.analyze(v):
                        counts[tok.term] = counts.get(tok.term, 0) + 1
                        pos = pos_base + tok.position
                        # positions beyond the key range are dropped (the doc
                        # still matches term queries; phrases can't see its
                        # tail — the analog of Lucene's MAX_POSITION bound,
                        # made lossy instead of fatal so one oversized doc
                        # can't poison every later refresh)
                        if pos < POS_L - 64:
                            pos_lists.setdefault(tok.term, []).append(pos)
                        last_pos = max(last_pos, tok.position)
                        length += 1
                    # multi-valued text: position gap between values
                    # (reference behavior: TextFieldMapper position_increment_gap
                    # default 100)
                    pos_base += last_pos + 1 + 100
                for term, tf in counts.items():
                    self.postings.setdefault((fld, term), {})[docid] = tf
                    if term in pos_lists:
                        self.positions.setdefault((fld, term), {})[docid] = pos_lists[term]
                self.doc_field_lengths.setdefault(fld, []).append((docid, length))
            elif t in KEYWORD_TYPES or t in IP_TYPES:
                kept = []
                for v in values:
                    if ft.ignore_above is not None and len(v) > ft.ignore_above:
                        continue
                    kept.append(v)
                if ft.index and kept:
                    if self._native is not None:
                        self._native.add_tokens(fld, docid, list(set(kept)), None)
                    else:
                        for v in set(kept):
                            p = self.postings.setdefault((fld, v), {})
                            p[docid] = p.get(docid, 0) + 1
                    fc = self.field_doc_counts.setdefault(fld, [-1, 0])
                    if fc[0] != docid:
                        fc[0] = docid
                        fc[1] += 1
                if ft.doc_values and kept:
                    # first value drives sort/collapse; ALL values feed the
                    # multi-value pair arrays for terms/cardinality aggs
                    self.docvalue_raw.setdefault(fld, []).append((docid, kept[0]))
                    if len(set(kept)) > 1:
                        self.mv_extra_raw.setdefault(fld, []).extend(
                            (docid, v) for v in sorted(set(kept))
                            if v != kept[0]
                        )
            elif (t in INT_TYPES or t in DATE_TYPES
                  or t in DATE_NANOS_TYPES or t in BOOL_TYPES):
                if ft.doc_values and values:
                    self.docvalue_raw.setdefault(fld, []).append((docid, int(values[0])))
            elif t in FLOAT_TYPES:
                if ft.doc_values and values:
                    self.docvalue_raw.setdefault(fld, []).append((docid, float(values[0])))
            elif t == "geo_point":
                for v in values:
                    latlon = _parse_geo_point(v)
                    if latlon is not None:
                        self.docvalue_raw.setdefault(f"{fld}#lat", []).append(
                            (docid, latlon[0]))
                        self.docvalue_raw.setdefault(f"{fld}#lon", []).append(
                            (docid, latlon[1]))
                        break  # single-valued column: first point wins
            elif t == "percolator":
                for v in values:
                    if not isinstance(v, dict):
                        from ..utils.errors import MapperParsingError

                        raise MapperParsingError(
                            f"percolator field [{fld}] requires a query object"
                        )
                    self.percolator_raw.setdefault(fld, []).append((docid, v))
            elif t == "completion":
                for v in values:
                    if isinstance(v, dict):
                        inputs = v.get("input") or []
                        if isinstance(inputs, str):
                            inputs = [inputs]
                        weight = int(v.get("weight", 1))
                    elif isinstance(v, list):
                        inputs, weight = v, 1
                    else:
                        inputs, weight = [v], 1
                    for inp in inputs:
                        self.completion_raw.setdefault(fld, []).append(
                            (str(inp), weight, docid)
                        )
            elif t in VECTOR_TYPES:
                if values:
                    if len(values) != ft.dims:
                        from ..utils.errors import MapperParsingError

                        raise MapperParsingError(
                            f"dense_vector [{fld}] has {len(values)} dims, mapping says {ft.dims}"
                        )
                    self.vector_raw.setdefault(fld, []).append((docid, [float(x) for x in values]))
        return docid

    def _add_text_native(self, fld: str, docid: int, analyzer, values):
        """Text-field token routing into the C++ accumulator. The ASCII fast
        path requires exact standard-analyzer semantics; anything else is
        Python-analyzed and fed as pre-tokenized terms."""
        nat = self._native
        fast = _standard_fast_path(analyzer)
        length = 0
        pos_base = 0
        for v in values:
            ret = nat.add_text(fld, docid, v, pos_base) if fast else -1
            if ret < 0:
                toks = analyzer.analyze(v)
                nat.add_tokens(
                    fld, docid,
                    [tk.term for tk in toks],
                    [pos_base + tk.position for tk in toks],
                )
                last_pos = max((tk.position for tk in toks), default=-1)
                length += len(toks)
                pos_base += last_pos + 1 + 100
            else:
                length += ret
                pos_base += ret + 100
        self.doc_field_lengths.setdefault(fld, []).append((docid, length))

    def add_documents_batch(self, parsed_docs: list[dict],
                            doc_ids: list | None = None) -> list[int]:
        """Batch add: one vectorized analyze dispatch per text field
        across the whole burst (analysis/batched.py) feeding the same
        accumulator state as N add_document calls — asserted
        byte-identical by tests/test_batched_analysis.py. Non-text
        fields ride the per-doc path unchanged (they were never the
        wall). ES_TPU_ANALYZE=host degrades to the reference per-doc
        loop. Returns the local docids."""
        from ..analysis.batched import analyze_burst, analyze_mode

        if doc_ids is None:
            doc_ids = [None] * len(parsed_docs)
        mode = analyze_mode()
        if mode == "host":
            from ..monitoring.refresh_profile import refresh_stage

            with refresh_stage("analyze"):
                return [self.add_document(p, doc_id=d)
                        for p, d in zip(parsed_docs, doc_ids)]
        docids: list[int] = []
        # field -> (docids-with-field, flat values, value->doc ordinal)
        bursts: dict[str, tuple[list[int], list[str], list[int]]] = {}
        for parsed, doc_id in zip(parsed_docs, doc_ids):
            docid = self.add_document(parsed, doc_id=doc_id, skip_text=True)
            docids.append(docid)
            for fld, values in parsed.items():
                ft = self.mappings.fields.get(fld)
                if ft is None or ft.type not in TEXT_TYPES or not ft.index:
                    continue
                fdocs, vals, vdoc = bursts.setdefault(fld, ([], [], []))
                d_ord = len(fdocs)
                fdocs.append(docid)
                vals.extend(values)
                vdoc.extend([d_ord] * len(values))
        for fld, (fdocs, vals, vdoc) in bursts.items():
            ba = self.mappings.fields[fld].get_batched_analyzer()
            if self._native_burst_eligible(ba, vals, mode):
                self._ingest_text_burst_native(fld, fdocs, vals, vdoc, ba)
                continue
            burst = analyze_burst(
                ba, vals, np.asarray(vdoc, np.int64), len(fdocs), mode=mode)
            self._ingest_text_burst(fld, fdocs, burst)
        return docids

    def _native_burst_eligible(self, ba, vals: list[str], mode: str) -> bool:
        """auto + C accumulator + plain standard analyzer: the C
        tokenizer (builder_add_text) is the measured-fastest
        analyze+insert route at every burst size, on the CPU (BENCH_NOTES
        round 20) and on a v5e's host (PERF.md, PR 29: 22-43 us a document
        against the device hash kernel's 90, and 57 against 191 with four
        shards' builders at once), and is byte-compatible with the oracle
        by the per-doc path's own contract, so auto takes it on every
        backend. Forced modes (host/batched/device) never take this
        route: their dispatch is the thing the parity tests pin down."""
        return (mode == "auto" and self._native is not None
                and ba.device_eligible)

    def _ingest_text_burst_native(self, fld: str, fdocs: list[int],
                                  vals: list[str], vdoc: list[int],
                                  ba) -> None:
        """One field's whole burst through the C accumulator under a
        single costed `build.analyze` dispatch. Routing is per doc via
        _add_text_native (identical chaining, non-ASCII per-value
        fallback), so state parity with N add_document calls holds by
        construction; what the batch buys is one stage dispatch and no
        per-doc Python parse/setup between values."""
        from ..monitoring.refresh_profile import build_stage

        with build_stage("build.analyze", nbytes=sum(map(len, vals)),
                         values=len(vals), docs=len(fdocs)):
            if self._add_texts_native(fld, fdocs, vals, vdoc, ba.analyzer):
                return
            i = 0
            n = len(vdoc)
            for d_ord, docid in enumerate(fdocs):
                j = i
                while j < n and vdoc[j] == d_ord:
                    j += 1
                self._add_text_native(fld, docid, ba.analyzer, vals[i:j])
                i = j

    def _add_texts_native(self, fld: str, fdocs: list[int], vals: list[str],
                          vdoc: list[int], analyzer) -> bool:
        """The burst in ONE call of the C accumulator where every value is
        ASCII and the analyzer is the plain standard one: the state N
        _add_text_native calls leave, without the interpreter lock taken
        and given back once a document (four shards' builders at once
        otherwise spend their time handing it to each other). False, and
        nothing added, where the burst must go document by document."""
        if not _standard_fast_path(analyzer) or not vals:
            return False
        docid_of = np.asarray(fdocs, np.int32)
        v_ord = np.asarray(vdoc, np.int64)
        counts = self._native.add_texts(fld, docid_of[v_ord], vals)
        if counts is None:
            return False
        lengths = np.bincount(v_ord, weights=counts,
                              minlength=len(fdocs)).astype(np.int64)
        self.doc_field_lengths.setdefault(fld, []).extend(
            zip(fdocs, lengths.tolist()))
        return True

    def _ingest_text_burst(self, fld: str, docids: list[int], burst) -> None:
        """Route one analyzed burst into the accumulator — the batch
        twin of the per-doc text branch: same postings/positions/
        field-length state, same POS_L bound on stored positions (term
        frequencies and lengths still count past it)."""
        bounds = np.zeros(len(docids) + 1, np.int64)
        np.cumsum(burst.lengths, out=bounds[1:])
        if self._native is not None:
            terms = burst.terms.tolist()
            pos = burst.positions.tolist()
            for k, docid in enumerate(docids):
                s, e = int(bounds[k]), int(bounds[k + 1])
                # unfiltered positions, like _add_text_native: the C++
                # accumulator applies the position bound itself
                self._native.add_tokens(fld, docid, terms[s:e], pos[s:e])
                self.doc_field_lengths.setdefault(fld, []).append(
                    (docid, int(burst.lengths[k])))
            return
        T = int(burst.terms.size)
        if T:
            # intern terms -> codes, then group tokens by (term, doc) in
            # one stable sort; each segment is one posting
            vocab: dict[str, int] = {}
            terms = burst.terms.tolist()
            tcode = np.fromiter(
                (vocab.setdefault(t, len(vocab)) for t in terms),
                np.int64, count=T)
            uniq = list(vocab)
            D = len(docids)
            key = tcode * D + burst.doc_idx
            order = np.argsort(key, kind="stable")
            ks = key[order]
            seg = np.flatnonzero(
                np.concatenate([[True], ks[1:] != ks[:-1]]))
            seg_end = np.concatenate([seg[1:], [ks.size]])
            pos_sorted = burst.positions[order]
            for s, e in zip(seg.tolist(), seg_end.tolist()):
                k = int(ks[s])
                term = uniq[k // D]
                docid = docids[k % D]
                self.postings.setdefault((fld, term), {})[docid] = e - s
                pl = pos_sorted[s:e]
                pl = pl[pl < POS_L - 64]
                if pl.size:
                    self.positions.setdefault(
                        (fld, term), {})[docid] = pl.tolist()
        for k, docid in enumerate(docids):
            self.doc_field_lengths.setdefault(fld, []).append(
                (docid, int(burst.lengths[k])))

    def _flat_csr_from_dicts(self):
        """Convert the dict-form postings/positions to the flat-CSR form the
        vectorized packer consumes (same layout the native accumulator
        emits)."""
        keys = sorted(self.postings.keys())
        T = len(keys)
        df = np.fromiter(
            (len(self.postings[k]) for k in keys), np.int64, count=T
        )
        post_offsets = np.zeros(T + 1, np.int64)
        np.cumsum(df, out=post_offsets[1:])
        total = int(post_offsets[-1])
        flat_docs = np.empty(total, np.int32)
        flat_tfs = np.empty(total, np.float32)
        for i, k in enumerate(keys):
            plist = self.postings[k]
            docs = np.fromiter(plist.keys(), np.int32, count=len(plist))
            tfs = np.fromiter(plist.values(), np.float32, count=len(plist))
            order = np.argsort(docs, kind="stable")
            s, e = post_offsets[i], post_offsets[i + 1]
            flat_docs[s:e] = docs[order]
            flat_tfs[s:e] = tfs[order]
        pos_counts = np.zeros(T, np.int64)
        for i, k in enumerate(keys):
            plists = self.positions.get(k)
            if plists:
                pos_counts[i] = sum(len(v) for v in plists.values())
        pos_offsets = np.zeros(T + 1, np.int64)
        np.cumsum(pos_counts, out=pos_offsets[1:])
        flat_pos = np.empty(int(pos_offsets[-1]), np.int64)
        for i, k in enumerate(keys):
            plists = self.positions.get(k)
            if not plists:
                continue
            s = pos_offsets[i]
            for d in sorted(plists):
                for p in plists[d]:
                    flat_pos[s] = d * POS_L + p
                    s += 1
        return keys, post_offsets, flat_docs, flat_tfs, pos_offsets, flat_pos

    def build(self, dense_min_df: int | None = None,
              device=None) -> ShardPack:
        """`device`: where the device build stages (the blocked-CSR scatter,
        the impact quantization) run: the device that will hold this shard
        (parallel/stacked.py); None is the default device."""
        from ..monitoring.refresh_profile import build_stage, refresh_stage

        N = self.num_docs
        mappings = self.mappings
        if dense_min_df is None:
            dense_min_df = default_dense_min_df(N)

        # ---- flat CSR (native accumulator or dict fallback) --------------
        with refresh_stage("flat_csr"):
            if self._native is not None:
                keys, post_offsets, flat_docs, flat_tfs, pos_offsets, \
                    flat_pos = self._native.pack()
                self._native.close()
                self._native = None
            else:
                keys, post_offsets, flat_docs, flat_tfs, pos_offsets, \
                    flat_pos = self._flat_csr_from_dicts()
        # term dictionary: stable order = sorted by (field, term)
        term_dict = {k: i for i, k in enumerate(keys)}
        T = len(keys)

        # ---- norms (quantized doc lengths) ------------------------------
        norms: dict[str, np.ndarray] = {}
        text_present: dict[str, np.ndarray] = {}
        field_stats: dict[str, dict] = {}
        with build_stage("build.norms", num_docs=N,
                         nfields=len(self.doc_field_lengths)):
            for fld, pairs in self.doc_field_lengths.items():
                lengths = np.zeros(N, dtype=np.int64)
                present = np.zeros(N, dtype=bool)
                for docid, ln in pairs:
                    lengths[docid] += ln
                    present[docid] = True
                norms[fld] = quantize_lengths(lengths)
                text_present[fld] = present
                # Lucene avgdl = sumTotalTermFreq / docCount where docCount
                # counts docs with at least one term for the field
                # (Terms.getDocCount)
                docs_with = len({docid for docid, ln in pairs if ln > 0})
                field_stats[fld] = {"sum_dl": float(lengths.sum()),
                                    "doc_count": docs_with}
        # norm-less indexed fields (keyword) still need per-field docCount
        # for idf (Lucene CollectionStatistics.docCount)
        for fld, (_, cnt) in self.field_doc_counts.items():
            if fld not in field_stats:
                field_stats[fld] = {"sum_dl": 0.0, "doc_count": cnt}
        # keyword fields used in scoring need norms too (constant length 1,
        # matching Lucene: keyword fields omit norms => norm = 1)
        # handled at query time by norm fallback.

        # ---- blocked postings (segment scatter from flat CSR) ------------
        # PR 15: the scatter + block-stat derivation also exists as one
        # jitted segment-scatter kernel
        # (index/device_build.csr_blocked_scatter_device) — byte parity
        # with the host path asserted by tests/test_device_build.py. Since
        # PR 29 the host's scatter is the default (use_device_csr_scatter
        # says why)
        from .device_build import (csr_blocked_scatter_device,
                                   use_device_build, use_device_csr_scatter)

        NP = len(flat_docs) if T else 0
        csr_dev = use_device_csr_scatter(NP)
        with build_stage("build.csr_assemble", postings=NP, num_docs=N,
                         terms=T, basis="device" if csr_dev else "host"):
            df = post_offsets[1:] - post_offsets[:-1]
            term_df = df.astype(np.int32)
            nblk = (df + BLOCK - 1) // BLOCK
            row_base = np.empty(T + 1, dtype=np.int64)
            row_base[0] = 1  # row 0 reserved all-padding
            row_base[1:] = 1 + np.cumsum(nblk)
            total_blocks = int(row_base[-1]) if T else 1
            term_block_start = row_base.astype(np.int32)

            field_names = sorted({k[0] for k in keys})
            fld_code = {f: i for i, f in enumerate(field_names)}
            field_of_term = np.fromiter(
                (fld_code[k[0]] for k in keys), np.int64, count=T
            )
            if NP:
                term_of_post = np.repeat(np.arange(T), df)
                local = np.arange(NP, dtype=np.int64) - np.repeat(
                    post_offsets[:-1], df
                )
                dest_row = row_base[:-1][term_of_post] + local // BLOCK
                dest_col = local % BLOCK
                # per-posting doc length (1.0 for norm-less fields)
                post_dl_flat = np.ones(NP, dtype=np.float32)
                fop = field_of_term[term_of_post]
                for f, nrm in norms.items():
                    code = fld_code.get(f)
                    if code is None:
                        continue
                    sel = fop == code
                    if sel.any():
                        post_dl_flat[sel] = nrm[flat_docs[sel]]
            if NP and csr_dev:
                (post_docids, post_tfs, post_dls, block_max_tf,
                 block_min_len) = csr_blocked_scatter_device(
                    flat_docs, flat_tfs, post_dl_flat, dest_row,
                    dest_col, total_blocks, BLOCK, N, device=device)
            else:
                post_docids = np.full((total_blocks, BLOCK), N,
                                      dtype=np.int32)
                post_tfs = np.zeros((total_blocks, BLOCK),
                                    dtype=np.float32)
                post_dls = np.ones((total_blocks, BLOCK),
                                   dtype=np.float32)
                block_max_tf = np.zeros(total_blocks, dtype=np.float32)
                block_min_len = np.full(total_blocks, np.inf,
                                        dtype=np.float32)
                if NP:
                    post_docids[dest_row, dest_col] = flat_docs
                    post_tfs[dest_row, dest_col] = flat_tfs
                    post_dls[dest_row, dest_col] = post_dl_flat
                    # per-block stats: flat order is block-contiguous, so
                    # reduceat over block starts gives segment max/min
                    starts = np.flatnonzero(
                        np.diff(dest_row, prepend=-1))
                    block_rows = dest_row[starts]
                    block_max_tf[block_rows] = np.maximum.reduceat(
                        flat_tfs, starts)
                    block_min_len[block_rows] = np.minimum.reduceat(
                        post_dl_flat, starts)
            block_min_len[~np.isfinite(block_min_len)] = 1.0

        # ---- docvalues ---------------------------------------------------
        docvalues: dict[str, DocValuesColumn] = {}
        for fld, pairs in self.docvalue_raw.items():
            if fld == "_id":
                ftype = "keyword"
            elif "#" in fld:
                ftype = "float"  # geo_point lat/lon sub-columns
            else:
                ftype = mappings.fields[fld].type
            has = np.zeros(N, dtype=bool)
            if ftype in KEYWORD_TYPES or ftype in IP_TYPES:
                extras = self.mv_extra_raw.get(fld, [])
                # ip ordinals sort by address value, not lexicographically,
                # so ord-range queries and sorts follow numeric ip order
                sort_key = ip_sort_key if ftype in IP_TYPES else None
                terms_sorted = sorted({v for _, v in pairs}
                                      | {v for _, v in extras}, key=sort_key)
                ord_of = {t: i for i, t in enumerate(terms_sorted)}
                vals = np.full(N, -1, dtype=np.int32)
                for docid, v in pairs:
                    if not has[docid]:
                        vals[docid] = ord_of[v]
                        has[docid] = True
                col = DocValuesColumn("ord", vals, has, terms_sorted)
                if extras:
                    all_pairs = sorted(
                        {(docid, ord_of[v]) for docid, v in pairs if v in ord_of}
                        | {(docid, ord_of[v]) for docid, v in extras}
                    )
                    col.mv_pair_docs = np.array([d for d, _ in all_pairs], np.int32)
                    col.mv_pair_ords = np.array([o for _, o in all_pairs], np.int32)
                docvalues[fld] = col
            elif ftype in FLOAT_TYPES:
                vals = np.zeros(N, dtype=np.float32)
                for docid, v in pairs:
                    if not has[docid]:
                        vals[docid] = v
                        has[docid] = True
                col = DocValuesColumn("float", vals, has)
                if has.any():
                    col.vmin = float(vals[has].min())
                    col.vmax = float(vals[has].max())
                docvalues[fld] = col
            else:  # int / date / boolean
                vals = np.zeros(N, dtype=np.int64)
                for docid, v in pairs:
                    if not has[docid]:
                        vals[docid] = v
                        has[docid] = True
                col = DocValuesColumn("int", vals, has)
                if has.any():
                    present = vals[has]
                    col.vmin = int(present.min())
                    col.vmax = int(present.max())
                    uniq, inv = np.unique(present, return_inverse=True)
                    ords = np.full(N, -1, dtype=np.int32)
                    ords[has] = inv.astype(np.int32)
                    col.uniq_values = uniq
                    col.uniq_ords = ords
                docvalues[fld] = col

        # ---- vectors -----------------------------------------------------
        vectors: dict[str, VectorColumn] = {}
        with refresh_stage("vectors"):
            for fld, pairs in self.vector_raw.items():
                ft = mappings.fields[fld]
                vals = np.zeros((N, ft.dims), dtype=np.float32)
                has = np.zeros(N, dtype=bool)
                for docid, vec in pairs:
                    vals[docid] = vec
                    has[docid] = True
                vc = VectorColumn(vals, has, ft.similarity, ft.dims,
                                  ann_quant=getattr(ft, "ann_quant", "int8"))
                if ft.ann_nlist is not None:
                    from ..ann import build_ann

                    nlist = ft.ann_nlist or max(1, int(has.sum() ** 0.5))
                    vc.ann = build_ann(vals, has, nlist)
                vectors[fld] = vc

        # ---- position blocks (vectorized scatter from flat CSR) ----------
        pos_keys = None
        term_pos_start = None
        term_pos_count = None
        n_positions = int(pos_offsets[-1]) if T else 0
        if n_positions:
            # position keys stay a host scatter for now: tiny next to the
            # postings volume, and phrase-heavy corpora are not the C7
            # write path (documented in BENCH_NOTES round 19)
            with build_stage("build.csr_assemble", postings=n_positions,
                             num_docs=N, terms=T, basis="host"):
                pos_df = pos_offsets[1:] - pos_offsets[:-1]
                pnblk = (pos_df + BLOCK - 1) // BLOCK
                prow_base = np.empty(T + 1, dtype=np.int64)
                prow_base[0] = 1
                prow_base[1:] = 1 + np.cumsum(pnblk)
                total_pos_blocks = int(prow_base[-1])
                pos_keys = np.full((total_pos_blocks, BLOCK), POS_INF,
                                   dtype=np.int64)
                term_pos_start = prow_base.astype(np.int32)
                term_pos_count = pos_df.astype(np.int32)
                pterm = np.repeat(np.arange(T), pos_df)
                plocal = np.arange(n_positions, dtype=np.int64) - np.repeat(
                    pos_offsets[:-1], pos_df
                )
                pos_keys[
                    prow_base[:-1][pterm] + plocal // BLOCK, plocal % BLOCK
                ] = flat_pos

        # per-field scoring constants, indexed by field code (dense tier +
        # impact tier share them)
        avgdl_of_field = np.ones(len(field_names), dtype=np.float64)
        has_norms_of_field = np.zeros(len(field_names), dtype=bool)
        for f, code in fld_code.items():
            st = field_stats.get(f, {"sum_dl": 0.0, "doc_count": 0})
            avgdl_of_field[code] = (
                st["sum_dl"] / max(st["doc_count"], 1)
            ) or 1.0
            has_norms_of_field[code] = f in norms

        # ---- impact tier (BM25S): quantized per-posting contributions ----
        impact_codes = impact_ubf = impact_meta = None
        if T:
            dtype = impact_dtype_default()
            qmax = IMPACT_QMAX[dtype]
            imp_dev = use_device_build(total_blocks * BLOCK)
            with build_stage("build.impact_quantize", rows=total_blocks,
                             code_bytes=2 if dtype == "uint16" else 1,
                             basis="device" if imp_dev else "host"):
                impact_ubf = impact_term_ubf(term_block_start, block_max_tf)
                row_terms = impact_row_terms(term_block_start, total_blocks)
                k_base, k_slope, scale_inv = impact_row_params(
                    row_terms, impact_ubf, field_of_term,
                    avgdl_of_field, has_norms_of_field, qmax)
                if imp_dev:
                    # PR 15: the quantization is a pure elementwise pass
                    # over the blocked CSR values — run it on device (the
                    # refresh_impacts shape, applied at build)
                    from .device_build import impact_codes_device

                    impact_codes = np.array(impact_codes_device(
                        post_tfs, post_dls, k_base, k_slope, scale_inv,
                        qmax=qmax, dtype=dtype, device=device))
                else:
                    impact_codes = impact_codes_host(
                        post_tfs, post_dls, k_base, k_slope, scale_inv,
                        qmax, dtype)
            impact_meta = {"dtype": dtype, "qmax": qmax,
                           "k1": BM25_K1, "b": BM25_B}

        # ---- dense tier (vectorized over all dense postings) -------------
        dense_ids = np.flatnonzero(df >= dense_min_df) if T else np.array([], np.int64)
        dense_keys = [keys[i] for i in dense_ids]
        dense_dict = {k: i for i, k in enumerate(dense_keys)}
        dense_tfn = None
        if dense_keys:
            # row count padded to a multiple of 128: per-shard vocabularies
            # differ slightly, and a lane-aligned row axis lets every shard
            # of an index share one compiled batched-query executable
            # (ops/batched.py W is [Q, V]); padding rows stay all-zero so
            # they never score or match
            with refresh_stage("dense_tier"):
                v_pad = -len(dense_keys) % 128
                dense_tfn = np.zeros((len(dense_keys) + v_pad, N),
                                     dtype=np.float32)
                dense_rank = np.full(T, -1, dtype=np.int64)
                dense_rank[dense_ids] = np.arange(len(dense_ids))
                dmask = dense_rank[term_of_post] >= 0
                rows = dense_rank[term_of_post[dmask]]
                cols = flat_docs[dmask]
                tfs_d = flat_tfs[dmask]
                dls_d = post_dl_flat[dmask]
                fcode = field_of_term[term_of_post[dmask]]
                K = np.where(
                    has_norms_of_field[fcode],
                    BM25_K1
                    * (1.0 - BM25_B + BM25_B * dls_d
                       / avgdl_of_field[fcode]),
                    BM25_K1,
                )
                dense_tfn[rows, cols] = (
                    tfs_d / (tfs_d + K)).astype(np.float32)

        completion = {
            fld: sorted(entries) for fld, entries in self.completion_raw.items()
        }
        percolator = dict(self.percolator_raw)
        return ShardPack(
            num_docs=N,
            post_docids=post_docids,
            post_tfs=post_tfs,
            post_dls=post_dls,
            term_block_start=term_block_start,
            term_df=term_df,
            block_max_tf=block_max_tf,
            block_min_len=block_min_len,
            term_dict=term_dict,
            norms=norms,
            text_present=text_present,
            field_stats=field_stats,
            docvalues=docvalues,
            vectors=vectors,
            live=np.ones(N, dtype=bool),
            dense_tfn=dense_tfn,
            dense_dict=dense_dict,
            pos_keys=pos_keys,
            term_pos_start=term_pos_start,
            term_pos_count=term_pos_count,
            completion=completion,
            percolator=percolator,
            impact_codes=impact_codes,
            impact_ubf=impact_ubf,
            impact_meta=impact_meta,
        )
