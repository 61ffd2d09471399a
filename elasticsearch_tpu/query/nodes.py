"""Query plan nodes: host-side prepare + device-side evaluation.

The reference compiles its JSON Query DSL through `QueryBuilder.toQuery()`
into Lucene `Query`/`Weight`/`Scorer` trees pulled doc-at-a-time (reference:
server/.../index/query/AbstractQueryBuilder.java, BoolQueryBuilder.java).
Here every node instead evaluates to a pair of dense device arrays

    (scores[N+1] float32, match[N+1] bool)

over the whole shard, and boolean composition is elementwise arithmetic —
the natural XLA shape: no iterators, no branches, fused by the compiler.

Protocol:
  prepare(pack)  -> (params pytree of numpy arrays, structural cache key)
     host work: term-dict lookups, idf, block-row padding to pow2 buckets.
     The cache key captures everything that changes the traced computation
     (node types, fields, bucket sizes) but NOT term values, so repeated
     queries with the same shape reuse the compiled executable.
  device_eval(dev, params, ctx) -> (scores, match)
     pure-jnp, called inside jit.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..index.pack import ShardPack
from ..ops.scoring import DEAD_SLOT_PAD, bm25_idf

MIN_BUCKET = 4


def _bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def _pad_rows(start: int, count: int) -> np.ndarray:
    """Block-row list padded to a pow2 bucket with the reserved padding row 0."""
    b = _bucket(count)
    rows = np.zeros(b, dtype=np.int32)
    rows[:count] = np.arange(start, start + count, dtype=np.int32)
    return rows


@dataclass
class ExecContext:
    """Static per-pack info available during tracing.

    k1/b apply to the sparse CSR scoring path only; dense-tier tfn rows bake
    the BM25 defaults at pack build (index/pack.py BM25_K1/BM25_B), so
    non-default values require building the pack with the dense tier
    disabled (dense_min_df large) — enforced by the searchers."""

    num_docs: int
    avgdl: dict[str, float]
    has_norms: frozenset[str]
    k1: float = 1.2
    b: float = 0.75
    # True when per-shard partials will be merged host-side: agg nodes then
    # emit mergeable forms (bitmaps, sorted arrays) instead of final values
    sharded: bool = False


class QueryNode:
    boost: float = 1.0

    def prepare(self, pack: ShardPack) -> tuple[Any, tuple]:
        raise NotImplementedError

    def device_eval(self, dev: dict, params: Any, ctx: ExecContext):
        raise NotImplementedError


# The solo path's family of `match` programs (PR 38). A match (one term, or a
# bool of terms on one field) plans as two padded lists, its dense terms and
# its sparse terms' posting-block rows, and its program is keyed by the two
# lists' tiers alone: (MATCH, field, scoring mode, dense tier, rows tier). A
# query of unseen words then compiles nothing new.
MATCH = "match"
# the smallest rows tier (`search.solo.min_rows_tier`, powers of two from
# it). Process-wide like the program caches it bounds; the engine's settings
# consumer sets it.
MATCH_MIN_ROWS = 8
# the core of the ladders: dense tiers 0, 1, 2, 4, 8 by rows tiers m, 2m, 4m
# (m = MATCH_MIN_ROWS): where a query of up to a dozen words lands. A query
# outside it rides one program a level further out, both tiers at once:
# (16, 16m), (32, 64m), ... so that the few longest queries of a stream share
# one program instead of a rare pair each.
MATCH_CORE_DENSE = 8


def match_tiers(n_dense: int, n_rows: int) -> tuple[int, int]:
    """-> (dense tier, rows tier) of a match with `n_dense` dense terms and
    `n_rows` sparse posting-block rows: the ladders of `ops/batched` (powers
    of two), the rows' at least MATCH_MIN_ROWS, inside the core box or on
    its diagonal beyond (scripts/solo_family.py counts the family)."""
    from ..ops.batched import BatchTermSearcher

    pow2 = BatchTermSearcher.pow2_tier
    td = pow2(n_dense) if n_dense else 0
    tr = pow2(n_rows, MATCH_MIN_ROWS)
    d_edge, r_edge = MATCH_CORE_DENSE, 4 * MATCH_MIN_ROWS
    if td <= d_edge and tr <= r_edge:
        return td, tr
    while td > d_edge or tr > r_edge:
        d_edge, r_edge = 2 * d_edge, 4 * r_edge
    return d_edge, r_edge


def _impact_mode(pack, terms) -> bool:
    """Whether a match's sparse terms score from the impact tier's codes:
    the pack serves them (the same answer on every shard of a stacked pack)
    and no term asks for exact scores (mark_exact)."""
    from ..ops.scoring import impact_enabled

    served = getattr(pack, "impact_served", None)
    return (impact_enabled() and served is not None and served()
            and not any(t.exact_scores for t in terms))


def match_params(pack, terms: list, threshold: int, boost: float):
    """-> (params, key) of a match over `terms` (TermNodes of one field):
    a document matches where at least `threshold` of them hold it, and its
    score is `boost` x the sum of theirs. Canonical order: dense terms by
    dense row, sparse terms by first block row; a term the shard lacks adds
    nothing. Each term's weight is computed as the per-term plan did, so a
    posting's contribution is the same f32 as before, summed in another
    order (ulps)."""
    fld = terms[0].fld
    impact = _impact_mode(pack, terms)
    doc_count = (pack.field_stats.get(fld, {}).get("doc_count")
                 or pack.num_docs)
    dense, sparse = [], []
    for t in terms:
        start, count, df = pack.term_blocks(fld, t.term)
        weight = (np.float32(t.boost * bm25_idf(doc_count, df)) if df > 0
                  else np.float32(0.0))
        dr = pack.dense_row_of(fld, t.term)
        if dr is not None:
            dense.append((int(dr), weight))
        elif count:
            # wscale = boost·idf·ubf/qmax — score = wscale · code
            wscale = (np.float32(weight * pack.impact_wscale(fld, t.term))
                      if impact else np.float32(0.0))
            sparse.append((start, count, weight, wscale))
    dense.sort(key=lambda d: d[0])
    sparse.sort(key=lambda s: s[0])
    n_rows = sum(c for _s, c, _w, _ws in sparse)
    td, tr = match_tiers(len(dense), n_rows)
    rows = np.zeros(tr, np.int32)           # padding: the reserved row 0
    rw = np.zeros(tr, np.float32)
    rs = np.zeros(tr, np.float32)
    at = 0
    for start, count, weight, wscale in sparse:
        rows[at:at + count] = np.arange(start, start + count, dtype=np.int32)
        rw[at:at + count] = weight
        rs[at:at + count] = wscale
        at += count
    dr = np.zeros(td, np.int32)             # padding: weight 0, no match
    dw = np.zeros(td, np.float32)
    dok = np.zeros(td, np.int32)
    for i, (row, weight) in enumerate(dense):
        dr[i], dw[i], dok[i] = row, weight, 1
    # avgdl rides as a runtime param (not a trace constant) so compiled
    # plans survive stat drift as tiered refreshes add documents
    params = (rows, rw, rs, dr, dw, dok, np.int32(threshold),
              np.float32(boost), np.float32(pack.avgdl(fld)))
    return params, (MATCH, fld, "impact" if impact else "exact", td, tr)


def match_eval(dev, params, ctx, fld: str, mode: str):
    """The device side of `match_params`' plan (ops/scoring.match_scores).
    `mode` is the key's: "impact" escalates to raw-postings BM25 where the
    searcher holds no codes or the context's k1/b are not the pack's."""
    from ..index.pack import BM25_B, BM25_K1
    from ..ops.scoring import match_scores

    impact = (mode == "impact" and "impact_codes" in dev
              and (ctx.k1, ctx.b) == (BM25_K1, BM25_B))
    return match_scores(dev, params, ctx.num_docs, ctx.k1, ctx.b,
                        fld in ctx.has_norms, impact)


def match_rows(key, params) -> tuple[int, int, tuple[int, int]] | None:
    """-> (rows gathered for real, rows with padding, (dense tier, rows
    tier)) of a plan whose key is a match family member (params stacked
    [S, ...]: every shard's rows), else None. A real sparse row is never the
    padding row 0; a real dense entry carries its flag."""
    if not (isinstance(key, tuple) and key and key[0] == MATCH):
        return None
    rows, dok = np.asarray(params[0]), np.asarray(params[5])
    real = int(np.count_nonzero(rows)) + int(np.count_nonzero(dok))
    return real, rows.size + dok.size, (key[3], key[4])


class _Ragged(Exception):
    pass


def plan_key(keys: list) -> tuple:
    """The key of a plan over its shards' keys: where they differ only in
    a match family member's tiers, each shard takes the largest shard's
    (`_stack_shard_params` pads the lists to the widest with zeros, i.e.
    padding), so the plan is one program of the family; else the shards'
    keys as they are."""

    def merge(ks):
        k0 = ks[0]
        if all(k == k0 for k in ks):
            return k0
        if not all(isinstance(k, tuple) and len(k) == len(k0) for k in ks):
            raise _Ragged
        if k0 and k0[0] == MATCH and all(k[:3] == k0[:3] for k in ks):
            return (*k0[:3], max(k[3] for k in ks), max(k[4] for k in ks))
        return tuple(merge(list(p)) for p in zip(*ks))

    try:
        return (merge(list(keys)),) * len(keys)
    except _Ragged:
        return tuple(keys)


@dataclass
class TermNode(QueryNode):
    """Exact term match with BM25 scoring (reference behavior:
    index/query/TermQueryBuilder.java -> Lucene TermQuery): a match of one
    term (`match_params`). Where the pack carries the impact-scored sparse
    tier (BM25S, index/pack.py) and nothing demands exact scores, a sparse
    term is a pure gather+sum over quantized impact codes: idf (from the ONE
    bm25_idf implementation, effective dfs stats included) folds into a
    host-side scalar and no tf/dl/avgdl math is traced. `exact_scores` (set
    by mark_exact for explain / scripted similarity) and non-default
    ctx.k1/b fall back to the raw-postings path."""

    fld: str
    term: str
    boost: float = 1.0
    exact_scores: bool = False

    def prepare(self, pack):
        params, key = match_params(pack, [self], 1, 1.0)
        self._mode = key[2]
        return params, key

    def device_eval(self, dev, params, ctx):
        return match_eval(dev, params, ctx, self.fld, self._mode)


@dataclass
class MatchAllNode(QueryNode):
    boost: float = 1.0

    def prepare(self, pack):
        return (np.float32(self.boost),), ("match_all",)

    def device_eval(self, dev, params, ctx):
        (boost,) = params
        n1 = ctx.num_docs + 1
        return jnp.full(n1, boost, jnp.float32), jnp.ones(n1, bool)


@dataclass
class MatchNoneNode(QueryNode):
    boost: float = 1.0

    def prepare(self, pack):
        return (), ("match_none",)

    def device_eval(self, dev, params, ctx):
        n1 = ctx.num_docs + 1
        return jnp.zeros(n1, jnp.float32), jnp.zeros(n1, bool)


@dataclass
class RangeNode(QueryNode):
    """Range over numeric/date/keyword docvalues; constant score = boost
    (reference behavior: index/query/RangeQueryBuilder.java — point/DV range
    queries score constantly)."""

    fld: str
    lo: float | int | None
    hi: float | int | None
    include_lo: bool = True
    include_hi: bool = True
    boost: float = 1.0
    kind: str = "int"  # int | float | ord

    def prepare(self, pack):
        col = pack.docvalues.get(self.fld)
        dtype = np.int64 if self.kind in ("int", "ord") else np.float32
        info_min = np.iinfo(np.int64).min if dtype == np.int64 else -np.inf
        info_max = np.iinfo(np.int64).max if dtype == np.int64 else np.inf
        lo = info_min if self.lo is None else self.lo
        hi = info_max if self.hi is None else self.hi
        params = (
            np.asarray(lo, dtype),
            np.asarray(hi, dtype),
            np.asarray(self.include_lo),
            np.asarray(self.include_hi),
            np.float32(self.boost),
        )
        return params, ("range", self.fld, self.kind, col is None)

    def device_eval(self, dev, params, ctx):
        lo, hi, inc_lo, inc_hi, boost = params
        n1 = ctx.num_docs + 1
        kinds = {"int": "dv_int", "float": "dv_float", "ord": "dv_ord"}
        store = dev[kinds[self.kind]]
        if self.fld not in store:
            return jnp.zeros(n1, jnp.float32), jnp.zeros(n1, bool)
        vals, has = store[self.fld]
        above = jnp.where(inc_lo, vals >= lo, vals > lo)
        below = jnp.where(inc_hi, vals <= hi, vals < hi)
        m = has & above & below
        match = jnp.zeros(n1, bool).at[: ctx.num_docs].set(m)
        return boost * match.astype(jnp.float32), match


@dataclass
class TermsNode(QueryNode):
    """`terms` query: doc matches any of the values; constant score = boost
    (reference behavior: index/query/TermsQueryBuilder.java -> Lucene
    TermInSetQuery under ConstantScore)."""

    fld: str
    values: list
    boost: float = 1.0
    kind: str = "ord"  # ord | int | float

    def prepare(self, pack):
        col = pack.docvalues.get(self.fld)
        if self.kind == "ord":
            terms = col.ord_terms if col is not None else []
            ord_of = {t: i for i, t in enumerate(terms)}
            ids = [ord_of[v] for v in map(str, self.values) if v in ord_of]
            arr = np.full(_bucket(max(len(ids), 1)), -2, dtype=np.int64)
            arr[: len(ids)] = ids
        else:
            dtype = np.int64 if self.kind == "int" else np.float32
            arr = np.full(_bucket(max(len(self.values), 1)), np.iinfo(np.int64).min + 1 if dtype == np.int64 else np.nan, dtype=dtype)
            arr[: len(self.values)] = [v for v in self.values]
        return (arr, np.float32(self.boost)), ("terms", self.fld, self.kind, len(arr), col is None)

    def device_eval(self, dev, params, ctx):
        arr, boost = params
        n1 = ctx.num_docs + 1
        kinds = {"int": "dv_int", "float": "dv_float", "ord": "dv_ord"}
        store = dev[kinds[self.kind]]
        if self.fld not in store:
            return jnp.zeros(n1, jnp.float32), jnp.zeros(n1, bool)
        vals, has = store[self.fld]
        if self.kind == "ord":
            vals = vals.astype(jnp.int64)
        m = has & (vals[:, None] == arr[None, :]).any(axis=1)
        match = jnp.zeros(n1, bool).at[: ctx.num_docs].set(m)
        return boost * match.astype(jnp.float32), match


@dataclass
class ExistsNode(QueryNode):
    fld: str
    boost: float = 1.0

    def prepare(self, pack):
        has_dv = (
            self.fld in pack.docvalues
            or self.fld in pack.vectors
            or self.fld in pack.text_present
        )
        return (np.float32(self.boost),), ("exists", self.fld, has_dv)

    def device_eval(self, dev, params, ctx):
        (boost,) = params
        n1 = ctx.num_docs + 1
        m = None
        for store_key in ("dv_int", "dv_float", "dv_ord"):
            if self.fld in dev[store_key]:
                m = dev[store_key][self.fld][1]
                break
        if m is None and self.fld in dev.get("vec_has", {}):
            m = dev["vec_has"][self.fld]
        if m is None and self.fld in dev["text_has"]:
            m = dev["text_has"][self.fld]
        if m is None:
            return jnp.zeros(n1, jnp.float32), jnp.zeros(n1, bool)
        match = jnp.zeros(n1, bool).at[: ctx.num_docs].set(m)
        return boost * match.astype(jnp.float32), match


@dataclass
class ConstantScoreNode(QueryNode):
    child: QueryNode = None
    boost: float = 1.0

    def prepare(self, pack):
        cp, ck = self.child.prepare(pack)
        return (cp, np.float32(self.boost)), ("const", ck)

    def device_eval(self, dev, params, ctx):
        cp, boost = params
        _, m = self.child.device_eval(dev, cp, ctx)
        return boost * m.astype(jnp.float32), m


@dataclass
class DisMaxNode(QueryNode):
    """Max over children + tie_breaker * sum(rest) (reference behavior:
    index/query/DisMaxQueryBuilder.java)."""

    children: list = dc_field(default_factory=list)
    tie_breaker: float = 0.0
    boost: float = 1.0

    def prepare(self, pack):
        parts = [c.prepare(pack) for c in self.children]
        return (
            tuple(p for p, _ in parts),
            np.float32(self.tie_breaker),
            np.float32(self.boost),
        ), ("dismax", tuple(k for _, k in parts))

    def device_eval(self, dev, params, ctx):
        child_params, tie, boost = params
        n1 = ctx.num_docs + 1
        best = jnp.zeros(n1, jnp.float32)
        total = jnp.zeros(n1, jnp.float32)
        match = jnp.zeros(n1, bool)
        for c, p in zip(self.children, child_params):
            s, m = c.device_eval(dev, p, ctx)
            best = jnp.maximum(best, s)
            total = total + s
            match = match | m
        score = boost * (best + tie * (total - best))
        return jnp.where(match, score, 0.0), match


@dataclass
class KnnNode(QueryNode):
    """Exact k-nearest-neighbor retrieval (reference behavior:
    search/vectors/KnnVectorQueryBuilder.java:54 + KnnSearchBuilder.java:44 —
    per-shard top num_candidates then global k). Here the scan is exact, so
    num_candidates only caps the per-shard match set; an optional filter is
    applied BEFORE neighbor selection (ES pre-filtering semantics)."""

    fld: str = ""
    qvec: list | None = None
    k: int = 10
    num_candidates: int | None = None
    filter_node: QueryNode | None = None
    boost: float = 1.0
    similarity_threshold: float | None = None
    # ANN controls: explicit probe count (None -> the dynamic index
    # setting / coverage heuristic); force_exact is the engine's
    # too-selective-filter escalation switch (recompiles to the scan)
    nprobe: int | None = None
    force_exact: bool = False
    _sim: str = "cosine"

    # filtered/thresholded ANN: retrieve this many times num_candidates
    # before post-filtering, so a moderately selective filter still
    # reaches k (the reference's filtered-HNSW over-probing analog)
    FILTER_OVERSAMPLE = 4

    def prepare(self, pack):
        vc = pack.vectors.get(self.fld)
        fp, fk = (None, None)
        if self.filter_node is not None:
            fp, fk = self.filter_node.prepare(pack)
        qv = np.zeros(vc.dims if vc else 1, np.float32)
        if vc is not None:
            if len(self.qvec) != vc.dims:
                from ..utils.errors import IllegalArgumentError

                raise IllegalArgumentError(
                    f"knn query vector has {len(self.qvec)} dims, field [{self.fld}] has {vc.dims}"
                )
            qv = np.asarray(self.qvec, np.float32)
        # trace-time constants consumed by device_eval; set ONLY here so the
        # struct key below always describes the plan that gets traced
        self._kk = min(self.num_candidates or self.k, max(pack.num_docs, 1))
        if vc is not None:
            self._sim = vc.similarity
        # device-resident ANN path (ann/): centroid probe + quantized
        # gather-scan + f32 rescore of survivors, all inside the compiled
        # plan. Filters/thresholds ride it with oversampled candidate
        # retrieval + post-filter; the engine re-prepares with
        # force_exact when the filtered result can't reach k.
        self._ann = None
        ann = getattr(vc, "ann", None) if vc is not None else None
        if ann is not None and not self.force_exact:
            from ..ann.search import default_nprobe

            C = int(ann["nlist"])
            L = int(ann["tile"])
            oversample = (self.FILTER_OVERSAMPLE
                          if (self.filter_node is not None
                              or self.similarity_threshold is not None)
                          else 1)
            nprobe = self.nprobe or default_nprobe(
                C, L, self._kk * oversample)
            nprobe = max(1, min(int(nprobe), C))
            if not self.nprobe:
                # PR 18: with planner.knn.target_ms set (and the scan
                # kernel's efficiency EMA warm), trade the coverage
                # heuristic for the LARGEST probe count whose predicted
                # gather-scan wall meets the latency target — recall
                # buys latency headroom instead of leaving it idle. An
                # explicit per-request nprobe is always respected.
                from ..planner import execution_planner

                nprobe = execution_planner().advise_nprobe(
                    nprobe, C, {"queries": 1, "dims": int(vc.dims),
                                "tile": L, "scan_tier": vc.ann_quant})
            kcand = min(nprobe * L, max(self._kk * oversample, self._kk))
            self._ann = (nprobe, kcand, vc.ann_quant)
            from ..telemetry import profile_event

            profile_event("tier", tier=f"ann_{vc.ann_quant}", queries=1,
                          nprobe=nprobe, kcand=kcand)
        return (qv, np.float32(self.boost), fp), (
            "knn", self.fld, vc is None, self._kk, self._sim,
            self.similarity_threshold, fk, self._ann,
        )

    def _score_threshold(self) -> float:
        """ES expresses `similarity` in the raw metric space; convert to the
        _score space the kernel compares against (reference behavior:
        VectorSimilarityQuery score translation)."""
        t = self.similarity_threshold
        if self._sim in ("cosine", "dot_product"):
            return (1.0 + t) / 2.0
        if self._sim == "l2_norm":
            return 1.0 / (1.0 + t * t)
        if self._sim == "max_inner_product":
            return 1.0 / (1.0 - t) if t < 0 else t + 1.0
        return t

    def device_eval(self, dev, params, ctx):
        from ..ops.vector import knn_scores

        qv, boost, fp = params
        n1 = ctx.num_docs + 1
        if self.fld not in dev["vec"]:
            return jnp.zeros(n1, jnp.float32), jnp.zeros(n1, bool)
        vecs = dev["vec"][self.fld]
        has = dev["vec_has"][self.fld]
        if self._ann is not None and self.fld in dev.get("vec_ann", {}):
            # ANN: quantized gather-scan of the probed cluster tiles
            # selects candidates; only they are rescored in f32 and
            # scattered into the dense accumulator
            from ..ann.kernels import ann_candidates_traced

            nprobe, kcand, tier = self._ann
            cand, sel_v, _tot = ann_candidates_traced(
                dev["vec_ann"][self.fld], qv, dev["live"], kcand,
                nprobe=nprobe, tier=tier, similarity=self._sim,
            )
            ok_cand = jnp.isfinite(sel_v)
            safe = jnp.where(cand >= 0, cand, 0)
            sub_scores = knn_scores(
                vecs[safe], dev["vec_sq"][self.fld][safe], qv, self._sim
            )
            tgt = jnp.where(ok_cand, cand, ctx.num_docs)
            scores_n1 = jnp.zeros(n1, jnp.float32).at[tgt].set(
                jnp.where(ok_cand, sub_scores, 0.0))
            in_cand = jnp.zeros(n1, bool).at[tgt].set(ok_cand)
            scores = scores_n1[: ctx.num_docs]
            ok = in_cand[: ctx.num_docs] & has & dev["live"]
        else:
            scores = knn_scores(vecs, dev["vec_sq"][self.fld], qv, self._sim)
            ok = has & dev["live"]
        if self.filter_node is not None:
            _, fm = self.filter_node.device_eval(dev, fp, ctx)
            ok = ok & fm[: ctx.num_docs]
        if self.similarity_threshold is not None:
            ok = ok & (scores >= self._score_threshold())
        masked = jnp.where(ok, scores, -jnp.inf)
        kth = jax.lax.top_k(masked, self._kk)[0][-1]
        match_n = ok & (masked >= kth) & jnp.isfinite(masked)
        match = jnp.zeros(n1, bool).at[: ctx.num_docs].set(match_n)
        score = jnp.zeros(n1, jnp.float32).at[: ctx.num_docs].set(
            jnp.where(match_n, boost * scores, 0.0)
        )
        return score, match


def mark_exact(node) -> "QueryNode":
    """Force exact BM25 scoring on every term in a plan tree — the
    impact-tier escalation switch for features a quantized score cannot
    serve: explain's per-clause breakdown, scripted similarity
    (script_score/function_score read the child's _score), rescore
    windows. Returns the node for chaining."""
    if isinstance(node, TermNode):
        node.exact_scores = True
    elif isinstance(node, BoolNode):
        for grp in (node.must, node.filter, node.should, node.must_not):
            for c in grp:
                mark_exact(c)
    elif isinstance(node, DisMaxNode):
        for c in node.children:
            mark_exact(c)
    elif isinstance(node, ConstantScoreNode):
        if node.child is not None:
            mark_exact(node.child)
    else:
        for attr in ("inner", "child", "filter_node"):
            c = getattr(node, attr, None)
            if isinstance(c, QueryNode):
                mark_exact(c)
    return node


MAX_CLAUSE_COUNT = 4096  # reference behavior: indices.query.bool.max_clause_count


@dataclass
class PhraseNode(QueryNode):
    """Exact phrase match (reference behavior: index/query/MatchPhraseQueryBuilder
    -> Lucene PhraseQuery, slop=0). TPU shape: positions are blocked sorted
    int64 keys (docid*POS_L + position); phrase matching is an m-way sorted-set
    intersection — the rarest term's keys probe each other term's key set via
    vectorized binary search (searchsorted), offset by the phrase positions.
    Phrase frequency (occurrence count per doc) feeds BM25 with the summed
    per-term idf, matching Lucene's PhraseQuery/BM25 scoring."""

    fld: str = ""
    terms: list = dc_field(default_factory=list)  # [(term, rel_position)]
    boost: float = 1.0
    slop: int = 0
    _no_pos: bool = False

    def prepare(self, pack):
        from ..utils.errors import IllegalArgumentError

        if self.slop != 0:
            raise IllegalArgumentError("[match_phrase] slop > 0 is not supported yet")
        stacked = getattr(pack, "stacked", None)
        pos = stacked.pos_keys if stacked is not None else getattr(pack, "pos_keys", None)
        self._no_pos = pos is None
        if self._no_pos:
            # no text tokens indexed anywhere -> nothing can match
            return (), ("phrase_empty", self.fld)
        doc_count = pack.field_stats.get(self.fld, {}).get("doc_count") or pack.num_docs
        idf_sum = 0.0
        infos = []
        for term, off in self.terms:
            ps, nb, cnt = pack.term_pos_blocks(self.fld, term)
            _s, _n, df = pack.term_blocks(self.fld, term)
            if df > 0:
                idf_sum += bm25_idf(doc_count, df)
            infos.append((ps, nb, cnt, off))
        # rarest term first: its positions become the probe set
        infos.sort(key=lambda x: x[2])
        rows = tuple(_pad_rows(ps, nb) for ps, nb, _c, _o in infos)
        offsets = np.array([o for _s, _n, _c, o in infos], np.int64)
        weight = np.float32(self.boost * idf_sum)
        return (rows, offsets, weight, np.float32(pack.avgdl(self.fld))), (
            "phrase", self.fld, tuple(len(r) for r in rows),
        )

    def device_eval(self, dev, params, ctx):
        from ..index.pack import POS_INF, POS_L

        if self._no_pos:
            n1 = ctx.num_docs + DEAD_SLOT_PAD
            return jnp.zeros(n1, jnp.float32), jnp.zeros(n1, bool)
        rows, offsets, weight, avgdl = params
        n = ctx.num_docs
        n1 = n + DEAD_SLOT_PAD
        pos_keys = dev["pos_keys"]
        probe = pos_keys[rows[0]].reshape(-1)  # sorted; POS_INF padding
        base = probe - offsets[0]
        alive = probe < POS_INF
        for i in range(1, len(rows)):
            table = pos_keys[rows[i]].reshape(-1)
            want = base + offsets[i]
            idx = jnp.searchsorted(table, want)
            hit = table[jnp.minimum(idx, table.shape[0] - 1)] == want
            alive = alive & hit
        ids = jnp.where(alive, (base // POS_L).astype(jnp.int32), n)
        phrase_tf = jnp.zeros(n1, jnp.float32).at[ids].add(
            jnp.where(alive, 1.0, 0.0), mode="drop"
        )
        tf = phrase_tf[:n]
        if self.fld in ctx.has_norms:
            dl = dev["norms"][self.fld]
            denom = tf + ctx.k1 * (1.0 - ctx.b + ctx.b * dl / avgdl)
        else:
            denom = tf + ctx.k1
        scores_n = jnp.where(tf > 0, weight * tf / denom, 0.0)
        scores = jnp.zeros(n1, jnp.float32).at[:n].set(scores_n)
        match = jnp.zeros(n1, bool).at[:n].set(tf > 0)
        return scores, match


@dataclass
class ExpandedTermsNode(QueryNode):
    """Multi-term query rewritten by host-side term-dictionary expansion
    (reference behavior: index/query/{Prefix,Wildcard,Regexp,Fuzzy}QueryBuilder
    -> Lucene MultiTermQuery; the dictionary enum runs host-side like Lucene's
    FST walk, the doc-set union runs on device).

    scored=False (prefix/wildcard/regexp): constant_score rewrite — every
    matching doc scores `boost`, like ES's default CONSTANT_SCORE rewrite.
    scored=True (fuzzy): each expanded term scores BM25 with its own idf and
    a per-term multiplier from `term_boost` (e.g. edit-distance decay).
    Divergence from Lucene's TopTermsBlendedFreq rewrite: per-term scores sum
    (bool-should semantics) instead of blending df across expanded terms.
    """

    kind: str = ""  # "prefix" | "wildcard" | "regexp" | "fuzzy" (cache tag)
    fld: str = ""
    matcher: Any = None  # host predicate: term -> False | True | weight-mult
    boost: float = 1.0
    scored: bool = False
    max_expansions: int | None = None  # cap on expanded terms (fuzzy: 50)

    def prepare(self, pack):
        from ..utils.errors import IllegalArgumentError

        expanded = []  # (term, multiplier)
        for t in pack.terms_for_field(self.fld):
            m = self.matcher(t)
            if m:
                expanded.append((t, 1.0 if m is True else float(m)))
        if self.max_expansions is not None and len(expanded) > self.max_expansions:
            # keep highest-df terms, like Lucene's top-terms rewrites
            expanded.sort(key=lambda tm: -pack.term_blocks(self.fld, tm[0])[2])
            expanded = expanded[: self.max_expansions]
        if len(expanded) > MAX_CLAUSE_COUNT:
            raise IllegalArgumentError(
                f"[{self.kind}] on [{self.fld}] expands to {len(expanded)} terms, "
                f"more than max_clause_count [{MAX_CLAUSE_COUNT}]"
            )
        doc_count = pack.field_stats.get(self.fld, {}).get("doc_count") or pack.num_docs
        rows_list, w_list = [], []
        for t, mult in expanded:
            s0, nb, df = pack.term_blocks(self.fld, t)
            if nb == 0:
                continue
            w = self.boost * mult * bm25_idf(doc_count, df) if self.scored else 1.0
            rows_list.extend(range(s0, s0 + nb))
            w_list.extend([w] * nb)
        r = max(len(rows_list), 1)
        width = 1 << (r - 1).bit_length()
        rows = np.zeros(width, np.int32)
        ws = np.zeros(width, np.float32)
        rows[: len(rows_list)] = rows_list
        ws[: len(w_list)] = w_list
        return (rows, ws, np.float32(self.boost), np.float32(pack.avgdl(self.fld))), (
            self.kind, self.fld, self.scored, width,
        )

    def device_eval(self, dev, params, ctx):
        rows, ws, boost, avgdl = params
        n1 = ctx.num_docs + DEAD_SLOT_PAD
        docids = dev["post_docids"][rows]  # [R, 128]
        tfs = dev["post_tfs"][rows]
        flat_ids = docids.reshape(-1)
        if not self.scored:
            match = jnp.zeros(n1, bool).at[flat_ids].set((tfs > 0).reshape(-1), mode="drop")
            match = match.at[ctx.num_docs].set(False)
            return jnp.where(match, boost, 0.0), match
        has_norms = self.fld in ctx.has_norms
        if has_norms:
            dls = dev["post_dls"][rows]
            denom = tfs + ctx.k1 * (1.0 - ctx.b + ctx.b * dls / avgdl)
        else:
            denom = tfs + ctx.k1
        lane_scores = ws[:, None] * tfs / denom
        scores = jnp.zeros(n1, jnp.float32).at[flat_ids].add(
            lane_scores.reshape(-1), mode="drop"
        )
        match = jnp.zeros(n1, bool).at[flat_ids].set((tfs > 0).reshape(-1), mode="drop")
        return scores, match


@dataclass
class PinnedScoresNode(QueryNode):
    """Matches a fixed (shard, docid) -> score set — the engine rewrites the
    knn section of a hybrid search to one of these holding the GLOBAL top-k
    knn hits (reference behavior: KnnSearchBuilder/KnnScoreDocQueryBuilder —
    per-shard num_candidates retrieval, then the global-k ScoreDocs become a
    query clause combined with the user query)."""

    per_shard: list = dc_field(default_factory=list)  # [(ids i32[m], scores f32[m])]

    def prepare(self, pack):
        s = getattr(pack, "shard_index", 0)
        n = pack.num_docs
        width = max((len(ids) for ids, _ in self.per_shard), default=0)
        width = max(width, 1)
        ids = np.full(width, n, np.int32)  # pad -> dead slot
        scs = np.zeros(width, np.float32)
        if self.per_shard:
            sids, sscs = self.per_shard[s]
            ids[: len(sids)] = sids
            scs[: len(sscs)] = sscs
        return (ids, scs), ("pinned", width)

    def device_eval(self, dev, params, ctx):
        ids, scs = params
        n1 = ctx.num_docs + 1
        scores = jnp.zeros(n1, jnp.float32).at[ids].set(scs, mode="drop")
        match = jnp.zeros(n1, bool).at[ids].set(True, mode="drop")
        return scores, match.at[ctx.num_docs].set(False)


@dataclass
class BoolNode(QueryNode):
    """Boolean composition (reference behavior:
    index/query/BoolQueryBuilder.java — must/filter/should/must_not with
    minimum_should_match; should is optional when must/filter present)."""

    must: list = dc_field(default_factory=list)
    filter: list = dc_field(default_factory=list)
    should: list = dc_field(default_factory=list)
    must_not: list = dc_field(default_factory=list)
    minimum_should_match: int | None = None
    boost: float = 1.0

    def _msm(self) -> int:
        if self.minimum_should_match is not None:
            return self.minimum_should_match
        if self.should and not (self.must or self.filter):
            return 1
        return 0

    def _match_terms(self):
        """-> (terms, threshold) where this bool is a `match`: TermNodes of
        one field, all `must` (threshold: all of them) or all `should`
        (threshold: minimum_should_match), nothing else; else None."""
        if self.filter or self.must_not or bool(self.must) == bool(self.should):
            return None
        terms = self.must or self.should
        if (any(type(c) is not TermNode for c in terms)
                or len({c.fld for c in terms}) != 1):
            return None
        threshold = len(terms) if self.must else self._msm()
        return (terms, threshold) if threshold >= 1 else None

    def prepare(self, pack):
        mt = self._match_terms()
        if mt is not None:
            params, key = match_params(pack, *mt, self.boost)
            self._mode = key[2]
            return params, key
        groups = []
        keys = []
        for grp in (self.must, self.filter, self.should, self.must_not):
            parts = [c.prepare(pack) for c in grp]
            groups.append(tuple(p for p, _ in parts))
            keys.append(tuple(k for _, k in parts))
        return (tuple(groups), np.float32(self.boost)), (
            "bool",
            tuple(keys),
            self._msm(),
        )

    def device_eval(self, dev, params, ctx):
        mt = self._match_terms()
        if mt is not None:
            return match_eval(dev, params, ctx, mt[0][0].fld, self._mode)
        groups, boost = params
        must_p, filter_p, should_p, not_p = groups
        n1 = ctx.num_docs + 1
        score = jnp.zeros(n1, jnp.float32)
        ok = jnp.ones(n1, bool)
        any_clause = bool(self.must or self.filter or self.should)
        for c, p in zip(self.must, must_p):
            s, m = c.device_eval(dev, p, ctx)
            score = score + s
            ok = ok & m
        for c, p in zip(self.filter, filter_p):
            _, m = c.device_eval(dev, p, ctx)
            ok = ok & m
        msm = self._msm()
        if self.should:
            cnt = jnp.zeros(n1, jnp.int32)
            for c, p in zip(self.should, should_p):
                s, m = c.device_eval(dev, p, ctx)
                score = score + s
                cnt = cnt + m.astype(jnp.int32)
            if msm > 0:
                ok = ok & (cnt >= msm)
        for c, p in zip(self.must_not, not_p):
            _, m = c.device_eval(dev, p, ctx)
            ok = ok & ~m
        if not any_clause and not self.must_not:
            pass  # empty bool matches everything (ok already all-true)
        score = jnp.where(ok, boost * score, 0.0)
        return score, ok
