"""StackedPack: S shard packs fused into [S, ...] arrays for a device mesh.

This is where the framework diverges hardest from the reference. The
reference's shards are independent Lucene indexes on separate nodes with
shard-local term dictionaries and ordinals, merged by string key at the
coordinator (reference behavior: SearchPhaseController.java:232 top-docs
merge; GlobalOrdinalsStringTermsAggregator + coordinator reduce for terms
aggs). On a TPU slice all shards pack in one process, so we can afford
**global dictionaries**: keyword ordinals, numeric uniq-ordinals, histogram
bucket plans, and avgdl/docCount stats are shared across shards. Shard merge
then degenerates to array reductions (sum/min/max/OR) instead of key-space
remapping — the agg reduce rides ICI/host memcpy, not string hashing.

Per-shard state that stays local: postings + term dictionary (each shard
scores its own term blocks; per-shard df supports the reference's default
query_then_fetch idf, global df supports dfs_query_then_fetch).

PR 10: the [S, ...] family built here is consumed as a GSPMD-sharded
PYTREE — `parallel/sharded._stacked_host_tree` names every leaf and
`parallel/spmd.PACK_PARTITION_RULES` maps leaf names to PartitionSpecs
(exactly-one-rule enforced), so adding an array to this class means
adding its rule, or the upload fails loudly instead of replicating the
array S-fold in HBM.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import numpy as np

from ..cluster.routing import shard_for_id
from ..index.mappings import Mappings
from ..index.pack import BLOCK, DocValuesColumn, PackBuilder, ShardPack, VectorColumn


@dataclass
class _ShardView:
    """ShardPack facade handing global stats to query planning.

    `term_blocks` resolves against the shard's own postings but reports the
    global df; `field_stats` and `docvalues` come from the global (stacked)
    dictionaries so every shard plans identical shapes and scores with
    identical statistics. This is the reference's dfs_query_then_fetch
    semantics (search/dfs/DfsPhase.java aggregates term/collection stats
    before scoring) — the only sharded scoring mode here, chosen because
    cross-shard-consistent scores are strictly more useful and global stats
    are free when all shards pack in one process."""

    pack: ShardPack
    stacked: "StackedPack"
    shard_index: int = 0

    @property
    def num_docs(self):
        # padded width: dense accumulators must be the same size on every
        # device of the mesh
        return self.stacked.n_max

    @property
    def field_stats(self):
        return self.stacked.eff_field_stats

    @property
    def docvalues(self):
        return self.stacked.global_docvalues

    @property
    def vectors(self):
        # the stacked union, NOT the per-shard dict: planning state derived
        # here (similarity, dims, field presence) must be identical on every
        # shard because device_eval is traced once for the whole mesh
        return self.stacked.vectors

    @property
    def norms(self):
        return self.pack.norms

    @property
    def text_present(self):
        return self.pack.text_present

    def avgdl(self, fld):
        st = self.stacked.eff_field_stats.get(fld)
        if not st or st["doc_count"] == 0:
            return 1.0
        return st["sum_dl"] / st["doc_count"]

    def term_blocks(self, fld, term):
        s, n, df = self.pack.term_blocks(fld, term)
        return s, n, self.stacked.eff_global_df.get((fld, term), df)

    def dense_row_of(self, fld, term):
        # global tier decision: identical on every shard (see StackedPack)
        return self.stacked.dense_dict.get((fld, term))

    @property
    def dense_tfn(self):
        # batched planning reads only the row-count shape; expose this
        # shard's raw stacked tier rows (tf, not tfn — never scored here)
        dt = getattr(self.stacked, "dense_tf", None)
        return None if dt is None else dt[self.shard_index]

    def impact_served(self):
        # the stacked serving state: the same answer on every shard
        return self.stacked.impact_serving()

    def impact_wscale(self, fld, term):
        """Impact-tier dequant scale (see ShardPack.impact_wscale), gated
        on the STACKED serving state: the searcher must have derived code
        blocks for the current effective stats (refresh_impacts). Returns
        0.0 — not None — for a term this shard simply lacks, so every
        shard prepares the same param shape (the rows are all-padding and
        contribute nothing)."""
        st = self.stacked
        if not st.impact_serving():
            return None
        tid = self.pack.term_dict.get((fld, term))
        if tid is None or self.pack.impact_ubf is None:
            return 0.0
        return float(self.pack.impact_ubf[tid]) / st.impact_meta["qmax"]

    def terms_for_field(self, fld):
        # expansion is per-shard (each shard enumerates its own dictionary),
        # matching the reference's per-shard MultiTermQuery rewrite
        return self.pack.terms_for_field(fld)

    def term_pos_blocks(self, fld, term):
        return self.pack.term_pos_blocks(fld, term)


# sentinel: "no searcher has derived impact codes for this pack yet" —
# distinct from stats_override's None so a fresh pack never claims to serve
_IMPACT_UNSET = object()


class StackedPack:
    def __init__(
        self,
        shards: list[ShardPack],
        mappings: Mappings,
        dense_min_df: int | None = None,
    ):
        self.shards = shards
        self.mappings = mappings
        self.S = len(shards)
        self._nbytes_cache: int | None = None
        # tiered refresh: when this pack is one tier of a (base, tail) pair,
        # the engine overrides the scoring statistics with the COMBINED
        # stats so both tiers score identically (the reference's analog:
        # Lucene collection statistics span all segments at reader open)
        self.stats_override: dict | None = None
        self.n_max = max((p.num_docs for p in shards), default=0)
        self.nb_max = max((p.num_blocks for p in shards), default=1)

        # ---- global stats ------------------------------------------------
        self.field_stats: dict[str, dict] = {}
        for p in shards:
            for fld, st in p.field_stats.items():
                g = self.field_stats.setdefault(fld, {"sum_dl": 0.0, "doc_count": 0})
                g["sum_dl"] += st["sum_dl"]
                g["doc_count"] += st["doc_count"]
        self.global_df: dict[tuple[str, str], int] = {}
        for p in shards:
            for key, tid in p.term_dict.items():
                self.global_df[key] = self.global_df.get(key, 0) + int(p.term_df[tid])

        # ---- global docvalue dictionaries + remapped columns -------------
        # built as columns padded to n_max and stacked [S, n_max]
        self.global_docvalues: dict[str, DocValuesColumn] = {}
        self.stacked_docvalues: dict[str, DocValuesColumn] = {}
        fields = sorted({f for p in shards for f in p.docvalues})
        for fld in fields:
            cols = [p.docvalues.get(fld) for p in shards]
            kind = next(c.kind for c in cols if c is not None)
            vals = []
            has = []
            if kind == "ord":
                terms = sorted({t for c in cols if c and c.ord_terms for t in c.ord_terms})
                ord_of = {t: i for i, t in enumerate(terms)}
                mv_any = any(c is not None and c.mv_pair_docs is not None
                             for c in cols)
                mv_docs_list, mv_ords_list = [], []
                for p, c in zip(shards, cols):
                    v = np.full(self.n_max, -1, np.int32)
                    h = np.zeros(self.n_max, bool)
                    if c is not None:
                        remap = np.array(
                            [ord_of[t] for t in (c.ord_terms or [])] + [-1], np.int32
                        )
                        v[: p.num_docs] = remap[c.values]
                        h[: p.num_docs] = c.has_value
                        if mv_any:
                            if c.mv_pair_docs is not None:
                                mv_docs_list.append(c.mv_pair_docs)
                                mv_ords_list.append(remap[c.mv_pair_ords])
                            else:
                                # single-valued shard: its pairs are the
                                # (doc, value) entries of the dense column
                                sel = np.flatnonzero(c.has_value)
                                mv_docs_list.append(sel.astype(np.int32))
                                mv_ords_list.append(remap[c.values[sel]])
                    elif mv_any:
                        mv_docs_list.append(np.array([], np.int32))
                        mv_ords_list.append(np.array([], np.int32))
                    vals.append(v)
                    has.append(h)
                g = DocValuesColumn(kind, np.stack(vals), np.stack(has), terms)
                if mv_any:
                    pmax = max((len(d) for d in mv_docs_list), default=1) or 1
                    sd = np.full((self.S, pmax), -1, np.int32)
                    so = np.zeros((self.S, pmax), np.int32)
                    for i, (d, o) in enumerate(zip(mv_docs_list, mv_ords_list)):
                        sd[i, : len(d)] = d
                        so[i, : len(o)] = o
                    g.mv_pair_docs = sd
                    g.mv_pair_ords = so
            else:
                dtype = np.int64 if kind == "int" else np.float32
                present_vals = [
                    c.values[c.has_value] for c in cols if c is not None and c.has_value.any()
                ]
                allv = np.concatenate(present_vals) if present_vals else np.array([], dtype)
                uniq = np.unique(allv) if kind == "int" else None
                for p, c in zip(shards, cols):
                    v = np.zeros(self.n_max, dtype)
                    h = np.zeros(self.n_max, bool)
                    if c is not None:
                        v[: p.num_docs] = c.values
                        h[: p.num_docs] = c.has_value
                    vals.append(v)
                    has.append(h)
                g = DocValuesColumn(kind, np.stack(vals), np.stack(has))
                if len(allv):
                    g.vmin = allv.min().item()
                    g.vmax = allv.max().item()
                if kind == "int" and uniq is not None and len(uniq):
                    g.uniq_values = uniq
                    ords = []
                    for p, c in zip(shards, cols):
                        o = np.full(self.n_max, -1, np.int32)
                        if c is not None and c.has_value.any():
                            o[: p.num_docs][c.has_value] = np.searchsorted(
                                uniq, c.values[c.has_value]
                            ).astype(np.int32)
                        ords.append(o)
                    g.uniq_ords = np.stack(ords)
            self.stacked_docvalues[fld] = g
            # planning view: same dict/stats, values not used by prepare
            self.global_docvalues[fld] = g

        # ---- stacked postings & norms ------------------------------------
        # each [S, nb_max, BLOCK] array is written once: a shard's blocks are
        # copied into its slice and only the rows past them are filled, the
        # shards side by side (the copies release the GIL). A shard's blocks
        # are dictionary-sized at least (every term pays a whole block), so
        # a fill of the whole array first would be paid once a shard again
        self.post_docids = np.empty((self.S, self.nb_max, BLOCK), np.int32)
        self.post_tfs = np.empty((self.S, self.nb_max, BLOCK), np.float32)
        self.post_dls = np.empty((self.S, self.nb_max, BLOCK), np.float32)
        self.live = np.zeros((self.S, self.n_max), bool)

        def _stack_postings(i: int) -> None:
            p = shards[i]
            nb = p.num_blocks
            d = self.post_docids[i]
            d[:nb] = p.post_docids
            if p.num_docs != self.n_max:
                # re-sentinel padding to n_max
                np.putmask(d[:nb], d[:nb] == p.num_docs, self.n_max)
            d[nb:] = self.n_max
            self.post_tfs[i, :nb] = p.post_tfs
            self.post_tfs[i, nb:] = 0.0
            self.post_dls[i, :nb] = p.post_dls
            self.post_dls[i, nb:] = 1.0
            self.live[i, : p.num_docs] = p.live

        self._each_shard(_stack_postings)
        # ---- impact tier planning state (BM25S) --------------------------
        # Per-shard row->term/field maps + the static per-row code scale
        # (avgdl-INDEPENDENT: ubf bounds tfn over any doc length, see
        # index/pack.py). The code BLOCKS themselves are derived on device
        # by StackedSearcher.refresh_impacts from the EFFECTIVE field
        # stats — global at build, combined under stats_override — so the
        # tier re-norms with one elementwise pass per refresh, never a
        # host rebuild. `_impact_basis` records which stats the resident
        # codes were derived from; serving is gated on it matching.
        from ..index.pack import (
            IMPACT_QMAX, impact_dtype_default, impact_row_terms,
            impact_term_ubf,
        )

        self.impact_meta = None
        self._impact_basis = _IMPACT_UNSET
        if any(len(p.term_df) for p in shards):
            dtype = impact_dtype_default()
            qmax = IMPACT_QMAX[dtype]
            self.impact_fields = sorted(
                {f for p in shards for (f, _t) in p.term_dict})
            fcode = {f: i for i, f in enumerate(self.impact_fields)}
            self.impact_row_scale_inv = np.zeros(
                (self.S, self.nb_max), np.float32)
            self.impact_row_field = np.full(
                (self.S, self.nb_max), -1, np.int32)
            for i, p in enumerate(shards):
                T = len(p.term_df)
                if T == 0:
                    continue
                ubf = p.impact_ubf
                if ubf is None:
                    ubf = impact_term_ubf(p.term_block_start, p.block_max_tf)
                    p.impact_ubf = ubf
                rt = impact_row_terms(p.term_block_start, p.num_blocks)
                fields_by_tid = np.array(
                    [fcode[f] for (f, _t), _tid in sorted(
                        p.term_dict.items(), key=lambda kv: kv[1])],
                    np.int32)
                sel = rt >= 0
                rows = np.flatnonzero(sel)
                self.impact_row_scale_inv[i, rows] = (
                    qmax / np.maximum(ubf[rt[sel]], 1e-9))
                self.impact_row_field[i, rows] = fields_by_tid[rt[sel]]
            from ..index.pack import BM25_B, BM25_K1

            self.impact_meta = {"dtype": dtype, "qmax": qmax,
                                "k1": BM25_K1, "b": BM25_B}

        # ---- stacked position blocks -------------------------------------
        self.pos_keys = None
        if any(p.pos_keys is not None for p in shards):
            from ..index.pack import POS_INF

            nbp_max = max(
                (p.pos_keys.shape[0] for p in shards if p.pos_keys is not None),
                default=1,
            )
            self.pos_keys = np.full((self.S, nbp_max, BLOCK), POS_INF, np.int64)
            for i, p in enumerate(shards):
                if p.pos_keys is not None:
                    self.pos_keys[i, : p.pos_keys.shape[0]] = p.pos_keys
        norm_fields = sorted({f for p in shards for f in p.norms})
        self.norms = {}
        self.text_present = {}
        for fld in norm_fields:
            arr = np.ones((self.S, self.n_max), np.float32)
            pres = np.zeros((self.S, self.n_max), bool)
            for i, p in enumerate(shards):
                if fld in p.norms:
                    arr[i, : p.num_docs] = p.norms[fld]
                    pres[i, : p.num_docs] = p.text_present[fld]
            self.norms[fld] = arr
            self.text_present[fld] = pres
        # completion inputs: host-side union with shard tags, input-sorted
        self.completion: dict[str, list] = {}
        for i, p in enumerate(shards):
            for fld, entries in p.completion.items():
                self.completion.setdefault(fld, []).extend(
                    (inp, w, i, d) for (inp, w, d) in entries
                )
        for fld in self.completion:
            self.completion[fld].sort()
        # ---- stacked vectors ---------------------------------------------
        self.vectors: dict[str, VectorColumn] = {}
        vec_fields = sorted({f for p in shards for f in p.vectors})
        for fld in vec_fields:
            vc0 = next(p.vectors[fld] for p in shards if fld in p.vectors)
            vals = np.zeros((self.S, self.n_max, vc0.dims), np.float32)
            has = np.zeros((self.S, self.n_max), bool)
            for i, p in enumerate(shards):
                if fld in p.vectors:
                    vals[i, : p.num_docs] = p.vectors[fld].values
                    has[i, : p.num_docs] = p.vectors[fld].has_value
            svc = VectorColumn(vals, has, vc0.similarity, vc0.dims,
                               ann_quant=vc0.ann_quant)
            # stacked ANN: present only when EVERY populated shard built
            # one (uniform nlist ensured by shared mappings). Shards pad
            # to the widest (C, L); pad centroids get a huge norm so
            # their probe logit (c.q - ||c||^2/2) can never win, pad
            # slots stay -1 (dead lanes in the gather-scan).
            anns = [p.vectors[fld].ann for p in shards if fld in p.vectors]
            if anns and all(v is not None for v in anns):
                C = max(v["centroids"].shape[0] for v in anns)
                L = max(v["tile"] for v in anns)
                D = vc0.dims
                cents = np.full((self.S, C, D), 1e6, np.float32)
                order = np.full((self.S, C, L), -1, np.int32)
                codes = np.zeros((self.S, C, L, D), np.int8)
                scale = np.zeros((self.S, C, L), np.float32)
                offset = np.zeros((self.S, C, L), np.float32)
                for i, p in enumerate(shards):
                    v = p.vectors[fld].ann if fld in p.vectors else None
                    if v is None:
                        continue
                    c_i, l_i = v["order"].shape
                    cents[i, :c_i] = v["centroids"]
                    order[i, :c_i, :l_i] = v["order"]
                    codes[i, :c_i, :l_i] = v["codes"]
                    scale[i, :c_i, :l_i] = v["scale"]
                    offset[i, :c_i, :l_i] = v["offset"]
                svc.ann = {
                    "centroids": cents, "order": order, "codes": codes,
                    "scale": scale, "offset": offset,
                    "nlist": C, "tile": L,
                    "built_n": max(v["built_n"] for v in anns),
                }
            self.vectors[fld] = svc

        # ---- global dense tier -------------------------------------------
        # tier membership must be a GLOBAL decision (global df) so every
        # shard's query plan routes each term identically — the per-shard
        # program is traced once for the whole mesh. RAW tf rows are stored
        # (dense_tf); the scored tfn rows are computed ON DEVICE from
        # (tf, norms, avgdl) by the searcher — avgdl is a runtime input, so
        # stat drift from tiered refreshes re-norms the tier with one
        # elementwise device pass instead of a host rebuild + transfer.
        from ..index.pack import default_dense_min_df

        n_total = sum(p.num_docs for p in shards)
        thresh = dense_min_df if dense_min_df is not None else default_dense_min_df(n_total)
        dense_keys = sorted(k for k, df in self.global_df.items() if df >= thresh)
        self.dense_dict: dict[tuple[str, str], int] = {
            k: i for i, k in enumerate(dense_keys)
        }
        self.dense_fields: list[str] = [k[0] for k in dense_keys]
        self.dense_tf = None
        if dense_keys:
            self.dense_tf = np.zeros((self.S, len(dense_keys), self.n_max), np.float32)

            def _stack_dense(s: int) -> None:
                p = shards[s]
                for i, k in enumerate(dense_keys):
                    s0, nb, _df = p.term_blocks(k[0], k[1])
                    if nb == 0:
                        continue
                    docs = p.post_docids[s0 : s0 + nb].ravel()
                    valid = docs < p.num_docs
                    docs = docs[valid]
                    tfs = p.post_tfs[s0 : s0 + nb].ravel()[valid]
                    self.dense_tf[s, i, docs] = tfs

            # a shard's rows are its own slab of the array: side by side
            self._each_shard(_stack_dense)

    def _each_shard(self, fn) -> None:
        """`fn(s)` for every shard, the shards side by side where there are
        several: what it does is NumPy copies into the shard's own slice of
        an [S, ...] array, which release the GIL."""
        if self.S <= 1:
            for s in range(self.S):
                fn(s)
            return
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(default_shard_builders(self.S)) as ex:
            list(ex.map(fn, range(self.S)))

    def impact_serving(self) -> bool:
        """True when the resident impact code blocks were derived from the
        CURRENT effective stats (StackedSearcher.refresh_impacts ran after
        the last stats_override change) — the planning gate for the
        gather+sum scoring path. A stale basis degrades to the exact
        raw-postings path, never to wrong scores."""
        return (self.impact_meta is not None
                and self._impact_basis is self.stats_override)

    @property
    def eff_field_stats(self) -> dict:
        if self.stats_override is not None:
            return self.stats_override["field_stats"]
        return self.field_stats

    @property
    def eff_global_df(self) -> dict:
        if self.stats_override is not None:
            return self.stats_override["global_df"]
        return self.global_df

    @property
    def num_docs(self) -> int:
        return sum(p.num_docs for p in self.shards)

    @property
    def dense_v(self) -> int:
        """Dense-tier row count (0 = no tier) — the fused-kernel geometry
        input shared by the single-shard and sharded fused searchers."""
        return 0 if self.dense_tf is None else self.dense_tf.shape[1]

    def shard_view(self, s: int) -> _ShardView:
        return _ShardView(self.shards[s], self, s)

    def nbytes(self) -> int:
        """Total array bytes of the stacked device-bound structures (the
        memory the circuit breaker must admit before the pack ships to HBM)."""
        if self._nbytes_cache is not None:
            return self._nbytes_cache

        seen: set[int] = set()
        total = 0

        scalars = (str, int, float, bool, type(None))

        def walk(obj):
            nonlocal total
            if isinstance(obj, scalars):
                return
            if id(obj) in seen:
                return
            seen.add(id(obj))
            if isinstance(obj, np.ndarray):
                total += obj.nbytes
            elif isinstance(obj, dict):
                # dictionary-sized maps of scalars (term -> id, term -> df):
                # no call a value
                for v in obj.values():
                    if type(v) not in scalars:
                        walk(v)
            elif isinstance(obj, (list, tuple)):
                for v in obj:
                    if type(v) not in scalars:
                        walk(v)
            elif hasattr(obj, "__dict__"):
                for k, v in vars(obj).items():
                    # the documents' own sources (JSON values, one dict a
                    # document) hold no array: not walked
                    if k != "doc_sources":
                        walk(v)

        walk({k: v for k, v in vars(self).items() if k != "mappings"})
        if self.impact_meta is not None:
            # the searcher derives the stacked impact-code blocks on
            # device (refresh_impacts): [S, nb_max, BLOCK] at the code
            # dtype, on top of the host planning arrays walked above
            code_bytes = 2 if self.impact_meta["dtype"] == "uint16" else 1
            total += self.S * self.nb_max * BLOCK * code_bytes
        if self.dense_tf is not None:
            # the searcher materializes the derived dense_tfn alongside the
            # raw tf rows on device — admit both copies
            total += self.dense_tf.nbytes
            from ..ops.fused import fused_enabled

            if fused_enabled() != "0":
                # the fused msearch arm holds the split-bf16 [2V, n_pad]
                # stack per shard too (~the f32 tier's bytes again)
                total += self.dense_tf.nbytes
        self._nbytes_cache = total
        return total


def route_docs(
    docs: list[tuple[str, dict]], num_shards: int
) -> list[list[tuple[str, dict]]]:
    """Murmur3-route (id, source) docs to per-shard lists — the single
    source of truth for doc->shard placement; pack building and hit-id
    resolution both consume this."""
    routed: list[list[tuple[str, dict]]] = [[] for _ in range(num_shards)]
    shards = _shards_for_ids([doc_id for doc_id, _ in docs], num_shards)
    if shards is not None:
        for s, doc in zip(shards, docs):
            routed[s].append(doc)
        return routed
    for doc_id, source in docs:
        routed[shard_for_id(doc_id, num_shards)].append((doc_id, source))
    return routed


def _shards_for_ids(ids: list[str], num_shards: int) -> list[int] | None:
    """`shard_for_id` of every id in one call of the native library (a
    refresh routes every live document: 6 us a document in Python, 7 s of
    a 1,179,648-document refresh); None where there is no library or an
    id is not ASCII, and the caller goes id by id."""
    from ..cluster.routing import default_routing_num_shards
    from ..native import get_lib

    lib = get_lib()
    joined = "".join(ids)
    if lib is None or not ids or not joined.isascii():
        return None
    import ctypes

    off = np.zeros(len(ids) + 1, np.int64)
    np.cumsum(np.fromiter(map(len, ids), np.int64, count=len(ids)),
              out=off[1:])
    out = np.empty(len(ids), np.int32)
    routing_num_shards = default_routing_num_shards(num_shards)
    lib.route_ascii_ids(
        joined.encode("ascii"), off.ctypes.data_as(ctypes.c_void_p),
        len(ids), routing_num_shards, routing_num_shards // num_shards,
        out.ctypes.data_as(ctypes.c_void_p))
    return out.tolist()


def _ingest_shard(builder: PackBuilder,
                  shard_docs: list[tuple[str, dict]],
                  mappings: Mappings) -> None:
    """Parse + batch-analyze one shard's docs into its builder (the
    vectorized dispatch inside tags itself `build.analyze`; the host
    oracle lane tags the legacy `analyze` stage)."""
    parsed = [mappings.parse_document(source) for _, source in shard_docs]
    builder.add_documents_batch(
        parsed, doc_ids=[doc_id for doc_id, _ in shard_docs])


def default_shard_builders(num_shards: int) -> int:
    """Shards a refresh builds at once where `indexing.refresh.shard_builders`
    is not set: one builder a shard, as far as the host has cores."""
    import os

    return max(1, min(num_shards, os.cpu_count() or 1))


def build_stacked_pack_routed(
    routed: list[list[tuple[str, dict]]], mappings: Mappings,
    dense_min_df: int | None = None, shard_builders: int | None = None,
    devices: list | None = None,
) -> StackedPack:
    """One pack a shard (analysis + `PackBuilder.build`), `shard_builders` of
    them at once, then the stack. The native accumulator, NumPy and XLA
    release the GIL, so shards built side by side are real wall-clock
    overlap; `shard_builders` bounds the host memory a refresh holds (that
    many shards' accumulators and flat postings at once). `devices[s]` is the
    device that will hold shard `s` (the mesh's `shards` axis): the shard's
    device build stages run there, so four scatters run on four chips and
    not all through the default device. The pack is the serial build's,
    array for array: a shard's build reads nothing of another's.

    Every shard's build is a span `refresh.shard_build` (attributes `shard`,
    `device`) and, in a profiled refresh, an async span of that name in
    `_refresh/profile`; the counters `es.refresh.shard_build.ns` (the sum of
    the shards' own time) over `es.refresh.build_wall.ns` (the wall time of
    the `build` stage that held them) say how many were built at once."""
    from concurrent.futures import ThreadPoolExecutor

    from ..monitoring.refresh_profile import (
        active_collector, collect_build_stages, refresh_stage)
    from ..telemetry import TRACER, metrics

    S = len(routed)
    builders = [PackBuilder(mappings) for _ in range(S)]
    at_once = max(1, min(int(shard_builders or default_shard_builders(S)), S))

    def _build_shard(s: int, on_worker: bool):
        """-> (pack, start ns, end ns, the worker's own stage seconds).
        A worker thread starts in a fresh context: it runs under a stage
        collector of its own (a flat-sum clock is one thread's) and hands
        its stages back; on the calling thread the stages inside (analyze,
        flat_csr, build.*) charge the refresh's own collector."""
        t0 = time.perf_counter_ns()
        with (collect_build_stages() if on_worker
              else contextlib.nullcontext()) as wc:
            with refresh_stage("refresh.shard_build"):
                # analyze stays a named stage: the batch dispatch nested
                # inside charges build.analyze, parse + residual stay in
                # `analyze`
                with refresh_stage("analyze"):
                    _ingest_shard(builders[s], routed[s], mappings)
                # per-shard dense tiers disabled: StackedPack builds its
                # own global one (global df decisions + global avgdl), so a
                # local tier would only burn build time and host RAM
                pack = builders[s].build(
                    dense_min_df=1 << 62,
                    device=devices[s] if devices is not None else None)
        builders[s] = None  # the accumulator's memory goes with its shard
        return (pack, t0, time.perf_counter_ns(),
                wc.finish()[1] if on_worker else None)

    t_wall = time.perf_counter_ns()
    with refresh_stage("build"):
        if at_once == 1:
            built = [_build_shard(s, False) for s in range(S)]
        else:
            with ThreadPoolExecutor(at_once,
                                    thread_name_prefix="shard-build") as ex:
                futures = [ex.submit(_build_shard, s, True)
                           for s in range(S)]
                try:
                    built = [f.result() for f in futures]
                except BaseException:
                    for f in futures:
                        f.cancel()
                    raise
    wall_ns = time.perf_counter_ns() - t_wall
    coll = active_collector()
    for s, (_pack, t0, t1, stages) in enumerate(built):
        device = str(devices[s]) if devices is not None else "default"
        TRACER.record("refresh.shard_build", t0, t1, shard=s, device=device)
        if coll is not None and stages is not None:
            coll.note_worker("refresh.shard_build", t0 * 1e-9, t1 * 1e-9,
                             stages)
    metrics.counter_inc("es.refresh.shard_build.ns",
                        sum(b[2] - b[1] for b in built))
    metrics.counter_inc("es.refresh.build_wall.ns", wall_ns)
    packs = [b[0] for b in built]
    for p, shard_docs in zip(packs, routed):
        # source references (shared with EsIndex.shard_docs) for host-side
        # per-object matching (nested queries, query/nested.py)
        p.doc_sources = [src for _, src in shard_docs]
    with refresh_stage("stack"):
        return StackedPack(packs, mappings, dense_min_df=dense_min_df)


def build_stacked_pack(
    docs: list[tuple[str, dict]], mappings: Mappings, num_shards: int,
    dense_min_df: int | None = None,
) -> StackedPack:
    """Route (id, source) docs to shards (Murmur3 like the reference) and
    pack each shard."""
    return build_stacked_pack_routed(
        route_docs(docs, num_shards), mappings, dense_min_df=dense_min_df)
