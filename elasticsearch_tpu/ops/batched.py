"""Batched multi-query BM25 execution: the `_msearch` fast path.

The reference executes an _msearch as independent async per-shard searches
(reference behavior: action/search/TransportMultiSearchAction.java fan-out).
On TPU a batch of term-disjunction queries is a single fused program with NO
scatter anywhere (profiling: element scatter runs ~200ns/element on TPU — the
one pattern to design out):

  dense tier:  scores[Q, N] = W[Q, V_dense] @ dense_tfn[V_dense, N]   (MXU)
  sparse tail: gather CSR rows -> per-posting partial scores -> sort by
               docid -> run-sum (cummax segmented-scan trick) -> explicit
               (docid, score) candidates
  merge:       dense top-k (candidates masked out) ++ candidates -> top-k

Exactness: every sparse candidate's full score = its run-sum + the dense-tier
score gathered at its docid; a doc with only dense contributions is exact in
the matmul; duplicates between the two lists are removed by masking the dense
top-k entries that appear among candidates. Totals are exact:
|{dense match}| + |{candidates with zero dense score}|.

Constraint: all term weights must be > 0 (true for BM25: idf > 0, boost > 0),
so "matches" == "score > 0". The generic per-query path handles boost == 0.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..index.pack import BLOCK
from .scoring import top_k_of_row


@dataclass
class BatchPlan:
    """Host-side per-batch inputs (all fixed-shape, stackable)."""

    W: np.ndarray  # [Q, V_dense] f32 dense-tier weights (0 = term unused)
    sparse_rows: np.ndarray  # [Q, Ts, B] int32 CSR block rows (0-padded)
    sparse_weights: np.ndarray  # [Q, Ts] f32
    k: int
    dense_only: bool = False  # no sparse terms anywhere -> fused Pallas path
    # per-query dense (tier row, weight) pairs [Q, Td] (0-padded): the
    # sparse view of W, for the tiered path's canonical f32 rescore
    dense_rows: np.ndarray | None = None
    dense_w: np.ndarray | None = None
    # impact tier (BM25S): per-sparse-term dequant weights
    # boost·idf·ubf/qmax [Q, Ts]; None when the pack carries no impact
    # tier (the raw-postings BM25 arms are the only option then)
    impact_w: np.ndarray | None = None


def batch_term_disjunction(
    dev: dict,
    plan_shapes: tuple,  # (Ts, B, k) — trace-time constants
    W: jax.Array,
    sparse_rows: jax.Array,
    sparse_weights: jax.Array,
    avgdl: float,
    num_docs: int,
    k1: float = 1.2,
    b: float = 0.75,
    has_norms: bool = True,
    impact_w: jax.Array | None = None,
):
    """-> (scores [Q,k], docids [Q,k], totals [Q]). Jit-traceable.

    With `impact_w` ([Q, Ts] dequant weights) the sparse tail scores from
    the quantized impact tier (dev["impact_codes"]) instead of the raw
    tf/dl postings — a pure gather+multiply, no BM25 math; everything
    downstream (candidate machinery, totals, merge order) is identical.

    GSPMD contract (PR 10, relaxed PR 11): this function is also the
    vmapped per-shard body of the pjit sharded msearch program
    (`parallel/sharded._msearch_merged`), where XLA's SPMD partitioner
    shards it over the mesh — keep it pure XLA so that stays true. A
    body that needs Pallas/custom calls is no longer locked out of the
    one-program route: it rides an embedded shard_map manual region
    instead (`parallel/spmd.manual_shard_region`, the fused arm's PR-11
    path) — manual regions never ask the partitioner to split anything."""
    Ts, B, k = plan_shapes
    live = dev["live"]
    n = num_docs

    # the scopes name phases, as in `search_solo`: HLO metadata that a
    # capture's device operations carry (`device.score_ms`, `device.topk_ms`)
    with jax.named_scope("score"):
        # ---- dense tier on the MXU ------------------------------------------
        dense = dev.get("dense_tfn")
        if dense is not None and W.shape[1] > 0:
            # HIGHEST: full-f32 MXU passes — default TPU matmul rounds through
            # bf16, which costs ~1e-4 relative score error vs the scalar path.
            # Never one row: a backend may take a matrix-vector route for it
            # that adds in another order than the matrix-matrix route of
            # every larger batch (the CPU's does: 1 ulp), and a query's row
            # must not depend on the batch it rides in.
            Wm = W if W.shape[0] > 1 else jnp.pad(W, ((0, 1), (0, 0)))
            scores_d = jnp.matmul(
                Wm, dense, precision=jax.lax.Precision.HIGHEST)[:W.shape[0]]
        else:
            scores_d = jnp.zeros((W.shape[0], n), jnp.float32)
        scores_d = jnp.where(live[None, :], scores_d, 0.0)

        # ---- sparse tail: explicit candidates, no scatter -------------------
        docids = dev["post_docids"][sparse_rows]  # [Q, Ts, B, 128]
        if impact_w is not None:
            codes = dev["impact_codes"][sparse_rows].astype(jnp.float32)
            part = impact_w[:, :, None, None] * codes  # pad lanes -> 0
        else:
            tfs = dev["post_tfs"][sparse_rows]
            if has_norms:
                dls = dev["post_dls"][sparse_rows]
                denom = tfs + k1 * (1.0 - b + b * dls / avgdl)
            else:
                denom = tfs + k1
            part = sparse_weights[:, :, None, None] * tfs / denom  # pad -> 0
        Q = docids.shape[0]
        C = Ts * B * BLOCK
        cd = docids.reshape(Q, C)
        cs = part.reshape(Q, C)
        # padding lanes carry docid == num_docs and score 0; sort pushes them
        # last. Multi-operand sort, not argsort + take_along_axis: the take is
        # a per-element gather (~30ns/element on TPU), measured 5x slower.
        # Lanes of one docid must stay in term order (the run sums below add
        # them in it), which a stable sort keeps; where (docid, term) fits
        # one int32 it is the key itself, every key is its own and the sort
        # need not be stable: 2.9 s of compile against 9.6 at C = 4,096.
        if (n + 1) * Ts < 2**31:
            term = jnp.arange(C, dtype=jnp.int32) // (B * BLOCK)
            skey, sv = jax.lax.sort((cd * Ts + term[None, :], cs), dimension=1,
                                    num_keys=1, is_stable=False)
            sd = skey // Ts
        else:
            sd, sv = jax.lax.sort((cd, cs), dimension=1, num_keys=1)
        # run sums: a (term, doc) pair holds at most one posting, so a docid's
        # run is at most Ts lanes long and its sum is the lane plus the Ts - 1
        # before it that carry the same docid: Ts - 1 shifted adds in f32. The
        # sum reads the run's own values alone, in one order, so docs whose
        # postings tie score bit-identically (what a f32 prefix sum over the
        # whole row loses: O(prefix/value * 2^-24) noise), a longer padded Ts
        # adds exact zeros, and no f64 is emulated: the f64 cumsum this
        # replaces was 147 s of the program's 178 s on the TPU compiler at
        # (Ts 4, B 8, Q 1) (PERF.md section 6, PR 35).
        col = jnp.arange(C)
        run_sum = sv
        for j in range(1, min(Ts, C)):
            same = jnp.pad(sd[:, :-j], ((0, 0), (j, 0)), constant_values=-1) == sd
            run_sum = run_sum + jnp.where(
                same, jnp.pad(sv[:, :-j], ((0, 0), (j, 0))), 0.0)
        is_end = jnp.where(col[None, :] == C - 1, True, sd != jnp.roll(sd, -1, axis=1))
        live_c = live[jnp.minimum(sd, n - 1)] & (sd < n)
        valid_end = is_end & live_c
        # full candidate score = sparse run sum + dense score at that doc
        dg = jnp.take_along_axis(scores_d, jnp.minimum(sd, n - 1), axis=1)
        cand = jnp.where(valid_end, run_sum + dg, -jnp.inf)

    # ---- merge ----------------------------------------------------------
    with jax.named_scope("topk"):
        masked_d = jnp.where(live[None, :] & (scores_d > 0), scores_d, -jnp.inf)
        # row by row in two levels (ops/scoring.top_k_of_row): as the body
        # of a shard axis this batch is rank 3, and the TPU compiler sorts
        # a rank-3 `lax.top_k` whole: 24 s of compile at N = 294,912
        dv, di = jax.vmap(lambda row: top_k_of_row(row, k))(masked_d)  # [Q, k]
        dup = (di[:, :, None] == sd[:, None, :]) & valid_end[:, None, :]
        dv = jnp.where(dup.any(-1), -jnp.inf, dv)
        # the candidates lie in docid order (the sort above), so an f32
        # top-k over them, which breaks ties by the lowest lane, already is
        # (score desc, docid asc); only its k winners meet the dense tier's k
        # under the int64 rank key. An int64 top_k over all C lanes is
        # sorted whole too: 20 s of compile at C = 4,096.
        kc = min(k, C)
        cv, cpos = jax.vmap(lambda row: top_k_of_row(row, kc))(cand)
        all_v = jnp.concatenate([cv, dv], axis=1)
        all_i = jnp.concatenate(
            [jnp.take_along_axis(sd, cpos, axis=1), di], axis=1)
        # exact (score desc, docid asc) order across both lists: non-negative
        # IEEE f32 bit patterns sort like values as int32 (and -inf sorts
        # below all), so pack [score_bits | ~docid] into one int64 rank key
        score_bits = jax.lax.bitcast_convert_type(all_v, jnp.int32).astype(jnp.int64)
        rank = (score_bits << 32) + (jnp.int64(0xFFFFFFFF) - all_i.astype(jnp.int64))
        _, fidx = jax.lax.top_k(rank, k)
        fv = jnp.take_along_axis(all_v, fidx, axis=1)
        fids = jnp.take_along_axis(all_i, fidx, axis=1)

    totals = (masked_d > 0).sum(axis=1) + (valid_end & (dg <= 0) & (run_sum > 0)).sum(axis=1)
    return fv, fids, totals.astype(jnp.int32)


def batch_term_disjunction_fast(
    dev: dict,
    extras: dict,  # fast-path device arrays (see BatchTermSearcher._fast_extras)
    plan_shapes: tuple,  # (Ts, B, k, M) — trace-time constants
    W: jax.Array,
    sparse_rows: jax.Array,
    sparse_weights: jax.Array,
    avgdl: float,
    num_docs: int,
    k1: float = 1.2,
    b: float = 0.75,
    has_norms: bool = True,
    bf16: bool = False,
):
    """Throughput-oriented mixed dense+sparse scoring for large shards.

    The exact path (batch_term_disjunction) gathers dense scores at EVERY
    sparse candidate — a [Q, Ts*B*128] element gather from [Q, N] that runs at
    ~30ns/element on TPU (the one pathological op class on this hardware,
    measured: 247ms for 8.4M elements). This path cuts candidates to the
    per-query top-M by sparse run-sum before the gather, with an on-device
    proof obligation that the cut did not change the top-k:

        dropped_best[q] + ub_dense[q] < kth_score[q]

    where ub_dense is the query's dense-tier score upper bound (sum of
    weight * per-row max tf/(tf+K)). `exact[q]` reports the proof; callers
    re-run the exact path for the (rare) failing queries.

    Totals follow the reference's default `track_total_hits=10000` contract
    (reference behavior: search/internal/ContextIndexSearcher.java hit-count
    thresholds; TotalHits.Relation GREATER_THAN_OR_EQUAL_TO): `totals_lb` is
    an exact count of dense-tier matches plus kept sparse-only candidates — a
    lower bound that is exact whenever no candidates were cut (C <= M).

    With bf16=True the dense tier matmul runs natively on the MXU in
    bfloat16 with f32 accumulation. The resulting <=0.2% score perturbation
    is below the reference's own 1-byte norm quantization noise
    (index/smallfloat.py; reference SmallFloat.intToByte4), and the top-k
    proof above is evaluated on the perturbed scores, so claimed-exact
    results are exact *for the bf16 score function*.

    -> (scores [Q,k], docids [Q,k], totals_lb [Q], exact [Q] bool,
        dropped [Q] i32) — true total is within [totals_lb, totals_lb +
    dropped]; dropped == 0 means totals_lb is exact.
    """
    Ts, B, k, M = plan_shapes

    # ---- sparse tail ----------------------------------------------------
    docids = dev["post_docids"][sparse_rows]  # [Q, Ts, B, 128]
    tfs = dev["post_tfs"][sparse_rows]
    if has_norms:
        dls = dev["post_dls"][sparse_rows]
        denom = tfs + k1 * (1.0 - b + b * dls / avgdl)
    else:
        denom = tfs + k1
    part = sparse_weights[:, :, None, None] * tfs / denom
    Q = docids.shape[0]
    C = Ts * B * BLOCK
    cd = docids.reshape(Q, C)
    cs = part.reshape(Q, C)
    return fast_topk_from_candidates(
        dev, extras, (k, M), W, cd, cs, num_docs=num_docs, bf16=bf16)


def fast_topk_from_candidates(
    dev: dict,
    extras: dict,
    plan_shapes: tuple,  # (k, M) — trace-time constants
    W: jax.Array,
    cd: jax.Array,  # [Q, C] i32 candidate docids (pad: num_docs)
    cs: jax.Array,  # [Q, C] f32 per-lane partial scores (pad: 0)
    num_docs: int,
    bf16: bool = False,
):
    """The dense tier + candidate sort/run-sum/cut/merge machinery of the
    fast path, taking explicit per-lane candidates: shared by the raw
    BM25 gather (batch_term_disjunction_fast) and the impact-tier
    gather+sum pipeline (BatchTermSearcher.run_impact), so both arms
    carry the identical exactness-proof and totals contracts — 'exact'
    means exact for whichever score function produced the lanes."""
    k, M = plan_shapes
    live = dev["live"]
    n = num_docs

    dense = extras.get("dense_bf16") if bf16 else dev.get("dense_tfn")
    if dense is not None and W.shape[1] > 0:
        Wd = W.astype(jnp.bfloat16) if bf16 else W
        # HIGHEST precision unless bf16 was requested: JAX's *default* f32
        # matmul is itself reduced precision (~3e-4 relative, measured on
        # both backends), enough to swap near-tied ranks vs the bit-exact
        # path — parity with the per-query reference requires full f32
        scores_d = jnp.matmul(
            Wd, dense,
            precision=(None if bf16 else jax.lax.Precision.HIGHEST),
            preferred_element_type=jnp.float32,
        )
        # the proof bound must dominate the *computed* score function: under
        # bf16 both W and the tier round, so use the bf16-derived row maxima
        # inflated by the two operands' worst-case relative rounding
        if bf16:
            ub_dense = jnp.matmul(W, extras["rowmax_bf16"]) * (1.0 + 2.0**-7)
        else:
            # the bound itself must not round below the true sum: HIGHEST
            # here too (it is a [Q,V]x[V] matvec — negligible cost)
            ub_dense = jnp.matmul(
                W, extras["rowmax"], precision=jax.lax.Precision.HIGHEST
            ) * (1.0 + 2.0**-18)
    else:
        scores_d = jnp.zeros((W.shape[0], n), jnp.float32)
        ub_dense = jnp.zeros((W.shape[0],), jnp.float32)
    scores_d = jnp.where(live[None, :], scores_d, 0.0)
    masked_d = jnp.where(scores_d > 0, scores_d, -jnp.inf)
    dv, di = jax.lax.top_k(masked_d, k)
    dense_count = (masked_d > 0).sum(axis=1, dtype=jnp.int32)

    Q, C = cd.shape
    # multi-operand sort replaces argsort + 2x take_along_axis (measured
    # 114ms -> 23ms at [512, 16k]: take_along_axis is itself a gather)
    sd, sv = jax.lax.sort((cd, cs), dimension=1, num_keys=1)
    csum = jnp.cumsum(sv, axis=1)
    col = jnp.arange(C)
    starts = jnp.where(col[None, :] == 0, True, sd != jnp.roll(sd, 1, axis=1))
    base = jnp.where(starts, csum - sv, -jnp.inf)
    run_base = jax.lax.cummax(base, axis=1)
    run_sum = csum - run_base
    is_end = jnp.where(col[None, :] == C - 1, True, sd != jnp.roll(sd, -1, axis=1))
    valid_end = is_end & (sd < n)

    # ---- candidate cut: keep top-M by run-sum ---------------------------
    if M < C:
        # sort (run_sum desc) carrying docids; ascending sort on negated key
        neg = jnp.where(valid_end, -run_sum, jnp.inf)
        _, cd_all, rs_all, ve_all = jax.lax.sort(
            (neg, sd, run_sum, valid_end), dimension=1, num_keys=1
        )
        cd_m, rs_m, ve_m = cd_all[:, :M], rs_all[:, :M], ve_all[:, :M]
        dropped_best = jnp.where(ve_all[:, M], rs_all[:, M], -jnp.inf)
    else:
        cd_m, rs_m, ve_m = sd, run_sum, valid_end
        dropped_best = jnp.full((Q,), -jnp.inf)

    # live-docs check deferred to the kept set (the cut may retain deleted
    # docs over live ones; the exactness proof below stays valid because
    # dropped_best bounds dropped *live* candidates too)
    live_m = live[jnp.minimum(cd_m, n - 1)] & ve_m
    dg = jnp.take_along_axis(scores_d, jnp.minimum(cd_m, n - 1), axis=1)
    cand = jnp.where(live_m, rs_m + dg, -jnp.inf)

    # ---- merge ----------------------------------------------------------
    dup = (di[:, :, None] == cd_m[:, None, :]) & live_m[:, None, :]
    dv = jnp.where(dup.any(-1), -jnp.inf, dv)
    all_v = jnp.concatenate([cand, dv], axis=1)
    all_i = jnp.concatenate([cd_m, di], axis=1)
    score_bits = jax.lax.bitcast_convert_type(all_v, jnp.int32).astype(jnp.int64)
    rank = (score_bits << 32) + (jnp.int64(0xFFFFFFFF) - all_i.astype(jnp.int64))
    _, fidx = jax.lax.top_k(rank, k)
    fv = jnp.take_along_axis(all_v, fidx, axis=1)
    fids = jnp.take_along_axis(all_i, fidx, axis=1)

    totals_lb = dense_count + (live_m & (dg <= 0) & (rs_m > 0)).sum(
        axis=1, dtype=jnp.int32
    )
    # every dropped candidate matches (run_sum > 0) but may already be in
    # dense_count; the spread [lb, lb + dropped] brackets the true total
    if M < C:
        dropped = (ve_all[:, M:] & (rs_all[:, M:] > 0)).sum(axis=1, dtype=jnp.int32)
    else:
        dropped = jnp.zeros((Q,), jnp.int32)
    kth = fv[:, k - 1]
    exact = (dropped_best + ub_dense < kth) | jnp.isneginf(dropped_best)
    return fv, fids, totals_lb, exact, dropped


class _RawChunks:
    """Unsynchronized per-chunk device outputs of a chunked batch run.

    Deliberately NOT a flat device array: any eager device op issued on
    not-yet-ready outputs (a concatenate, even a [:Q] slice) waits for
    them and so serializes multi-group batches. Stitching therefore
    happens host-side in numpy after ONE device_get of everything (tuple(self) or np.asarray
    via __iter__/resolve)."""

    def __init__(self, chunk_outs: list, Q: int, n_out: int):
        self.chunk_outs = chunk_outs
        self.Q = Q
        self.n_out = n_out
        self._resolved: tuple | None = None

    def resolve(self) -> tuple:
        """-> n_out numpy arrays, padding stripped. One device round-trip,
        memoized (indexed access must not re-fetch everything)."""
        if self._resolved is None:
            self._resolved = self.resolve_all([self])[0]
        return self._resolved

    # iterating (or tuple-unpacking) a result resolves it: keeps the
    # `v, i, t = bs.run(...)` call sites working unchanged
    def __iter__(self):
        return iter(self.resolve())

    def __getitem__(self, j):
        return self.resolve()[j]

    @staticmethod
    def stitch(chunks: list, Q: int, n_out: int) -> tuple:
        """Host-side assembly of fetched chunk outputs: concat + strip
        the tail padding. THE single copy of this contract."""
        if len(chunks) == 1:
            return tuple(np.asarray(o)[:Q] for o in chunks[0][:n_out])
        return tuple(
            np.concatenate([np.asarray(c[j]) for c in chunks])[:Q]
            for j in range(n_out)
        )

    @staticmethod
    def resolve_all(raws: list["_RawChunks"]) -> list[tuple]:
        """Resolve several raw results with a single device round-trip."""
        host = jax.device_get([r.chunk_outs for r in raws])
        return [
            _RawChunks.stitch(chunks, r.Q, r.n_out)
            for r, chunks in zip(raws, host)
        ]


class BatchTermSearcher:
    """Compiled-plan cache for batched term-disjunction queries against one
    ShardSearcher's device pack."""

    # fast-path candidate budget: the post-cut dense gather is [Q, M] at
    # ~30ns/element (~32ms per 512-query chunk at 2048). 2048 covers the
    # full candidate set of most real queries (sum of sparse-term dfs),
    # making the cut a no-op — and a no-op cut is provably exact, which is
    # what keeps the rerun rate (the expensive path) low
    FAST_M = 2048
    # query-chunk budget: cap the materialized [Qc, N] f32 score matrix.
    # 2 GB => 512-query chunks on a 1M-doc shard — measured to be the
    # per-chunk sweet spot: doubling the chunk to 1024 made per-chunk time
    # ~2.7x (superlinear top_k/sort behavior at [1024, N]), a net loss
    SCORE_BYTES_BUDGET = 1 << 31  # 2 GB

    def __init__(self, searcher):
        self.searcher = searcher
        self._cache = {}

    def plan(
        self,
        fld: str,
        queries: list[list[tuple[str, float]]],
        k: int,
        *,
        pad_ts: int | None = None,
        pad_b: int | None = None,
    ) -> BatchPlan:
        """queries: per query a list of (term, boost) on field `fld`.
        pad_ts/pad_b force the padded (sparse-term, block) shape so bucketed
        callers share compiled executables across batches."""
        from .scoring import bm25_idf

        pack = self.searcher.pack
        k = min(max(k, 1), max(pack.num_docs, 1))
        V = pack.dense_tfn.shape[0] if pack.dense_tfn is not None else 0
        Q = len(queries)
        doc_count = pack.field_stats.get(fld, {}).get("doc_count") or pack.num_docs
        max_ts, max_b = 1, 1
        has_impact = True
        parsed = []
        for terms in queries:
            dense, sparse = [], []
            for term, boost in terms:
                w = 0.0
                s0, nb, df = pack.term_blocks(fld, term)
                if df > 0:
                    w = boost * bm25_idf(doc_count, df)
                dr = pack.dense_row_of(fld, term)
                if dr is not None:
                    dense.append((dr, w))
                elif nb > 0:
                    isc = pack.impact_wscale(fld, term)
                    if isc is None:
                        has_impact = False
                    sparse.append((s0, nb, w, w * (isc or 0.0)))
                    max_b = max(max_b, nb)
            max_ts = max(max_ts, len(sparse))
            parsed.append((dense, sparse))
        B = pad_b or (1 << (max_b - 1).bit_length())
        if pad_ts:
            max_ts = max(max_ts, pad_ts)
        W = np.zeros((Q, V), np.float32)
        rows = np.zeros((Q, max_ts, B), np.int32)
        ws = np.zeros((Q, max_ts), np.float32)
        iws = np.zeros((Q, max_ts), np.float32)
        td_max = max((len(d) for d, _ in parsed), default=1) or 1
        Td = 1 << (max(td_max, 4) - 1).bit_length()
        dense_rows = np.zeros((Q, Td), np.int32)
        dense_w = np.zeros((Q, Td), np.float32)
        for qi, (dense, sparse) in enumerate(parsed):
            for ti, (dr, w) in enumerate(dense):
                W[qi, dr] += w
                dense_rows[qi, ti] = dr
                dense_w[qi, ti] = w
            for ti, (s0, nb, w, iw) in enumerate(sparse):
                rows[qi, ti, :nb] = np.arange(s0, s0 + nb)
                ws[qi, ti] = w
                iws[qi, ti] = iw
        dense_only = V > 0 and all(not sparse for _, sparse in parsed)
        return BatchPlan(W, rows, ws, k, dense_only,
                         dense_rows=dense_rows, dense_w=dense_w,
                         impact_w=iws if has_impact else None)

    def _chunk_q(self, Q: int) -> int:
        """Power-of-two chunk width: caps the materialized [Qc, N] f32 score
        matrix at SCORE_BYTES_BUDGET (no small-Q floor: on a huge shard the
        budget wins) and bounds the compiled-shape family — every batch size
        maps onto {1, 2, 4, ...} wide executables with tail padding."""
        n = max(self.searcher.pack.num_docs, 1)
        budget = max(1, self.SCORE_BYTES_BUDGET // (4 * n))
        pow2_floor = 1 << (budget.bit_length() - 1)
        if Q >= pow2_floor:
            return pow2_floor
        # whole batch fits one chunk: round Q up to pow2 (tail-padded)
        return 1 << max(Q - 1, 0).bit_length() if Q > 1 else 1

    def _run_chunked(self, kernel, map_key, plan: BatchPlan, n_out: int):
        """Run a traceable kernel(dev, extras, W, sr, sw) over uniform
        [qc, ...] chunks of the plan, one compiled executable shared by all
        chunks.

        Constraints (measured on real hardware):
          - the materialized [qc, N] score matrix must stay under
            SCORE_BYTES_BUDGET, so the query axis is chunked;
          - chunks upload as per-chunk host slices, NOT device-side slices
            of one big array: any eager device op on a not-yet-ready
            buffer (a slice included) acts as a dispatch barrier and
            serializes the whole batch;
          - for the same reason the outputs return UNRESOLVED
            (_RawChunks): no concatenate/[:Q] happens on device — callers
            stitch host-side after one device_get;
          - a `lax.map` over chunks (single dispatch) was tried and is
            SLOWER: the scan serializes against XLA's inter-dispatch
            pipelining and compiles 5-10x longer."""
        Q = plan.W.shape[0]
        qc = self._chunk_q(Q)
        pad = (-Q) % qc
        arrs = [plan.W, plan.sparse_rows, plan.sparse_weights]
        if map_key[0] == "dense_tiered":
            # the tiered kernel rescores against the per-query (tier row,
            # weight) pairs, so they ride along as chunked operands
            arrs += [plan.dense_rows, plan.dense_w]
        if pad:
            arrs = [np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                    for a in arrs]
        cache_key = ("chunk", map_key, qc)
        fn = self._cache.get(cache_key)
        if fn is None:
            fn = jax.jit(kernel)
            self._cache[cache_key] = fn
        if map_key[0] == "fast":
            extras = self._fast_extras(map_key[-1])
        elif map_key[0] == "dense_tiered":
            extras = self._tiered_extras()
        else:
            extras = {}
        dev = self.searcher.dev
        first = tuple(jnp.asarray(a[:qc]) for a in arrs)
        from ..monitoring.xla_introspect import check_dispatch

        # PR 12: the chunk executable vs its own compiled cost analysis
        # (one capture per chunk shape; all chunks share the executable)
        check_dispatch(
            "batched.disjunction", fn, (dev, extras, *first),
            fields={"queries": qc, "num_docs": self.searcher.pack.num_docs,
                    "rows": int(np.prod(plan.sparse_rows[:qc].shape))})
        outs = [
            fn(dev, extras, *(jnp.asarray(a[i : i + qc]) for a in arrs))
            for i in range(0, Q + pad, qc)
        ]
        return _RawChunks(outs, Q, n_out)

    def run(self, fld: str, plan: BatchPlan):
        """-> (scores [Q,k], docids [Q,k], totals [Q]) on device (async).

        Chunks the query axis so the materialized [Qc, N] score matrix stays
        under SCORE_BYTES_BUDGET (a 4096-query batch over a 1M-doc shard
        would otherwise need 15.3 GB of HBM for scores alone)."""
        if plan.dense_only:
            # whole batch lives in the dense tier: fused Pallas scan+topk —
            # scores never leave VMEM (ops/kernels.py)
            from .kernels import scan_topk

            dev = self.searcher.dev
            return scan_topk(
                jnp.asarray(plan.W), dev["dense_tfn"], dev["live"], plan.k
            )
        Ts, B = plan.sparse_rows.shape[1], plan.sparse_rows.shape[2]
        pack = self.searcher.pack
        avgdl = pack.avgdl(fld)
        has_norms = fld in self.searcher.ctx.has_norms
        k = plan.k

        def kernel(dev, extras, W, sr, sw):
            return batch_term_disjunction(
                dev, (Ts, B, k), W, sr, sw,
                avgdl=avgdl, num_docs=pack.num_docs, has_norms=has_norms,
            )

        return self._run_chunked(
            kernel, ("exact", Ts, B, k, fld), plan, 3
        )

    def _fast_extras(self, bf16: bool) -> dict:
        """Fast-path device arrays, kept OUT of searcher.dev: mutating the
        shared dev dict would change its pytree structure and force every
        already-compiled executable that takes dev as an argument to
        retrace (per-query searchers, the exact batch path). Each precision
        mode gets its own fixed-keys dict (stable treedef per compiled fn),
        and the bf16 tier copy (~half the dense tier's HBM again) is only
        materialized if a bf16 call actually happens."""
        attr = "_extras_bf16" if bf16 else "_extras_f32"
        extras = getattr(self, attr, None)
        if extras is None:
            extras = {}
            dev = self.searcher.dev
            if "dense_tfn" in dev:
                if bf16:
                    extras["dense_bf16"] = dev["dense_tfn"].astype(jnp.bfloat16)
                    extras["rowmax_bf16"] = jnp.max(
                        extras["dense_bf16"].astype(jnp.float32), axis=1
                    )
                else:
                    extras["rowmax"] = jnp.max(dev["dense_tfn"], axis=1)
            setattr(self, attr, extras)
        return extras

    def _tiered_extras(self) -> dict:
        """Split-bf16 (hi, lo) copies of the dense tier for the tiered
        selection kernel — kept out of searcher.dev for the same treedef
        reasons as _fast_extras."""
        extras = getattr(self, "_extras_tiered", None)
        if extras is None:
            from .kernels import split_bf16

            dev = self.searcher.dev
            hi, lo = jax.jit(split_bf16)(dev["dense_tfn"])
            extras = {"dense_hi": hi, "dense_lo": lo}
            self._extras_tiered = extras
        return extras

    def run_fast(self, fld: str, plan: BatchPlan, *, bf16: bool = False, M: int | None = None):
        """Throughput path -> (scores [Q,k], docids [Q,k], totals_lb [Q],
        exact [Q], dropped [Q]) on device. See batch_term_disjunction_fast
        for the totals/exactness contract; callers needing guaranteed-exact
        results re-run flagged queries with M = C."""
        dev = self.searcher.dev
        if plan.dense_only:
            from .fused import rank_topk
            from .kernels import (
                EPS_TIERED, KB_TIERED, fused_topk_enabled, scan_topk_xla,
                tiered_candidates,
            )

            k = plan.k
            if (fused_topk_enabled() and k <= KB_TIERED
                    and plan.dense_rows is not None):
                # tiered path (ES_TPU_FUSED_TOPK default): split-bf16
                # selection with a running in-VMEM top-KB on TPU, then the
                # canonical f32 rescore of the survivors against the f32
                # tier — flagged queries (margin test) escalate to the
                # exact scan via msearch's rerun loop
                kb = min(max(KB_TIERED, k), self.searcher.pack.num_docs)
                Td = plan.dense_rows.shape[1]

                def dense_kernel(dv, extras, W, sr, sw, dr, dw):
                    sel_v, sel_i, totals = tiered_candidates(
                        W, extras["dense_hi"], extras["dense_lo"],
                        dv["live"], kb,
                        transform="identity", count_positive=True,
                    )
                    cand_ok = jnp.isfinite(sel_v)
                    dg = dv["dense_tfn"][
                        dr[:, :, None], sel_i[:, None, :]]  # [Qc, Td, kb]
                    resc = jnp.sum(dw[:, :, None] * dg, axis=1)
                    resc = jnp.where(cand_ok & (resc > 0), resc, -jnp.inf)
                    v, i_ = rank_topk(resc, sel_i, min(k, kb))
                    am_kernel = sel_v[:, -1]
                    am_resc = jnp.min(
                        jnp.where(cand_ok, resc, jnp.inf), axis=1)
                    rk = v[:, -1]
                    bound = am_kernel + EPS_TIERED * jnp.abs(am_kernel)
                    safe = (jnp.isneginf(am_kernel) | (rk > bound)
                            | (rk == am_resc))
                    return (v, i_, totals, safe,
                            jnp.zeros(v.shape[0], jnp.int32))

                return self._run_chunked(
                    dense_kernel, ("dense_tiered", k, kb, Td), plan, 5)

            # chunked XLA matmul+top_k fallback (ES_TPU_FUSED_TOPK=0 or
            # k beyond the selection width): the [Qc, N] score
            # materialization stays under SCORE_BYTES_BUDGET
            def dense_kernel(dv, extras, W, sr, sw):
                N = dv["dense_tfn"].shape[1]
                v, i_, t = scan_topk_xla(
                    W,
                    dv["dense_tfn"],
                    dv["live"],
                    jnp.zeros((N,), jnp.float32),
                    jnp.zeros((W.shape[0],), jnp.float32),
                    k=k,
                    transform="identity",
                    count_positive=True,
                )
                ones = jnp.ones(v.shape[0], bool)
                return v, i_, t, ones, jnp.zeros(v.shape[0], jnp.int32)

            return self._run_chunked(dense_kernel, ("dense", k), plan, 5)
        Ts, B = plan.sparse_rows.shape[1], plan.sparse_rows.shape[2]
        M = min(M or self.FAST_M, Ts * B * BLOCK)
        pack = self.searcher.pack
        avgdl = pack.avgdl(fld)
        has_norms = fld in self.searcher.ctx.has_norms
        k = plan.k

        def kernel(dv, extras, W, sr, sw):
            return batch_term_disjunction_fast(
                dv, extras, (Ts, B, k, M), W, sr, sw,
                avgdl=avgdl, num_docs=pack.num_docs, has_norms=has_norms,
                bf16=bf16,
            )

        return self._run_chunked(
            kernel, ("fast", Ts, B, k, M, fld, bf16), plan, 5
        )

    def impact_usable(self) -> bool:
        """The impact tier serves this searcher's sparse terms: routing
        enabled (ES_TPU_IMPACT) and the quantized code blocks resident."""
        from .scoring import impact_enabled

        return impact_enabled() and "impact_codes" in self.searcher.dev

    def run_impact(self, fld: str, plan: BatchPlan, *, M: int | None = None):
        """Impact-tier throughput arm (BM25S) -> the run_fast output
        contract (scores, docids, totals_lb, exact, dropped) on device.

        Two stages, both ahead of the shared candidate tail:
          1. sparse.impact_gather — ops/kernels.impact_gather fetches the
             query terms' quantized code blocks and dequantizes with one
             per-term scalar (Pallas scalar-prefetch arm on TPU, XLA row
             gather elsewhere). No tf, no doc length, no idf: ~6 bytes
             per posting (4 docid + 1-2 code) instead of 12, zero
             arithmetic beyond one multiply.
          2. sparse.impact_sum — fast_topk_from_candidates: the identical
             sort/run-sum/cut/dense-merge machinery of run_fast, so the
             exactness proof and totals contract carry over verbatim
             ('exact' = exact for the impact score function; the
             quantization error bound is index/pack.py's documented
             model, asserted by tests/test_impact.py)."""
        dev = self.searcher.dev
        if plan.dense_only or plan.impact_w is None or "impact_codes" not in dev:
            return self.run_fast(fld, plan, M=M)
        from .kernels import impact_gather

        Ts, B = plan.sparse_rows.shape[1], plan.sparse_rows.shape[2]
        C = Ts * B * BLOCK
        M = min(M or self.FAST_M, C)
        k = plan.k
        n = self.searcher.pack.num_docs
        Q = plan.W.shape[0]
        qc = self._chunk_q(Q)
        pad = (-Q) % qc
        rows_flat = plan.sparse_rows.reshape(Q, Ts * B)
        w_flat = np.repeat(plan.impact_w, B, axis=1)  # [Q, Ts*B]
        arrs = [plan.W, rows_flat, w_flat]
        if pad:
            arrs = [np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                    for a in arrs]
        Wa, rows_a, w_a = arrs
        fn1 = self._cache.get("impact_gather")
        if fn1 is None:
            fn1 = self._cache["impact_gather"] = jax.jit(
                lambda dv, r, w: impact_gather(
                    dv["impact_codes"], dv["post_docids"], r, w))
        key2 = ("impact_sum", k, M)
        fn2 = self._cache.get(key2)
        if fn2 is None:
            def tail(dv, extras, W_, cd, cs):
                return fast_topk_from_candidates(
                    dv, extras, (k, M), W_, cd, cs, num_docs=n)

            fn2 = self._cache[key2] = jax.jit(tail)
        extras = self._fast_extras(False)
        from ..monitoring.xla_introspect import check_dispatch
        from ..telemetry import time_kernel

        code_bytes = int(np.dtype(dev["impact_codes"].dtype).itemsize)
        check_dispatch(
            "sparse.impact_gather", fn1,
            (dev, jnp.asarray(rows_a[:qc]), jnp.asarray(w_a[:qc])),
            fields={"queries": qc, "rows": qc * Ts * B,
                    "code_bytes": code_bytes})
        cands = []
        for i in range(0, Q + pad, qc):
            cands.append(fn1(dev, jnp.asarray(rows_a[i: i + qc]),
                             jnp.asarray(w_a[i: i + qc])))
        with time_kernel("sparse.impact_gather", tier="impact", queries=Q,
                         rows=Q * Ts * B, code_bytes=code_bytes):
            jax.block_until_ready(cands)
        check_dispatch(
            "sparse.impact_sum", fn2,
            (dev, extras, jnp.asarray(Wa[:qc]), *cands[0]),
            fields={"queries": qc, "num_docs": n, "cands": M})
        outs = [
            fn2(dev, extras, jnp.asarray(Wa[i: i + qc]), cd, cs)
            for (cd, cs), i in zip(cands, range(0, Q + pad, qc))
        ]
        return _RawChunks(outs, Q, 5)

    def search(self, fld: str, queries: list[list[tuple[str, float]]], k: int = 10):
        out = self.run(fld, self.plan(fld, queries, k))
        if isinstance(out, _RawChunks):
            return out.resolve()
        return jax.device_get(out)  # dense-only fused path returns arrays

    def plan_bucketed(
        self, fld: str, queries: list[list[tuple[str, float]]], k: int
    ) -> list[tuple[np.ndarray, BatchPlan]]:
        """Split a batch into shape-homogeneous groups before padding.

        One global plan pads every query to the batch's worst case (max
        sparse-term count x max posting blocks); a single long-postings
        query makes all Q queries pay its candidate width in the sort and
        gather stages. Bucketing by power-of-two (Ts, B) keeps each group's
        C = Ts*B*128 proportional to its own heaviest member — the batch
        analog of the reference running each query's own WAND iterator
        rather than one worst-case loop (Lucene per-query scorers).

        -> list of (original query indices, BatchPlan); compiled shapes are
        shared across batches with the same bucket structure.
        """
        pack = self.searcher.pack
        shapes = []
        for terms in queries:
            ts, maxb = 0, 0
            for term, _ in terms:
                if pack.dense_row_of(fld, term) is not None:
                    continue
                _, nb, df = pack.term_blocks(fld, term)
                if nb > 0:
                    ts += 1
                    maxb = max(maxb, nb)
            # buckets: Ts pow2, B in 4x steps from 8 (the waves' ladder).
            # The sparse sort/scan cost per query is proportional to Ts*B,
            # so queries must not pay a heavier query's padding;
            # executable dispatches are effectively free once compiled, so
            # more groups only cost one-time compiles (persisted in the
            # XLA cache).
            shapes.append(
                ((1 << max(ts - 1, 0).bit_length()) if ts else 0,
                 self.wave_b_tier(maxb) if maxb else 0)
            )
        groups: dict[tuple, list[int]] = {}
        for qi, sh in enumerate(shapes):
            groups.setdefault(sh, []).append(qi)
        out = []
        for (ts_b, b_b), idxs in sorted(groups.items()):
            sub = [queries[i] for i in idxs]
            out.append(
                (
                    np.asarray(idxs, np.int64),
                    self.plan(fld, sub, k,
                              pad_ts=ts_b or None, pad_b=b_b or None),
                )
            )
        return out

    def _fused_searcher(self, k):
        """Cached FusedTermSearcher when the pack/k qualify, else None."""
        from .fused import FusedTermSearcher

        if not FusedTermSearcher.usable(self.searcher.pack, k):
            return None
        fs = getattr(self, "_fused", None)
        if fs is None:
            fs = self._fused = FusedTermSearcher(self)
        return fs

    # the smallest batch tier (`serving.wave.min_tier`): a wave, or an
    # escalation, of fewer queries is padded up to it. Process-wide like
    # the program caches it bounds; the engine's settings consumer sets it.
    WAVE_MIN_TIER = 1

    @classmethod
    def wave_q_tier(cls, q: int) -> int:
        """The compiled batch tier a q-query wave pads to: the next power
        of two (the same {1, 2, 4, ...} executable family `_chunk_q` and
        `plan_bucketed` already key their compiled-plan caches on), at
        least WAVE_MIN_TIER. The serving front end pads coalesced waves to
        this tier so steady-state traffic reuses a small family of
        compiled programs, and reports q / wave_q_tier(q) as the wave's
        device occupancy."""
        return cls.pow2_tier(q, cls.WAVE_MIN_TIER)

    @staticmethod
    def pow2_tier(n: int, floor: int = 1) -> int:
        """The least power of two >= n, at least `floor`: the ladder every
        batch tier of the wave programs and the solo path's `match` family
        (query/nodes.match_tiers) are padded to."""
        return max(floor, 1 << max(int(n) - 1, 0).bit_length())

    @staticmethod
    def _steps_of_four(n: int, floor: int) -> int:
        tier = floor
        while tier < n:
            tier *= 4
        return tier

    @classmethod
    def wave_ts_tier(cls, ts: int) -> int:
        """The padded count of sparse terms the widest query of a stacked
        batch is planned with: 4, then in steps of four (4, 16, 64). Coarse
        on purpose: which queries share a wave follows arrival order, so
        every tier is one more program that some wave may be the first to
        need, and a padded term costs a batch Q x B x 128 sorted lanes, small
        beside the dense tier it reads whole."""
        return cls._steps_of_four(ts, 4)

    @classmethod
    def wave_b_tier(cls, nb: int) -> int:
        """The padded count of posting blocks a sparse term (the longest of
        a batch) is planned with: 8, then in steps of four. A term of
        `dense_min_df` documents or more lies in the dense tier, so the
        ladder ends at the pack's own longest sparse term."""
        return cls._steps_of_four(nb, 8)

    @classmethod
    def wave_r_tier(cls, rows: int) -> int:
        """The padded count of posting-block rows a fused chunk is planned
        with: 64, then in steps of four (64, 256, 1,024). Coarser than
        `plan_fused`'s own powers of two for the reason `wave_ts_tier`
        gives: with those, two runs of ten under 64 callers met a program
        for the first time inside the measured window, a 10 s compile
        (PERF.md section 6, PR 35)."""
        return cls._steps_of_four(rows, 64)

    @classmethod
    def wave_td_tier(cls, td: int) -> int:
        """The padded count of dense terms a fused chunk's widest query is
        planned with: 16, then in steps of four: one tier for every query of
        up to 16 dense terms."""
        return cls._steps_of_four(td, 16)

    def msearch_coalesced(self, fld, groups, k: int = 10, **kw):
        """Coalesced msearch for the serving front end: pack several
        callers' query lists into ONE batched dispatch and de-interleave
        the result rows per caller.

        groups: list of per-request query lists (each a list of
        [(term, boost)] queries). -> list of per-group (scores, ids,
        totals, exact) numpy tuples, in group order.

        Each query's result row is byte-identical to running its group
        alone: per-row computations are independent (matmul rows, per-row
        sorts/top-k), bucketed plan shapes derive from each query's OWN
        terms, and chunk padding appends zero-weight queries that
        contribute exact 0.0 to nothing — so coalescing changes only
        which executable tier the batch pads to, never any row's bytes
        (asserted by tests/test_serving.py)."""
        flat = [q for g in groups for q in g]
        if not flat:
            return [(np.zeros((0, k), np.float32), np.zeros((0, k), np.int64),
                     np.zeros((0,), np.int64), np.ones((0,), bool))
                    for _ in groups]
        scores, ids, totals, exact = self.msearch(fld, flat, k, **kw)
        out, pos = [], 0
        for g in groups:
            n = len(g)
            out.append((scores[pos:pos + n], ids[pos:pos + n],
                        totals[pos:pos + n], exact[pos:pos + n]))
            pos += n
        return out

    def msearch_many(self, fld, batches, k: int = 10):
        """Pipelined multi-batch msearch (serving-concurrency regime):
        every batch dispatches before any fetch. Falls back to sequential
        msearch when the fused path is unavailable."""
        fs = self._fused_searcher(k)
        if fs is not None:
            return fs.msearch_many(fld, batches, k)
        return [self.msearch(fld, qs, k) for qs in batches]

    def msearch(
        self,
        fld: str,
        queries: list[list[tuple[str, float]]],
        k: int = 10,
        *,
        fast: bool = True,
        bf16: bool = False,
        track_total_hits: int = 10_000,
    ):
        """Bucketed batch search -> (scores [Q,k], docids [Q,k], totals [Q],
        first_pass_exact [Q]) as numpy, stitched back to input order.

        fast=True uses the candidate-cut path and re-runs (with the cut
        disabled) any query whose top-k exactness proof failed OR whose
        total-hits bracket straddles track_total_hits, so top-k docs are
        ALWAYS exact and totals satisfy the reference's track_total_hits
        contract: exact below the threshold, lower bound at/above it
        (reference behavior: TotalHits.Relation / ContextIndexSearcher
        hit-count thresholds). first_pass_exact reports which queries were
        proven exact WITHOUT the rerun — the fast path's hit rate.

        Missing-hit columns carry -inf scores (when fewer than k docs
        match, and when k was clamped to the doc count)."""
        arm = "exact"
        if fast:
            # PR 18: eligible arms (same gates as before — fused needs a
            # usable FusedTermSearcher, impact a servable impact tier)
            # route through the execution planner: static priority
            # fused > impact > fast while cold, argmin of predicted
            # walls once the kernel EMAs are warm
            from ..planner import execution_planner

            fs = self._fused_searcher(k)
            n_docs = self.searcher.pack.num_docs
            cands = []
            if fs is not None:
                cands.append(("fused", "fused.pallas_scan",
                              {"k": k, **fs._cost_fields(len(queries))}))
            if self.impact_usable():
                cands.append(("impact", "sparse.impact_sum",
                              {"queries": len(queries), "k": k,
                               "num_docs": n_docs}))
            cands.append(("exact", "batched.disjunction",
                          {"queries": len(queries), "k": k,
                           "num_docs": n_docs}))
            arm = execution_planner().choose_arm("batched.msearch", cands)
            if arm == "fused":
                from ..telemetry import profile_event, time_kernel

                profile_event("tier", tier="fused", queries=len(queries))
                with time_kernel("fused.msearch", tier="fused",
                                 queries=len(queries), k=k):
                    return fs.msearch(fld, queries, k)
        Q = len(queries)
        use_impact = arm == "impact"
        scores = np.full((Q, k), -np.inf, np.float32)
        ids = np.zeros((Q, k), np.int64)
        totals = np.zeros((Q,), np.int64)
        exact = np.ones((Q,), bool)
        pending: list[np.ndarray] = []
        parts = []

        def _run_first(plan):
            if not fast:
                return self.run(fld, plan)
            if use_impact and plan.impact_w is not None and not plan.dense_only:
                return self.run_impact(fld, plan)
            return self.run_fast(fld, plan, bf16=bf16)

        for idxs, plan in self.plan_bucketed(fld, queries, k):
            parts.append((idxs, _run_first(plan)))
        # resolve every group with ONE device round-trip, and only after
        # every group was dispatched (no intermediate eager ops: those act
        # as dispatch barriers). Plain-array groups (the dense-only fused
        # path under fast=False) join the same fetch.
        from ..telemetry import profile_event, time_kernel

        tier = ("impact" if use_impact else "fast") if fast else "exact"
        profile_event("tier", tier=tier, queries=Q)
        raws = [p.chunk_outs if isinstance(p, _RawChunks) else p
                for _, p in parts]
        if use_impact:
            # the impact arm's candidate tail: the gather stage already
            # synced under its own sparse.impact_gather span (run_impact)
            with time_kernel("sparse.impact_sum", tier="impact", queries=Q,
                             k=k, num_docs=self.searcher.pack.num_docs):
                host = jax.device_get(raws)
        else:
            with time_kernel("batched.disjunction",
                             tier=tier, queries=Q, k=k,
                             num_docs=self.searcher.pack.num_docs):
                host = jax.device_get(raws)
        parts = [
            (idxs, _RawChunks.stitch(h, p.Q, p.n_out)
             if isinstance(p, _RawChunks) else h)
            for (idxs, p), h in zip(parts, host)
        ]
        for idxs, out in parts:
            kk = out[0].shape[1]
            scores[idxs, :kk] = out[0]
            ids[idxs, :kk] = out[1]
            totals[idxs] = out[2]
            if len(out) > 3:
                topk_ok = out[3]
                totals_ok = (out[4] == 0) | (out[2] >= track_total_hits)
                ok = topk_ok & totals_ok
                exact[idxs] = ok
                if not ok.all():
                    pending.append(idxs[~ok])
        rerun_m = 4 * self.FAST_M
        while pending:
            # escalate the candidate budget for flagged queries (4x per
            # round, up to M = C where the cut disappears and the result is
            # provably exact with exact sparse-only totals) — reusing the
            # fast-path program family instead of compiling the legacy path
            redo = np.concatenate(pending)
            pending = []
            profile_event("tier", tier="exact_escalation",
                          queries=int(redo.shape[0]))
            rerun_parts = []
            exact_parts = []
            for idxs, plan in self.plan_bucketed(
                fld, [queries[i] for i in redo], k
            ):
                if plan.dense_only:
                    # a tiered-selection flag has no candidate budget to
                    # widen — escalate straight to the exact scan path
                    exact_parts.append((idxs, self.run(fld, plan)))
                    continue
                C = plan.sparse_rows.shape[1] * plan.sparse_rows.shape[2] * BLOCK
                M = min(rerun_m, C)
                if use_impact and plan.impact_w is not None:
                    rerun = self.run_impact(fld, plan, M=M)
                else:
                    rerun = self.run_fast(fld, plan, bf16=bf16, M=M)
                rerun_parts.append((idxs, M >= C, rerun))
            for idxs, out in exact_parts:
                ev, ei, et = [np.asarray(x) for x in (
                    out.resolve() if isinstance(out, _RawChunks)
                    else jax.device_get(out))]
                done = redo[idxs]
                scores[done, : ev.shape[1]] = ev
                ids[done, : ev.shape[1]] = ei
                totals[done] = et
            resolved = _RawChunks.resolve_all([r for _, _, r in rerun_parts])
            for (idxs, uncut, _), (ev, ei, et, eok, edrop) in zip(
                rerun_parts, resolved
            ):
                ok = eok & ((edrop == 0) | (et >= track_total_hits))
                if uncut:
                    ok[:] = True
                done = idxs[ok]
                scores[redo[done], : ev.shape[1]] = ev[ok]
                ids[redo[done], : ev.shape[1]] = ei[ok]
                totals[redo[done]] = et[ok]
                if not ok.all():
                    pending.append(redo[idxs[~ok]])
            rerun_m *= 4
        return scores, ids, totals, exact
