"""Fused batched BM25: bf16 dense matmul + one-hot MXU sparse-add + in-kernel
top-K' + exact match counts, followed by a canonical f32 rescore.

This replaces the round-2 `_msearch` hot path, whose XLA composition paid two
taxes this kernel removes (not measured on this machine yet):

  - `lax.top_k` on a [512, 1M] score matrix costs ~1.25 s — three orders of
    magnitude over the HBM roofline. Here top-K' selection runs inside the
    doc-tile scan against a VMEM accumulator (buffered merge, below).
  - per-element gathers/scatters run on the TPU scalar core (~15-30 ns/elem).
    The sparse tail (CSR postings below the dense-tier df threshold) is
    instead ACCUMULATED INTO THE SCORE TILES BY ONE-HOT MATMULS: candidate
    windows, sorted by (query-subtile, docid), are DMA'd per tile and
    expanded to
        At[p, q] = weight_p * (query_p == q)     [P, QSUB]
        D [p, n] = (docid_p - tile_base == n)    [P, TILE_N]
    so `scores_tile += At.T @ D` performs a segmented scatter-add on the
    MXU. Duplicate (query, doc) candidates sum automatically, which deletes
    the old path's per-(query,doc) run-sum machinery (sort + cummax scan),
    and dense+sparse overlap resolves by ordinary addition instead of a
    candidate-list merge.

The dense-tier matmul runs OUTSIDE the kernel: XLA's [512,896]x[896,1M] bf16
matmul is ~2 ms materialized, and the [Qc, N] bf16 score matrix it writes is
~1 GB of HBM traffic (~2.5 ms) — cheap, unlike its f32 top_k. Totals are
exact: a live lane matches iff its combined score is > 0 (every BM25 term
weight is > 0 — reference behavior: Lucene BM25Similarity idf > 0), and
rounding preserves sign, so the in-kernel count of positive live lanes is
the reference's exact hit count (better than the reference's own default,
which stops counting at 10k — TotalHits.Relation.GREATER_THAN_OR_EQUAL_TO).

Selection in bf16 perturbs near-ties, so the kernel's output is a
CANDIDATE SET, not the result: `canonical_rescore` recomputes each
winner's score in f32 with one shared function used by every path, and the
final ranking is (rescored score desc, docid asc). A per-query safety test
flags queries whose kth rescored score is not provably above anything the
bf16 pass could have excluded; flagged queries re-run on the legacy exact
path. Pattern ties (docs with identical (tf, dl) profiles — common under
quantized norms) produce bit-identical scores in both precisions, so the
kernel's docid tie-break already orders them correctly; the safety test
treats an exact kth==K'th rescored tie as safe for that reason.

SPMD note (PR 10, closed PR 11): these Pallas kernels are custom calls
XLA's GSPMD partitioner cannot shard — but manual partitioning needs no
partitioner, so the sharded consumer
(`parallel/sharded._FusedShardedMsearch.msearch_merged_begin`) runs the
pipeline inside a shard_map region EMBEDDED in the one compiled pjit
program (`parallel/spmd.manual_shard_region`), feeding the on-device
all-gather top-k merge in the same program. The standalone shard_map +
host-merge form survives only as the legacy-execution-model / parity-
oracle route; there is no `ES_TPU_SPMD` arm matrix for the fused tier.

Round-4 restructure (the round-3 bottleneck was ~3,900 grid steps of fixed
sequencing/DMA-issue cost plus per-step tiered top-K' accumulator merges of
up to ~40 VPU reduce rounds — the MXU was <3% busy, BENCH_NOTES.md): the
kernel no longer maintains a cross-step top-K' accumulator at all. Each
grid step covers a WIDE doc tile (TILE_N=4096; 4x fewer steps) and emits
only that tile's top-T candidates (T unrolled reduce rounds); the global
top-K' merge happens OUTSIDE the kernel as one small `lax.top_k` over the
[Q, njc*T] per-tile candidates. Losing a true top-K' entry is detectable
after the fact: if a tile contributed fewer than T of the final K' winners,
its T-th candidate ranks below the K'-th winner, so everything that tile
dropped ranks below the K'-th winner too — hence the exact flag "some tile
saturated its T slots among the K' winners", which composes with the same
rerun escalation as the window-overflow flag. T is sized so saturation is
~never hit at bench shapes (P[>=5 of the top-32 in one 4096-doc tile of
244] ~ 6e-5 per query under exchangeable doc placement). The one-hot
scatter keeps its measured-best 1024-doc granularity (FINE_N): each coarse
step processes its 4 fine sub-windows with exact fori_loop row bounds from
the scalar-prefetched pointers, replacing round 3's unrolled
every-row-gated window walk.

Reference behavior replaced: the DAAT BulkScorer loop + TopScoreDocCollector
(reference: search/internal/ContextIndexSearcher.java:411-431) and the
default hit-count threshold semantics (search/query/QueryPhase.java).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..index.pack import BLOCK

KB = 64  # rescored candidate set size (top-K'); final k must be <= KB
# (round 5: widened 32 -> 64 together with the 2-pass dense tier — the
# deeper candidate margin is what keeps the cheaper selection's flag
# rate negligible: measured 5th-pct relative gap between the 10th and
# 64th dense score is 2.3e-2 vs the 2-pass error bound of 8e-3)
# geometry defaults from the round-4 sweep on a v5e (BENCH_NOTES.md):
# tile 8192 x qsub 256 measured 4.24x the C1 baseline model vs 3.6x for
# 4096x128 — fewer grid steps win until VPU/matmul work dominates
TILE_N = 8192  # coarse doc tile: one grid step scores [QSUB, TILE_N]
FINE_N = 1024  # one-hot scatter + window-pointer granularity (measured best)
TILE_T = 5  # per-tile candidates kept (see saturation flag, module doc)
QSUB = 256  # query sub-tile rows per grid step (2 MXU row blocks)
QC = 512  # fused query-chunk width
# max docs a fused shard may hold (docid bit budget of the window sort key)
MAX_DOCS_FUSED = (1 << 21) - 2 * TILE_N
# relative slack of the split-bf16 SELECTION tier vs the canonical f32
# rescore. The dense tier runs TWO logical passes (Wh@T16 + Wh@T16lo):
# the tier side carries ~15 mantissa bits while the query-weight side is
# bf16-truncated, so the error is dominated by |W - Wh| ~ 2^-9 relative —
# measured max 7.4e-3 on bench-shaped operands at 1M docs; 8e-3 is the
# bound the safety flag uses. (Round 4 ran three passes at 2e-4; round 5
# trades the third [Qc,N] matmul pass — ~7.7 ms/chunk — for a deeper
# KB=64 candidate margin, which the measured k10..k64 gap covers.) The
# split MUST be built by integer masking: the runtime compiles with
# --xla_allow_excess_precision=true, which lets XLA elide
# f32->bf16->f32 round-trips, so `t - bf16(t)` folds to zero and an
# astype-based split silently degenerates to one bf16 pass (measured).
EPS_SPLIT = 8e-3


def _mask_hi(t):
    """Truncate to the top 16 bits (sign+exp+7-bit mantissa): an exactly
    bf16-representable f32 that XLA cannot constant-fold away."""
    bits = jax.lax.bitcast_convert_type(t, jnp.int32)
    return jax.lax.bitcast_convert_type(
        bits & jnp.int32(-65536), jnp.float32
    )


_I0 = np.int32(0)  # index-map constant: python ints trace to i64 under x64


def fused_enabled() -> str:
    """'0' | 'auto' | 'force' — force enables on CPU (interpret, tests)."""
    return os.environ.get("ES_TPU_FUSED", "auto")


def fused_topk_enabled() -> bool:
    """ES_TPU_FUSED_TOPK (default on): run the dense-tier matmul INSIDE the
    Pallas kernel, so the [Qc, N] score matrix lives only as per-tile VMEM
    transients and the running top-t selection never round-trips HBM.
    '0' reverts to the round-5 out-of-kernel matmul (scores materialized
    in HBM, kernel reads tiles of them)."""
    return os.environ.get("ES_TPU_FUSED_TOPK", "auto") != "0"


def _key_bits(n_pad: int, qsub: int, nsub: int):
    qb = int(np.log2(qsub))
    db = max(1, int(np.ceil(np.log2(max(n_pad + 1, 2)))))
    sb = qb + db
    nsb = max(1, int(np.ceil(np.log2(max(nsub, 2)))))
    if sb + nsb > 31:
        raise ValueError("fused window key overflow: shard too large")
    return qb, db, sb


def _topk_rounds(cand_v, cand_i, k):
    """Exact top-k of a candidate row-set by (value desc, id asc): k unrolled
    (max, argmin-id, mask) rounds — VPU reduce/selects, no sort. Same
    contract as ops.kernels._merge_topk."""
    out_v, out_i = [], []
    big = jnp.int32(2**31 - 1)
    for _ in range(k):
        vmax = jnp.max(cand_v, axis=1, keepdims=True)
        ismax = cand_v == vmax
        imin = jnp.min(jnp.where(ismax, cand_i, big), axis=1, keepdims=True)
        out_v.append(vmax)
        out_i.append(imin)
        cand_v = jnp.where(ismax & (cand_i == imin), -jnp.inf, cand_v)
    return jnp.concatenate(out_v, axis=1), jnp.concatenate(out_i, axis=1)


def _cfg_tile() -> int:
    """Coarse tile width; env-overridable for geometry sweeps."""
    return int(os.environ.get("ES_TPU_FUSED_TILE", TILE_N))


def auto_tile_matmul(vp2: int, qsub: int) -> int:
    """Tile width for the in-kernel-matmul mode: the double-buffered
    [vp2, tile] bf16 tier block + f32 sacc + dense transient must fit the
    ~64MB scoped VMEM budget with headroom for the window blocks. At the
    bench shape (V=896 -> vp2=1792, qsub=256) this lands on 4096."""
    budget = 40 * 1024 * 1024
    fixed = 2 * qsub * vp2 * 2  # double-buffered [qsub, vp2] weight block
    per_col = 2 * vp2 * 2 + 8 * qsub  # tier (x2 buffers) + sacc + dense
    tile = (budget - fixed) // max(per_col, 1)
    return max(FINE_N, min(TILE_N, (tile // FINE_N) * FINE_N))


def _cfg_qsub() -> int:
    """Query sub-tile rows per grid step; env-overridable for sweeps."""
    return int(os.environ.get("ES_TPU_FUSED_QSUB", QSUB))


def tile_t_for(njc: int) -> int:
    """Per-tile candidate count. A tile's share of the top-K' is
    ~Binomial(KB, 1/njc) under exchangeable doc placement, so t is sized
    mean + 5*sigma-ish + slack to keep the saturation-flag rate negligible
    (t=11 at njc=5 measured ~20% flagged; this formula gives 23 there and
    6 at njc=245). t = KB+1 can never flag or lose (a tile holding the
    whole top-K' still keeps K'+1 candidates)."""
    t = int(os.environ.get("ES_TPU_FUSED_T", 0))
    if t > 0:
        return t
    if njc <= 1:
        return KB + 1
    mu = KB / njc
    import math

    return max(TILE_T, min(KB + 1, math.ceil(mu + 5 * math.sqrt(mu) + 4)))


def _fused_kernel(
    ptr_ref,  # scalar prefetch [nsub*(njf+1)] i32 exact fine window starts
    ptrb_ref,  # scalar prefetch [nsub*(njc+1)] i32 coarse window block idx
    *refs,
    # matmul=False refs: (scores [QSUB, tile_n] bf16|f32, live [1, tile_n]
    #   f32, keya/keyb/vala/valb [bud, 128] i32, cv [1, QSUB, t] f32,
    #   ci [1, QSUB, t] i32, ot [QSUB, 1] f32, of [QSUB, 1] f32,
    #   sacc VMEM [QSUB, tile_n] f32, cnt/ovf VMEM [QC, 1] f32)
    # matmul=True: scores is replaced by (w [QSUB, Vp2] bf16 split-bf16
    #   query weights [Wh | Wh], tstack [Vp2, tile_n] bf16 [T16; T16lo]):
    #   the dense tile is computed HERE on the MXU, so the [Qc, N] score
    #   matrix never exists outside VMEM (ES_TPU_FUSED_TOPK tentpole)
    t, tile_n, fine_n, bud, qsub, qb, db, sb, njc, njf, matmul,
):
    if matmul:
        (w_ref, tier_ref, live_ref, keya_ref, keyb_ref, vala_ref, valb_ref,
         cv_ref, ci_ref, ot_ref, of_ref, sacc, cnt, ovf) = refs
    else:
        (scores_ref, live_ref, keya_ref, keyb_ref, vala_ref, valb_ref,
         cv_ref, ci_ref, ot_ref, of_ref, sacc, cnt, ovf) = refs
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((j == 0) & (i == 0))
    def _():
        cnt[:] = jnp.zeros_like(cnt)
        ovf[:] = jnp.zeros_like(ovf)

    # ---- candidate window: two consecutive bud-row blocks ----------------
    # One coarse step owns the sorted-entry range [ptr[i, j*fine],
    # ptr[i, (j+1)*fine]) — contiguous because the sort key is
    # (subtile | docid | qlow). The pipeline streams the two bud-row blocks
    # around its start; rows are walked with EXACT fori_loop bounds per
    # fine sub-tile (no per-row gating), and per-entry masks handle block
    # edges, foreign subtiles, and sentinel padding. A range outside the
    # 2*bud resident rows loses its tail -> overflow flag -> rerun.
    fine = tile_n // fine_n
    wrow0 = ptrb_ref[i * (njc + 1) + j] * bud
    qrow = jax.lax.broadcasted_iota(jnp.int32, (qsub, 128), 0)
    nrow = jax.lax.broadcasted_iota(jnp.int32, (fine_n, 128), 0)
    one = jnp.float32(1.0)
    zero = jnp.float32(0.0)
    dn = (((1,), (1,)), ((), ()))
    sacc[...] = jnp.zeros_like(sacc)
    lost = jnp.bool_(False)
    for f in range(fine):
        basef = i * (njf + 1) + j * fine + f
        start = ptr_ref[basef]
        end = ptr_ref[basef + 1]
        # >> 7 == // 128: Mosaic's scalar floor_divide lowering recurses
        # infinitely under x64 (measured; shifts lower cleanly)
        ra = jnp.maximum((start >> 7) - wrow0, 0)
        rb_need = ((end + 127) >> 7) - wrow0
        two_bud = np.int32(2 * bud)
        rb = jnp.minimum(jnp.maximum(rb_need, ra), two_bud)
        lost = lost | (rb_need > two_bud)
        base_doc = (j * fine + f) * fine_n
        col0 = f * fine_n  # static python int: pl.ds lowers it as a literal

        # ---- one-hot expansion: the MXU as a segmented scatter-add ------
        def _row(key_ref, val_ref, off_r, c):
            key = key_ref[pl.ds(c - off_r, 1), :]  # [1, 128]
            val = jax.lax.bitcast_convert_type(
                val_ref[pl.ds(c - off_r, 1), :], jnp.float32
            )
            qlow = key & (qsub - 1)
            doc = jax.lax.shift_right_logical(
                key, jnp.int32(qb)
            ) & ((1 << db) - 1)
            off = doc - base_doc
            inwin = (
                (jax.lax.shift_right_logical(key, jnp.int32(sb)) == i)
                & (off >= 0)
                & (off < fine_n)
            )
            At = jnp.where((qrow == qlow) & inwin, val, zero)  # [qsub, 128]
            D = jnp.where((nrow == off) & inwin, one, zero).astype(
                jnp.bfloat16
            )  # [fine_n, 128]
            # split-bf16 weights (masked — see EPS_SPLIT note): hi + lo
            # carries ~15 mantissa bits through two bf16 MXU passes with
            # f32 accumulation, keeping selection within EPS_SPLIT of the
            # canonical f32 rescore
            Ahf = _mask_hi(At)
            Ah = Ahf.astype(jnp.bfloat16)
            Al = (At - Ahf).astype(jnp.bfloat16)
            sacc[:, pl.ds(col0, fine_n)] += jax.lax.dot_general(
                Ah, D, dn, preferred_element_type=jnp.float32
            ) + jax.lax.dot_general(
                Al, D, dn, preferred_element_type=jnp.float32
            )  # [qsub, fine_n]

        jax.lax.fori_loop(
            ra, jnp.minimum(rb, bud),
            lambda c, _, : _row(keya_ref, vala_ref, 0, c) or 0, 0,
        )
        jax.lax.fori_loop(
            jnp.maximum(ra, bud), rb,
            lambda c, _, : _row(keyb_ref, valb_ref, bud, c) or 0, 0,
        )

    if matmul:
        # 2-pass split-bf16 selection fused with the scan: [Wh | Wh] @
        # [T16; T16lo] accumulates Wh@T16 + Wh@T16lo in f32 on the MXU —
        # same EPS_SPLIT error contract as the out-of-kernel form, but the
        # [QSUB, tile_n] result is a VMEM transient, not HBM traffic
        dense = jax.lax.dot_general(
            w_ref[:], tier_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        dense = scores_ref[:].astype(jnp.float32)
    lv = live_ref[0:1, :] > 0
    total = dense + sacc[...]
    total = jnp.where(lv & (total > 0), total, -jnp.inf)
    ids = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)

    rs = pl.ds(i * qsub, qsub)
    cnt[rs] += jnp.sum(
        total > 0, axis=1, keepdims=True, dtype=jnp.float32
    )
    ovf[rs] += jnp.broadcast_to(lost.astype(jnp.float32), (qsub, 1))

    # ---- per-tile top-t: the ONLY selection done in-kernel ---------------
    tv, ti = _topk_rounds(total, ids, t)
    cv_ref[_I0] = tv
    ci_ref[_I0] = ti

    @pl.when(j == njc - 1)
    def _():
        ot_ref[:] = cnt[rs]
        of_ref[:] = ovf[rs]


@functools.partial(
    jax.jit,
    static_argnames=("t", "tile_n", "fine_n", "bud", "qsub", "interpret"),
)
def fused_tile_candidates(
    scores,  # [Qc, Npad] bf16 | f32 dense-tier scores (padding cols = 0),
    #         OR None with (w, tstack) set: the matmul runs in-kernel
    live,  # [1, Npad] f32 (0 for dead/padding)
    keys,  # [Gpad/128, 128] i32 sorted window keys; rows % bud == 0, with
    #       >= 2*bud trailing sentinel rows (key = int32 max)
    vals,  # [Gpad/128, 128] i32 f32-bits of the per-posting partial scores
    ptr,  # [nsub*(njf+1)] i32 window starts (entry index) into keys/vals
    w=None,  # [Qc, Vp2] bf16 [Wh | Wh] split query weights (matmul mode)
    tstack=None,  # [Vp2, Npad] bf16 [T16; T16lo] stacked tier (matmul mode)
    *,
    t,
    bud,
    tile_n=TILE_N,
    fine_n=FINE_N,
    qsub=QSUB,
    interpret=False,
):
    """-> (cand_v [Qc, njc*t] f32, cand_i [Qc, njc*t] i32, totals [Qc] i32,
    window_lost [Qc] bool). Per-tile top-t candidates by split-bf16
    selection (see EPS_SPLIT); totals exact. The global merge + saturation
    flag happen in the caller. With (w, tstack) instead of scores, the
    dense matmul happens inside the kernel per doc tile (the
    ES_TPU_FUSED_TOPK default): one grid step streams a [Vp2, tile_n] tier
    block and a [qsub, Vp2] weight block through the MXU instead of
    reading a precomputed score tile from HBM."""
    matmul = scores is None
    if matmul:
        qc, vp2 = w.shape
        n_pad = tstack.shape[1]
    else:
        qc, n_pad = scores.shape
    assert qc % qsub == 0 and n_pad % tile_n == 0 and tile_n % fine_n == 0
    nsub = qc // qsub
    njc = n_pad // tile_n
    njf = n_pad // fine_n
    fine = tile_n // fine_n
    qb, db, sb = _key_bits(n_pad, qsub, nsub)
    kernel = functools.partial(
        _fused_kernel,
        t=t, tile_n=tile_n, fine_n=fine_n, bud=bud, qsub=qsub,
        qb=qb, db=db, sb=sb, njc=njc, njf=njf, matmul=matmul,
    )
    nblk = keys.shape[0] // bud
    # coarse window start block (units of bud rows), from the fine ptr
    coarse_start = ptr.reshape(nsub, njf + 1)[:, ::fine]
    ptrb = jnp.minimum(
        coarse_start.reshape(-1) // 128 // bud, nblk - 2
    ).astype(jnp.int32)
    if matmul:
        score_specs = [
            pl.BlockSpec((qsub, vp2), lambda j, i, *_: (i, _I0)),
            pl.BlockSpec((vp2, tile_n), lambda j, i, *_: (_I0, j)),
        ]
        score_ops = (w, tstack)
    else:
        score_specs = [pl.BlockSpec((qsub, tile_n), lambda j, i, *_: (i, j))]
        score_ops = (scores,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(njc, nsub),
        in_specs=score_specs + [
            pl.BlockSpec((1, tile_n), lambda j, i, *_: (_I0, j)),
            pl.BlockSpec(
                (bud, 128),
                lambda j, i, ptr, ptrb: (ptrb[i * (njc + 1) + j], _I0),
            ),
            pl.BlockSpec(
                (bud, 128),
                lambda j, i, ptr, ptrb: (ptrb[i * (njc + 1) + j] + 1, _I0),
            ),
            pl.BlockSpec(
                (bud, 128),
                lambda j, i, ptr, ptrb: (ptrb[i * (njc + 1) + j], _I0),
            ),
            pl.BlockSpec(
                (bud, 128),
                lambda j, i, ptr, ptrb: (ptrb[i * (njc + 1) + j] + 1, _I0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, qsub, t), lambda j, i, *_: (j, i, _I0)),
            pl.BlockSpec((1, qsub, t), lambda j, i, *_: (j, i, _I0)),
            pl.BlockSpec((qsub, 1), lambda j, i, *_: (i, _I0)),
            pl.BlockSpec((qsub, 1), lambda j, i, *_: (i, _I0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qsub, tile_n), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
        ],
    )
    cv, ci, ot, of = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((njc, qc, t), jnp.float32),
            jax.ShapeDtypeStruct((njc, qc, t), jnp.int32),
            jax.ShapeDtypeStruct((qc, 1), jnp.float32),
            jax.ShapeDtypeStruct((qc, 1), jnp.float32),
        ],
        # v5e has 128MB of physical VMEM; Mosaic's default 16MB scoped
        # budget double-counts per-region transients
        compiler_params=(
            None if interpret else pltpu.CompilerParams(
                vmem_limit_bytes=64 * 1024 * 1024
            )
        ),
        interpret=interpret,
    )(ptr, ptrb, *score_ops, live, keys, keys, vals, vals)
    cv = jnp.swapaxes(cv, 0, 1).reshape(qc, njc * t)
    ci = jnp.swapaxes(ci, 0, 1).reshape(qc, njc * t)
    return cv, ci, ot[:, 0].astype(jnp.int32), of[:, 0] > 0


# ---------------------------------------------------------------------------
# canonical rescore: THE score function both precisions rank by
# ---------------------------------------------------------------------------


def canonical_rescore(
    tier,  # [V, Npad] f32 dense tfn rows (or None)
    dense_rows,  # [Q, Td] i32 (pad row 0 with weight 0)
    dense_w,  # [Q, Td] f32
    row_q,  # [R] i32 owner query of each CSR block row
    docids,  # [R, BLOCK] i32 gathered postings (pad: docid >= n)
    parts,  # [R, BLOCK] f32 per-posting partial scores
    cand_i,  # [Q, KB] i32 kernel winners
    cand_ok,  # [Q, KB] bool valid lanes
):
    """Exact f32 score of each candidate, computed identically by every path:
    dense part by per-(query, dense-term, winner) tier lookups summed in plan
    order; sparse part by comparison-reduce over the gathered posting rows
    and a one-hot f32 matmul segment-sum over block rows. Each (term, doc)
    contributes at most one posting, so the inner reductions add exact zeros
    everywhere but one slot and the result does not depend on padding."""
    Q, kb = cand_i.shape
    if tier is not None and dense_rows.shape[1] > 0:
        dg = tier[dense_rows[:, :, None], cand_i[:, None, :]]  # [Q, Td, KB]
        dsum = jnp.sum(dense_w[:, :, None] * dg, axis=1)
    else:
        dsum = jnp.zeros((Q, kb), jnp.float32)
    if docids.shape[0] > 1:
        win_row = cand_i[row_q]  # [R, KB] winners of each row's owner query
        eq = docids[:, :, None] == win_row[:, None, :]
        row_sum = jnp.sum(
            jnp.where(eq, parts[:, :, None], 0.0), axis=1
        )  # [R, KB]
        qrow = jax.lax.broadcasted_iota(jnp.int32, (Q, docids.shape[0]), 0)
        onehot = (qrow == row_q[None, :]).astype(jnp.float32)
        # [Q, R] @ [R, KB]: segment-sum of row contributions by owner query.
        # Each (q, winner) cell receives <= one nonzero per sparse term.
        ssum = jnp.matmul(onehot, row_sum, precision=jax.lax.Precision.HIGHEST)
    else:
        ssum = jnp.zeros((Q, kb), jnp.float32)
    return jnp.where(cand_ok, dsum + ssum, -jnp.inf)


# ---------------------------------------------------------------------------
# host planning + device pipeline
# ---------------------------------------------------------------------------


class FusedPlan:
    """Host-side per-chunk inputs. Block-row-major: instead of the legacy
    [Q, Ts, B] padded layout (~84% padding at Zipf query mixes), the sparse
    side is one flat list of REAL CSR block rows with an owner query and a
    term weight per row — no per-query shape bucketing at all. R and Td pad
    to powers of two so every batch reuses a tiny compiled-shape family."""

    __slots__ = ("W", "rows", "row_q", "row_w", "dense_rows", "dense_w",
                 "k", "nreal")

    def __init__(self, W, rows, row_q, row_w, dense_rows, dense_w, k,
                 nreal=0):
        self.W = W
        self.rows = rows
        self.row_q = row_q
        self.row_w = row_w
        self.dense_rows = dense_rows
        self.dense_w = dense_w
        self.k = k
        self.nreal = nreal


def plan_fused(pack, fld, queries, k, qc=QC):
    """queries: per query a list of (term, boost); -> FusedPlan padded to
    qc query rows."""
    from .scoring import bm25_idf

    V = pack.dense_tfn.shape[0] if pack.dense_tfn is not None else 0
    Q = len(queries)
    doc_count = pack.field_stats.get(fld, {}).get("doc_count") or pack.num_docs
    rows_l, rowq_l, roww_l = [], [], []
    dense_l = []
    td_max = 1
    for qi, terms in enumerate(queries):
        dlist = []
        for term, boost in terms:
            s0, nb, df = pack.term_blocks(fld, term)
            if df <= 0:
                continue
            w = boost * bm25_idf(doc_count, df)
            dr = pack.dense_row_of(fld, term)
            if dr is not None:
                dlist.append((dr, w))
            elif nb > 0:
                rows_l.append(np.arange(s0, s0 + nb, dtype=np.int32))
                rowq_l.append(np.full(nb, qi, np.int32))
                roww_l.append(np.full(nb, w, np.float32))
        dense_l.append(dlist)
        td_max = max(td_max, len(dlist))
    nreal = sum(len(r) for r in rows_l)
    # quantize R in pow2 steps: every distinct R is a fresh XLA compile
    # (persistent-cached), and Zipf batches flap across boundaries often
    # enough to thrash a finer quantization. (4x steps — the round-3 choice — left the device
    # sorting ~2x more entries than real on average; the sort is a top-3
    # chunk cost, so the extra compile variants pay for themselves.)
    R = 64
    while R < nreal:
        R *= 2
    rows = np.zeros(R, np.int32)  # row 0 of the pack = all-padding block
    row_q = np.zeros(R, np.int32)
    row_w = np.zeros(R, np.float32)
    if nreal:
        rows[:nreal] = np.concatenate(rows_l)
        row_q[:nreal] = np.concatenate(rowq_l)
        row_w[:nreal] = np.concatenate(roww_l)
    Td = 1 << (max(td_max, 4) - 1).bit_length()
    dense_rows = np.zeros((qc, Td), np.int32)
    dense_w = np.zeros((qc, Td), np.float32)
    for qi, dlist in enumerate(dense_l):
        for ti, (dr, w) in enumerate(dlist):
            dense_rows[qi, ti] = dr
            dense_w[qi, ti] = w
    # W ([qc, V] dense query weights) is NOT materialized host-side:
    # the pipeline rebuilds it on device from (dense_rows, dense_w)
    return FusedPlan(None, rows, row_q, row_w, dense_rows, dense_w, k,
                     nreal=nreal)


def _fused_pipeline(
    fa,  # device dict: tier16/tier32 [V, n_pad], live [1, n_pad], post_*
    avgdl,  # () f32 — a TRACED arg: baking this per-pack float into the
    #         HLO caused a fresh compile per shard in the C5 bench
    #         (every shard's avgdl differs slightly)
    rows, row_q, row_w, dense_rows, dense_w,
    *,
    k, n, n_pad, has_norms, k1, b, bud, t, tile_n, interpret,
    qsub=QSUB,
    inkernel=False,
):
    """One fused chunk, fully on device. -> (v [Q,k], i, totals, flags)."""
    qc = dense_rows.shape[0]
    # the dense query-weight matrix is ~99.6% zeros (<= Td terms of V per
    # query): build it ON DEVICE from the tiny (dense_rows, dense_w)
    # pairs instead of uploading [Qc, V] f32 (~1.8 MB a chunk).
    # Duplicate dense terms of one query sum, exactly like the host-side
    # accumulation did.
    V = fa["tier32"].shape[0]
    W = jnp.sum(
        jax.nn.one_hot(dense_rows, V, dtype=jnp.float32)
        * dense_w[:, :, None],
        axis=1,
    )
    R = rows.shape[0]
    nsub = qc // qsub
    njf = n_pad // FINE_N
    njc = n_pad // tile_n
    qb, db, sb = _key_bits(n_pad, qsub, nsub)

    # phase A: gather CSR block rows, per-posting partial scores
    docids = fa["post_docids"][rows]  # [R, BLOCK]
    tfs = fa["post_tfs"][rows]
    if has_norms:
        dls = fa["post_dls"][rows]
        denom = tfs + k1 * (1.0 - b + b * dls / avgdl)
    else:
        denom = tfs + k1
    parts = row_w[:, None] * tfs / denom  # [R, BLOCK]; pad lanes -> 0

    # window sort key: (query subtile | docid | query low bits)
    q2 = row_q[:, None]
    key = (
        ((q2 >> qb) << sb)
        | (docids << qb)
        | (q2 & (qsub - 1))
    )
    # padding lanes (docid >= n, tf == 0) take the sentinel key: without
    # this they all fall into the LAST doc tile's window (docid == n is in
    # range) and their ~30% mass overflows it, flagging every query
    key = jnp.where(docids >= n, jnp.int32(2**31 - 1), key)
    skey, sval = jax.lax.sort(
        (key.reshape(-1), parts.reshape(-1)), num_keys=1
    )
    bounds = (
        (jnp.arange(nsub, dtype=jnp.int32)[:, None] << sb)
        | (jnp.arange(njf + 1, dtype=jnp.int32)[None, :] * FINE_N << qb)
    )
    ptr = jnp.searchsorted(skey, bounds.reshape(-1)).astype(jnp.int32)
    bude = bud * 128
    pad_n = 2 * bude + (-(skey.shape[0] + 2 * bude)) % bude
    sent = jnp.full((pad_n,), jnp.int32(2**31 - 1))
    keys2 = jnp.concatenate([skey, sent]).reshape(-1, 128)
    vals2 = jnp.concatenate(
        [jax.lax.bitcast_convert_type(sval, jnp.int32), sent]
    ).reshape(-1, 128)

    # dense SELECTION tier, 2-pass split-bf16 (Wh@T16 + Wh@T16lo as one
    # stacked matmul): the tier side keeps ~15 mantissa bits; the
    # remaining error is the bf16 truncation of the query weights
    # (~2^-9 relative, EPS_SPLIT bounds it at 8e-3) — covered by the
    # KB=64 candidate margin + canonical rescore + safety flag. Round
    # 4's third pass (Wl@T16, 2e-4 error) cost ~7.7 ms/chunk of pure
    # MXU time for precision the wider margin makes redundant.
    Wh = _mask_hi(W).astype(jnp.bfloat16)
    if "tier16_stack" in fa:
        W2 = jnp.concatenate([Wh, Wh], axis=1)  # [Qc, 2V]
        vp2 = fa["tier16_stack"].shape[0]
        if vp2 > W2.shape[1]:  # stack rows are lane-padded (see _arrays)
            W2 = jnp.pad(W2, ((0, 0), (0, vp2 - W2.shape[1])))
        if inkernel:
            # ES_TPU_FUSED_TOPK default: the dense matmul runs inside the
            # kernel per doc tile; no [Qc, N] score array exists at all
            cv, ci, totals, wlost = fused_tile_candidates(
                None, fa["live"], keys2, vals2, ptr,
                w=W2, tstack=fa["tier16_stack"],
                t=t, bud=bud, tile_n=tile_n, qsub=qsub, interpret=interpret,
            )
            scores = None
        else:
            scores = jnp.matmul(
                W2, fa["tier16_stack"], preferred_element_type=jnp.float32,
            )
    else:
        scores = (
            jnp.matmul(Wh, fa["tier16"], preferred_element_type=jnp.float32)
            + jnp.matmul(
                Wh, fa["tier16_lo"], preferred_element_type=jnp.float32
            )
        )
    if scores is not None:
        cv, ci, totals, wlost = fused_tile_candidates(
            scores, fa["live"], keys2, vals2, ptr,
            t=t, bud=bud, tile_n=tile_n, qsub=qsub, interpret=interpret,
        )

    # global top-K' over the per-tile candidates. An i64 (score, docid)
    # rank-key top_k over the WIDE candidate matrix costs ~13 ms/chunk;
    # instead: f32 top_k by value with a 16-deep margin (~3 ms), then the
    # exact i64 rank order within that margin set. Docid-order selection
    # can only go wrong if a bit-identical value-tie cluster at the K'-th
    # value extends past the margin (pattern ties are common in Zipf
    # corpora — value-boundary ties alone flagged 20-27% of smoke
    # queries); that residue is flagged (tie_clip) and escalates.
    kb_eff = min(KB, cv.shape[1])
    m_eff = min(kb_eff + 16, cv.shape[1])
    mv, sel = jax.lax.top_k(cv, m_eff)
    mi = jnp.take_along_axis(ci, sel, axis=1)
    kv, ki = rank_topk(mv, mi, kb_eff)
    cand_ok = kv > -jnp.inf
    vstar = kv[:, kb_eff - 1 : kb_eff]
    n_at_vstar = jnp.sum(cv == vstar, axis=1)
    n_in_margin = jnp.sum(mv == vstar, axis=1)
    tie_clip = jnp.isfinite(vstar[:, 0]) & (n_at_vstar > n_in_margin)

    # saturation flag: if a tile contributed >= t of the K' winners it may
    # have dropped entries that also belonged in the K' set (module doc
    # has the proof sketch)
    tiles = ki // tile_n
    same_tile = (
        (tiles[:, :, None] == tiles[:, None, :])
        & cand_ok[:, :, None]
        & cand_ok[:, None, :]
    )
    sat = jnp.any(
        cand_ok & (jnp.sum(same_tile, axis=2) >= t), axis=1
    ) | tie_clip

    # canonical rescore + final ranking + safety test
    resc = canonical_rescore(
        fa["tier32"], dense_rows, dense_w, row_q, docids, parts, ki, cand_ok
    )
    v, i = rank_topk(resc, ki, k)
    am_kernel = kv[:, -1]
    am_resc = jnp.min(jnp.where(cand_ok, resc, jnp.inf), axis=1)
    rk = v[:, k - 1]
    bound = am_kernel + EPS_SPLIT * jnp.abs(am_kernel)
    safe = jnp.isneginf(am_kernel) | (rk > bound) | (rk == am_resc)
    return v, i, totals, wlost | sat | ~safe


class FusedTermSearcher:
    """Batched `_msearch` over one shard pack through the fused kernel.

    Wraps a BatchTermSearcher for planning metadata and as the last-resort
    fallback; chunks query batches to QC rows; flagged queries escalate
    bf16 -> f32 scores -> legacy path. All chunks of a call resolve with one
    device round-trip (see ops/batched._RawChunks)."""

    def __init__(self, bts):
        self.bts = bts  # BatchTermSearcher
        self.searcher = bts.searcher
        self._cache = {}
        self._fa = None
        self._fa_live_of = None
        # geometry snapshot: taken ONCE here so a mid-process env change
        # (ES_TPU_FUSED_TILE/QSUB/T sweeps) can never mismatch a cached
        # compiled pipeline against freshly padded arrays (ADVICE r4 #3)
        self._tile_n = _cfg_tile()
        self._qsub = _cfg_qsub()
        self._t_env = int(os.environ.get("ES_TPU_FUSED_T", 0))
        # in-kernel matmul mode (ES_TPU_FUSED_TOPK, default ON): needs the
        # stacked tier layout, and a tile width whose tier block fits VMEM
        pack = self.searcher.pack
        V = pack.dense_tfn.shape[0] if pack.dense_tfn is not None else 0
        self._vp2 = -(-2 * V // 128) * 128  # lane-padded [T16; T16lo] rows
        if (fused_topk_enabled() and V
                and os.environ.get("ES_TPU_FUSED_TILE") is None):
            self._tile_n = min(
                self._tile_n, auto_tile_matmul(self._vp2, self._qsub))
        n_pad = -(-pack.num_docs // self._tile_n) * self._tile_n
        self._use_stack = (
            os.environ.get("ES_TPU_FUSED_STACK", "1") != "0"
            and self._vp2 * n_pad * 2 <= 6 * 1024**3
        )
        self._inkernel = fused_topk_enabled() and self._use_stack and V > 0

    @staticmethod
    def usable(pack, k) -> bool:
        mode = fused_enabled()
        if mode == "0":
            return False
        if pack.dense_tfn is None:
            return False
        if not (0 < k <= 16) or pack.num_docs > MAX_DOCS_FUSED:
            return False
        if mode == "force":
            return True
        return (
            jax.default_backend() == "tpu"
            and pack.num_docs >= 4 * FINE_N
        )

    def _arrays(self):
        dev = self.searcher.dev
        tile_n = self._tile_n
        n = self.searcher.pack.num_docs
        n_pad = ((n + tile_n - 1) // tile_n) * tile_n
        padw = n_pad - n
        if self._fa is None:
            # HBM budget: the f32 tier stays SHARED with the legacy path
            # (unpadded — the rescore only gathers from it); only the
            # bf16 hi/lo pair is padded for the matmul. One fused jit so
            # the padded f32 intermediate is a transient, not a resident.
            self._fa = {
                "tier32": dev["dense_tfn"],
                "post_docids": dev["post_docids"],
                "post_tfs": dev["post_tfs"],
                "post_dls": dev["post_dls"],
            }
            V = dev["dense_tfn"].shape[0]
            # [vp2, n_pad] stacked tier [T16; T16lo] (rows lane-padded to
            # 128 so the in-kernel matmul's blocks tile cleanly) -> ONE
            # dense matmul per chunk (out-of-kernel mode) or the kernel's
            # per-tile operand (in-kernel mode, ES_TPU_FUSED_TOPK); gate
            # on the stack staying inside a 16 GB chip alongside tier32,
            # postings, and per-execution score workspaces. Built by ONE
            # jit straight from the f32 tier so the hi/lo parts never
            # materialize as separate resident arrays (peak = tier32 +
            # stack, not + 2 intermediate copies).
            use_stack = self._use_stack
            rpad = self._vp2 - 2 * V

            @jax.jit
            def split(t):
                tp = jnp.pad(t, ((0, 0), (0, padw)))
                hif = _mask_hi(tp)
                hi = hif.astype(jnp.bfloat16)
                lo = (tp - hif).astype(jnp.bfloat16)
                if use_stack:
                    st = jnp.concatenate([hi, lo], axis=0)
                    return (jnp.pad(st, ((0, rpad), (0, 0))),)
                return hi, lo

            if use_stack:
                (self._fa["tier16_stack"],) = split(dev["dense_tfn"])
            else:
                hi, lo = split(dev["dense_tfn"])
                self._fa["tier16"] = hi
                self._fa["tier16_lo"] = lo
        # tiered refresh re-ships dev["live"] (StackedSearcher.update_live)
        # — rebuild the padded copy whenever the device buffer changes so a
        # long-lived fused searcher never scores deleted docs. The cache
        # key is the buffer OBJECT (held, so its id cannot be recycled).
        if self._fa_live_of is not dev["live"]:
            self._fa["live"] = jnp.pad(
                dev["live"].astype(jnp.float32), (0, padw)
            )[None, :]
            self._fa_live_of = dev["live"]
        return self._fa

    def _compiled_scan(self, fld, C, R, Td, k, nreal, interpret):
        """One EXECUTABLE for a whole C-chunk batch: lax.scan runs the
        per-chunk pipeline sequentially inside a single program, so the
        fixed per-execution dispatch+fetch cost is paid once per BATCH
        instead of once per chunk."""
        pack = self.searcher.pack
        n = pack.num_docs
        tile_n = self._tile_n
        qsub = self._qsub
        n_pad = ((n + tile_n - 1) // tile_n) * tile_n
        njc = n_pad // tile_n
        t = self._t_env if self._t_env > 0 else tile_t_for(njc)
        # window sizing follows the REAL posting count (R counts padded
        # slots — up to ~40% at Zipf loads, which doubles the budget for
        # nothing), quantized in pow2 steps so batch-to-batch jitter cannot
        # flap the compile key; floor 2048 entries: [bud, 128] blocks need
        # >= 8 sublanes
        nreal_q = 1 << max(nreal - 1, 1).bit_length()
        mean_win = max(1, nreal_q * BLOCK // ((QC // qsub) * njc))
        bude = min(
            64 * 1024, max(2048, 1 << (2 * mean_win - 1).bit_length())
        )
        bud = bude // 128
        key = (fld, C, R, Td, k, interpret, bud, tile_n, qsub, t,
               self._inkernel)
        fn = self._cache.get(key)
        from ..monitoring.device import note_executable_cache

        note_executable_cache("fused_scan", fn is not None)
        if fn is None:
            kw = dict(
                k=k, n=n, n_pad=n_pad,
                has_norms=fld in self.searcher.ctx.has_norms,
                k1=1.2, b=0.75,
                bud=bud, t=t, tile_n=tile_n, qsub=qsub,
                interpret=interpret, inkernel=self._inkernel,
            )

            def scan_pipeline(fa, avgdl, rows, row_q, row_w, dr, dw):
                def body(carry, xs):
                    return carry, _fused_pipeline(fa, avgdl, *xs, **kw)

                _, outs = jax.lax.scan(
                    body, 0, (rows, row_q, row_w, dr, dw))
                return outs

            fn = jax.jit(scan_pipeline)
            self._cache[key] = fn
        return fn

    def _dispatch_batch(self, fld, queries, k):
        """Plan + launch one query batch WITHOUT fetching: chunks are
        planned, padded to one (R, Td) envelope, and executed as ONE
        scanned program (_compiled_scan). Returns (idxs, device outs)
        for _collect_batch."""
        Q = len(queries)
        idxs = [np.arange(s, min(s + QC, Q)) for s in range(0, Q, QC)]
        # planning is serial host work ahead of the ONE dispatch; across
        # a multi-batch wave (msearch_many) batch k+1's planning overlaps
        # batch k's device execution because dispatch does not block
        plans = [plan_fused(self.searcher.pack, fld,
                            [queries[i] for i in qidx], k)
                 for qidx in idxs]
        C = len(plans)
        R = max(p.rows.shape[0] for p in plans)
        Td = max(p.dense_rows.shape[1] for p in plans)
        nreal = max(p.nreal for p in plans)

        def _padr(a, width):
            return np.pad(a, [(0, width - a.shape[0])] + [(0, 0)] * (
                a.ndim - 1))

        rows = np.stack([_padr(p.rows, R) for p in plans])
        row_q = np.stack([_padr(p.row_q, R) for p in plans])
        row_w = np.stack([_padr(p.row_w, R) for p in plans])
        dr = np.stack([
            np.pad(p.dense_rows, ((0, 0), (0, Td - p.dense_rows.shape[1])))
            for p in plans])
        dw = np.stack([
            np.pad(p.dense_w, ((0, 0), (0, Td - p.dense_w.shape[1])))
            for p in plans])
        interpret = jax.default_backend() != "tpu"
        fn = self._compiled_scan(fld, C, R, Td, k, nreal, interpret)
        outs = fn(self._arrays(),
                  np.float32(self.searcher.pack.avgdl(fld)),
                  rows, row_q, row_w, dr, dw)
        return idxs, outs

    @staticmethod
    def _collect_batch(Q, k, idxs, host):
        scores = np.full((Q, k), -np.inf, np.float32)
        ids = np.zeros((Q, k), np.int64)
        totals = np.zeros((Q,), np.int64)
        flagged = np.zeros((Q,), bool)
        v, i, t, fl = host
        for ci, qidx in enumerate(idxs):
            nq = len(qidx)
            scores[qidx] = v[ci][:nq]
            ids[qidx] = i[ci][:nq]
            totals[qidx] = t[ci][:nq]
            flagged[qidx] = fl[ci][:nq]
        return scores, ids, totals, flagged

    def _cost_fields(self, queries_n: int) -> dict:
        """Shape fields of one fused pass for the cost model
        (monitoring/costmodel): dense-tier geometry + corpus size."""
        pack = self.searcher.pack
        V = pack.dense_tfn.shape[0] if pack.dense_tfn is not None else 0
        tile_n = self._tile_n
        n_pad = -(-pack.num_docs // tile_n) * tile_n
        return {"v": V, "num_docs": n_pad,
                "queries": -(-queries_n // QC) * QC}

    def _run_pass(self, fld, queries, k):
        """One fused pass over all queries -> (v, i, t, flagged_bool)."""
        from ..telemetry import time_kernel

        idxs, outs = self._dispatch_batch(fld, queries, k)
        with time_kernel("fused.pallas_scan", tier="fused", k=k,
                         **self._cost_fields(len(queries))):
            host = jax.device_get(outs)
        return self._collect_batch(len(queries), k, idxs, host)

    def msearch_many(self, fld, batches, k=10):
        """Pipelined multi-batch msearch: EVERY batch's scanned program is
        dispatched before any result is fetched, so the fixed
        per-execution overhead amortizes across the wave — the serving
        regime of a node answering concurrent _msearch requests (same
        discipline as StackedSearcher.search_batch for aggs). Returns a
        list of msearch-style (scores, ids, totals, first_pass_ok)
        tuples, escalation included."""
        from ..telemetry import time_kernel

        disp = [self._dispatch_batch(fld, qs, k) for qs in batches]
        with time_kernel("fused.pallas_scan", tier="fused", k=k,
                         **self._cost_fields(sum(len(b) for b in batches))):
            hosts = jax.device_get([outs for _idxs, outs in disp])
        out = []
        for qs, (idxs, _), host in zip(batches, disp, hosts):
            raw = self._collect_batch(len(qs), k, idxs, host)
            out.append(self._finish(fld, qs, k, *raw))
        return out

    def msearch(self, fld, queries, k=10):
        """-> (scores [Q,k], docids [Q,k], totals [Q] exact,
        first_pass_ok [Q]) numpy, in input order. Top-k is always the
        canonical f32 ranking; flagged queries (window overflow, or a
        top-k boundary the split-precision pass cannot separate) re-run
        on the legacy exact path, so results never depend on the fused
        pass. The split-bf16 selection keeps the flag rate near zero."""
        scores, ids, totals, flagged = self._run_pass(fld, queries, k)
        return self._finish(fld, queries, k, scores, ids, totals, flagged)

    def _finish(self, fld, queries, k, scores, ids, totals, flagged):
        """Escalate flagged queries on the legacy exact path."""
        first_ok = ~flagged
        if flagged.any():
            from ..telemetry import profile_event

            still = np.nonzero(flagged)[0]
            profile_event("tier", tier="exact_escalation",
                          queries=int(still.shape[0]))
            # legacy exact path (independent machinery). Its final scores
            # equal the canonical values only up to ulps; ranking
            # differences at that level are accepted. The plan pads to a
            # FIXED (Ts, B) envelope: flagged queries are rare (~1e-3),
            # and letting each handful mint its own (Ts, B) bucket costs
            # a fresh multi-minute XLA compile mid-serving.
            flagged_qs = [queries[i] for i in still]
            pack = self.searcher.pack
            max_ts = max(
                (sum(1 for t, _ in q
                     if pack.dense_row_of(fld, t) is None)
                 for q in flagged_qs),
                default=1,
            )
            max_b = max(
                (pack.term_blocks(fld, t)[1]
                 for q in flagged_qs for t, _ in q
                 if pack.dense_row_of(fld, t) is None), default=1)
            from ..telemetry import time_kernel

            with time_kernel("batched.escalation", tier="exact_escalation",
                             queries=int(still.shape[0]), k=k,
                             num_docs=pack.num_docs):
                sv, si, st = [
                    np.asarray(x)
                    for x in self.bts.run(
                        fld,
                        self.bts.plan(
                            fld, flagged_qs, k,
                            pad_ts=1 << (max(max_ts, 4) - 1).bit_length(),
                            pad_b=max(32,
                                      1 << (max(max_b, 1) - 1).bit_length()),
                        ),
                    )
                ]
            scores[still, : sv.shape[1]] = sv
            ids[still, : sv.shape[1]] = si
            totals[still] = st
        return scores, ids, totals, first_ok


def rank_topk(values, ids, k):
    """(score desc, docid asc) exact order via one int64 rank-key top_k.
    values must be >= 0 or -inf (IEEE bit-pattern order trick)."""
    score_bits = jax.lax.bitcast_convert_type(values, jnp.int32).astype(jnp.int64)
    rank = (score_bits << 32) + (jnp.int64(0xFFFFFFFF) - ids.astype(jnp.int64))
    _, sel = jax.lax.top_k(rank, k)
    return (
        jnp.take_along_axis(values, sel, axis=1),
        jnp.take_along_axis(ids, sel, axis=1),
    )
