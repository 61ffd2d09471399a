"""Pallas TPU kernels for the scoring hot loop.

The reference's innermost hot loop is Lucene's `BulkScorer.score` — a
doc-at-a-time pull iterator feeding a top-k heap (reference behavior:
search/internal/ContextIndexSearcher.java:411-431). The TPU inversion keeps
the FLOPs on the MXU and the heap in VMEM:

    fused_scan_topk:  grid over doc tiles; per step a [TILE_B, D] x [D, TILE_N]
    matmul (MXU) produces a tile of scores, which updates a running
    (score desc, docid asc) top-k held in VMEM scratch. TPU grids execute
    sequentially on a core, so the scratch accumulator is race-free — the
    Pallas analog of Lucene's per-segment collector state.

The scan takes q [B, D] against mat_t [D, N]: it serves batched dense-tier
BM25 (q = per-query term weights, mat_t = dense tfn rows) and exact kNN
scans (q = query vectors, mat_t = transposed doc vectors). One query's
already-scored row is not its job (ops/scoring.top_k_with_total selects it
in plain XLA): B = 1 leaves seven of eight sublanes of every vreg idle and
the merge's serial rounds run once a 512-lane tile with nothing to overlap.

Why fusion matters: materializing [B, N] f32 scores for a 4k-query batch over
a 1M-doc shard is ~16 GB of HBM traffic before top-k even starts; the fused
kernel keeps scores in VMEM and writes only [B, k].

The kernel reproduces the exact result order of ops/scoring.top_k_with_total:
score descending, docid ascending on ties, -inf for dead lanes. On non-TPU
backends `scan_topk` dispatches to an XLA reference implementation with
identical semantics (tests compare both, running the kernel in interpret
mode).

Sharded execution (PR 11): these kernels are custom calls GSPMD cannot
partition, so sharded callers run them inside shard_map manual regions
embedded in the one compiled SPMD program
(`parallel/spmd.manual_shard_region`) — per-shard shapes reach the
kernel exactly as the single-device path builds them, and the
surrounding program (all-gather top-k merge) stays GSPMD. No caller
pins the XLA arm for partitionability anymore.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I0 = np.int32(0)  # index-map constant: python ints trace to i64 under x64

_I32_MAX = np.int32(2**31 - 1)


MAX_FUSED_K = 128  # beyond this the unrolled merge loses to sort-based top_k


def _pick_tiles(B: int, D: int, N: int, k: int) -> tuple[int, int] | None:
    """Choose (TILE_B, TILE_N) fitting q + mat + scratch in ~10MB of VMEM.
    None when nothing fits (caller falls back to the XLA path)."""
    tile_b = 128 if B > 8 else 8
    budget = 10 * 1024 * 1024
    # bytes per step ~ 2*(q block + mat block) for double buffering
    for tile_n in (512, 256, 128):
        need = 2 * 4 * (tile_b * D + D * tile_n) + 4 * tile_b * (2 * k + tile_n)
        if need <= budget:
            return tile_b, tile_n
    return None


def _merge_topk(vals, idxs, acc_v, acc_i, k):
    """One merge round: running top-k + a tile of candidates -> new top-k.

    k unrolled (max, argmin-id, mask) rounds over [TB, k + TILE_N]; every op
    is a VPU reduction/select, no sort. Tie-break: lowest docid wins among
    equal scores, matching Lucene's TopScoreDocCollector order.
    """
    cand_v = jnp.concatenate([acc_v, vals], axis=1)
    cand_i = jnp.concatenate([acc_i, idxs], axis=1)
    out_v, out_i = [], []
    for _ in range(k):
        vmax = jnp.max(cand_v, axis=1, keepdims=True)
        ismax = cand_v == vmax
        imin = jnp.min(jnp.where(ismax, cand_i, _I32_MAX), axis=1, keepdims=True)
        out_v.append(vmax)
        out_i.append(imin)
        cand_v = jnp.where(ismax & (cand_i == imin), -jnp.inf, cand_v)
    return jnp.concatenate(out_v, axis=1), jnp.concatenate(out_i, axis=1)


def _apply_transform(dots, transform, auxd_row, auxq_col):
    """Map raw dots to _score space (see ops/vector.py conventions)."""
    if transform == "identity":
        return dots
    if transform == "cosine":
        # auxd = 1/||d||, auxq = 1/||q||
        return (1.0 + dots * auxd_row[None, :] * auxq_col) / 2.0
    if transform == "dot_product":
        return (1.0 + dots) / 2.0
    if transform == "l2_norm":
        # auxd = ||d||^2, auxq = ||q||^2
        l2 = jnp.maximum(auxd_row[None, :] - 2.0 * dots + auxq_col, 0.0)
        return 1.0 / (1.0 + l2)
    if transform == "max_inner_product":
        return jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
    raise ValueError(f"unknown transform [{transform}]")


def _scan_topk_kernel(
    q_ref, m_ref, live_ref, auxd_ref, auxq_ref,
    ov_ref, oi_ref, ot_ref,
    acc_v, acc_i, cnt,
    *, k, tile_n, transform, count_positive,
):
    j = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc_v[:] = jnp.full_like(acc_v, -jnp.inf)
        acc_i[:] = jnp.zeros_like(acc_i)
        cnt[:] = jnp.zeros_like(cnt)

    # HIGHEST: full-f32 MXU passes for bit-parity with the unfused path
    dots = jnp.dot(
        q_ref[:], m_ref[:],
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = _apply_transform(dots, transform, auxd_ref[0, :], auxq_ref[:])
    ids = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    ok = live_ref[0, :] > 0
    scores = jnp.where(ok[None, :], scores, -jnp.inf)
    if count_positive:
        # BM25 match semantics: score <= 0 means "no matching term" (all term
        # weights are > 0), so such lanes are not hits and not candidates
        scores = jnp.where(scores > 0, scores, -jnp.inf)
        cnt[:] += (scores > 0).astype(jnp.float32)
    else:
        cnt[:] += jnp.broadcast_to(ok[None, :], scores.shape).astype(jnp.float32)
    new_v, new_i = _merge_topk(scores, ids, acc_v[:], acc_i[:], k)
    acc_v[:] = new_v
    acc_i[:] = new_i

    @pl.when(j == nn - 1)
    def _():
        ov_ref[:] = acc_v[:]
        oi_ref[:] = acc_i[:]
        ot_ref[:] = jnp.sum(cnt[:], axis=1, keepdims=True).astype(jnp.int32)


def _pad_to(x, mult, axis, value):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("k", "transform", "count_positive", "interpret", "tiles"),
)
def _scan_topk_pallas(
    q, mat_t, live, aux_doc, aux_q,
    *, k, transform, count_positive, interpret, tiles,
):
    B, D = q.shape
    tile_b, tile_n = tiles
    qp = _pad_to(q, tile_b, 0, 0.0)
    mp = _pad_to(mat_t, tile_n, 1, 0.0)
    livep = _pad_to(live.astype(jnp.float32)[None, :], tile_n, 1, 0.0)
    auxdp = _pad_to(aux_doc[None, :], tile_n, 1, 0.0)
    auxqp = _pad_to(aux_q[:, None], tile_b, 0, 0.0)
    Bp, Np = qp.shape[0], mp.shape[1]
    nb, nn = Bp // tile_b, Np // tile_n

    kernel = functools.partial(
        _scan_topk_kernel,
        k=k, tile_n=tile_n, transform=transform,
        count_positive=count_positive,
    )
    out_v, out_i, out_t = pl.pallas_call(
        kernel,
        grid=(nb, nn),
        in_specs=[
            pl.BlockSpec((tile_b, D), lambda i, j: (i, _I0)),
            pl.BlockSpec((D, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((1, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((1, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((tile_b, 1), lambda i, j: (i, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, k), lambda i, j: (i, _I0)),
            pl.BlockSpec((tile_b, k), lambda i, j: (i, _I0)),
            pl.BlockSpec((tile_b, 1), lambda i, j: (i, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, k), jnp.float32),
            pltpu.VMEM((tile_b, k), jnp.int32),
            pltpu.VMEM((tile_b, tile_n), jnp.float32),
        ],
        interpret=interpret,
    )(qp, mp, livep, auxdp, auxqp)
    return out_v[:B], out_i[:B], out_t[:B, 0]


@functools.partial(
    jax.jit, static_argnames=("k", "transform", "count_positive")
)
def scan_topk_xla(q, mat_t, live, aux_doc, aux_q, *, k, transform, count_positive):
    """XLA reference with identical semantics (and the non-TPU fast path).
    Jitted: callers outside a trace (e.g. the batched dense-only dispatch)
    must not fall back to eager per-op execution."""
    dots = jnp.matmul(q, mat_t, precision=jax.lax.Precision.HIGHEST)
    auxq = aux_q[:, None] if aux_q.ndim == 1 else aux_q
    scores = _apply_transform(dots, transform, aux_doc, auxq)
    scores = jnp.where(live[None, :] > 0, scores, -jnp.inf)
    if count_positive:
        scores = jnp.where(scores > 0, scores, -jnp.inf)
        totals = jnp.sum(scores > 0, axis=1, dtype=jnp.int32)
    else:
        totals = jnp.broadcast_to(
            jnp.sum(live > 0, dtype=jnp.int32), (scores.shape[0],)
        )
    top_v, top_i = jax.lax.top_k(scores, k)
    return top_v, top_i.astype(jnp.int32), totals


# auto mode switches to the fused kernel when materializing [B, N] scores
# would cost more HBM traffic than this threshold — below it XLA's own
# matmul+top_k fusion wins (measured on real hardware)
PALLAS_SCORE_BYTES_THRESHOLD = 1 << 31  # 2 GB


def fused_topk_enabled() -> bool:
    """ES_TPU_FUSED_TOPK (default on): route large matmul+top-k scans
    through the tiered split-bf16 selection + f32 rescore path instead of
    f32-HIGHEST matmuls / XLA TopK. '0' reverts every wired call site."""
    return os.environ.get("ES_TPU_FUSED_TOPK", "auto") != "0"


def _mask_hi(t):
    """Truncate f32 to its top 16 bits (exactly bf16-representable) by
    integer masking — an astype round-trip constant-folds away under
    --xla_allow_excess_precision (see ops/fused.py EPS_SPLIT note)."""
    bits = jax.lax.bitcast_convert_type(t, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536), jnp.float32)


def split_bf16(mat: jax.Array) -> tuple[jax.Array, jax.Array]:
    """f32 matrix -> (hi, lo) bf16 pair carrying ~15 mantissa bits: the
    selection-tier layout of the tiered scan (hi = masked top 16 bits,
    lo = exact residual truncated to bf16)."""
    hif = _mask_hi(mat)
    return hif.astype(jnp.bfloat16), (mat - hif).astype(jnp.bfloat16)


# relative slack of tiered split-bf16 selection vs the f32 rescore: the
# query side is bf16-truncated (~2^-9 per element) while the mat side
# carries ~15 mantissa bits — same regime as ops/fused.EPS_SPLIT, with
# margin for the transform's score-space amplification
EPS_TIERED = 2e-2
# selection width: candidates carried to the f32 rescore (the KB-64
# margin discipline of ops/fused.py)
KB_TIERED = 64


def _tiered_scan_kernel(
    q_ref, mh_ref, ml_ref, live_ref, auxd_ref, auxq_ref,
    ov_ref, oi_ref, ot_ref,
    acc_v, acc_i, cnt,
    *, kb, tile_n, transform, count_positive,
):
    """Per doc tile: split-bf16 matmul on the MXU (f32 accumulation) +
    running top-kb selection in VMEM — the tiered arm of _scan_topk_kernel
    (which runs 6-pass f32 HIGHEST for bit-parity; this arm trades that
    for ~3x fewer MXU passes and rescores survivors outside)."""
    j = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc_v[:] = jnp.full_like(acc_v, -jnp.inf)
        acc_i[:] = jnp.zeros_like(acc_i)
        cnt[:] = jnp.zeros_like(cnt)

    dn = (((1,), (0,)), ((), ()))
    dots = jax.lax.dot_general(
        q_ref[:], mh_ref[:], dn, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        q_ref[:], ml_ref[:], dn, preferred_element_type=jnp.float32
    )
    scores = _apply_transform(dots, transform, auxd_ref[0, :], auxq_ref[:])
    ids = j * tile_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    ok = live_ref[0, :] > 0
    scores = jnp.where(ok[None, :], scores, -jnp.inf)
    if count_positive:
        # sign survives the split-bf16 rounding (BM25: every product is
        # >= 0), so the tiered counts equal the exact counts
        scores = jnp.where(scores > 0, scores, -jnp.inf)
        cnt[:] += (scores > 0).astype(jnp.float32)
    else:
        cnt[:] += jnp.broadcast_to(ok[None, :], scores.shape).astype(
            jnp.float32)
    new_v, new_i = _merge_topk(scores, ids, acc_v[:], acc_i[:], kb)
    acc_v[:] = new_v
    acc_i[:] = new_i

    @pl.when(j == nn - 1)
    def _():
        ov_ref[:] = acc_v[:]
        oi_ref[:] = acc_i[:]
        ot_ref[:] = jnp.sum(cnt[:], axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("kb", "transform", "count_positive", "interpret",
                     "tiles"),
)
def _tiered_candidates_pallas(
    qh, mat_hi, mat_lo, live, aux_doc, aux_q,
    *, kb, transform, count_positive, interpret, tiles,
):
    B, D = qh.shape
    N = mat_hi.shape[1]
    tile_b, tile_n = tiles
    qp = _pad_to(qh, tile_b, 0, 0)
    mhp = _pad_to(mat_hi, tile_n, 1, 0)
    mlp = _pad_to(mat_lo, tile_n, 1, 0)
    livep = _pad_to(live.astype(jnp.float32)[None, :], tile_n, 1, 0.0)
    auxdp = _pad_to(aux_doc[None, :], tile_n, 1, 0.0)
    auxqp = _pad_to(aux_q[:, None], tile_b, 0, 0.0)
    Bp, Np = qp.shape[0], mhp.shape[1]
    nb, nn = Bp // tile_b, Np // tile_n
    kernel = functools.partial(
        _tiered_scan_kernel,
        kb=kb, tile_n=tile_n, transform=transform,
        count_positive=count_positive,
    )
    out_v, out_i, out_t = pl.pallas_call(
        kernel,
        grid=(nb, nn),
        in_specs=[
            pl.BlockSpec((tile_b, D), lambda i, j: (i, _I0)),
            pl.BlockSpec((D, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((D, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((1, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((1, tile_n), lambda i, j: (_I0, j)),
            pl.BlockSpec((tile_b, 1), lambda i, j: (i, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, kb), lambda i, j: (i, _I0)),
            pl.BlockSpec((tile_b, kb), lambda i, j: (i, _I0)),
            pl.BlockSpec((tile_b, 1), lambda i, j: (i, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, kb), jnp.float32),
            jax.ShapeDtypeStruct((Bp, kb), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, kb), jnp.float32),
            pltpu.VMEM((tile_b, kb), jnp.int32),
            pltpu.VMEM((tile_b, tile_n), jnp.float32),
        ],
        interpret=interpret,
    )(qp, mhp, mlp, livep, auxdp, auxqp)
    return out_v[:B], out_i[:B], out_t[:B, 0]


@functools.partial(
    jax.jit, static_argnames=("kb", "transform", "count_positive")
)
def _tiered_candidates_xla(
    qh, mat_hi, mat_lo, live, aux_doc, aux_q,
    *, kb, transform, count_positive,
):
    """XLA arm with the same selection semantics (non-TPU fast path; the
    kernel arm is bit-comparable up to f32 accumulation order)."""
    dots = (
        jnp.matmul(qh, mat_hi, preferred_element_type=jnp.float32)
        + jnp.matmul(qh, mat_lo, preferred_element_type=jnp.float32)
    )
    auxq = aux_q[:, None] if aux_q.ndim == 1 else aux_q
    scores = _apply_transform(dots, transform, aux_doc, auxq)
    scores = jnp.where(live[None, :] > 0, scores, -jnp.inf)
    if count_positive:
        scores = jnp.where(scores > 0, scores, -jnp.inf)
        totals = jnp.sum(scores > 0, axis=1, dtype=jnp.int32)
    else:
        totals = jnp.broadcast_to(
            jnp.sum(live > 0, dtype=jnp.int32), (scores.shape[0],)
        )
    sel_v, sel_i = jax.lax.top_k(scores, kb)
    return sel_v, sel_i.astype(jnp.int32), totals


def tiered_candidates(
    q: jax.Array,  # [B, D] f32 query rows (weights / query vectors)
    mat_hi: jax.Array,  # [D, N] bf16 hi tier (split_bf16)
    mat_lo: jax.Array,  # [D, N] bf16 lo tier
    live: jax.Array,  # [N] mask
    kb: int,
    *,
    transform: str = "identity",
    aux_doc: jax.Array | None = None,
    aux_q: jax.Array | None = None,
    count_positive: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Tiered selection pass -> (sel_v [B, kb], sel_i [B, kb], totals [B]).

    sel_v are SELECTION scores (split-bf16, within ~EPS_TIERED of f32);
    callers must f32-rescore the sel_i candidates and apply the margin
    safety test (see ops/vector.knn_topk / ops/batched dense tiered path)
    before treating the ranking as exact. totals are exact (live counts,
    or sign-exact positive counts — see the kernel comment)."""
    B, N = q.shape[0], mat_hi.shape[1]
    kb = max(1, min(kb, N))
    if aux_doc is None:
        aux_doc = jnp.zeros((N,), jnp.float32)
    if aux_q is None:
        aux_q = jnp.zeros((B,), jnp.float32)
    qh = _mask_hi(q).astype(jnp.bfloat16)
    tiles = (
        _pick_tiles(B, q.shape[1], N, kb) if kb <= MAX_FUSED_K else None
    )
    if interpret is None:
        if not use_pallas(score_bytes=4 * B * N) or tiles is None:
            return _tiered_candidates_xla(
                qh, mat_hi, mat_lo, live, aux_doc, aux_q,
                kb=kb, transform=transform, count_positive=count_positive,
            )
        interpret = jax.default_backend() != "tpu"
    if tiles is None:
        return _tiered_candidates_xla(
            qh, mat_hi, mat_lo, live, aux_doc, aux_q,
            kb=kb, transform=transform, count_positive=count_positive,
        )
    return _tiered_candidates_pallas(
        qh, mat_hi, mat_lo, live, aux_doc, aux_q,
        kb=kb, transform=transform, count_positive=count_positive,
        interpret=bool(interpret), tiles=tiles,
    )


# ---------------------------------------------------------------------------
# impact-tier gather (BM25S): the sparse arm of the batched disjunction
# as a pure gather+dequant — block rows of quantized impact codes are
# fetched and scaled by one per-row weight; no tf/dl/avgdl math exists
# anywhere downstream of the index build. Two arms like ann/kernels.py:
# a Pallas kernel whose scalar-prefetched row ids drive the code-block
# DMA through BlockSpec index maps, and an XLA gather with identical
# semantics for non-TPU backends.
# ---------------------------------------------------------------------------

_IMPACT_G = 8  # gathered block rows per grid step (DMA granularity)


def _impact_gather_kernel(rows_ref, w_ref, *refs, g):
    """refs = g code blocks + g docid blocks + (out_scores, out_ids)."""
    os_ref, oi_ref = refs[-2], refs[-1]
    for i in range(g):
        c_ref = refs[i]
        d_ref = refs[g + i]
        # i32 hop: Mosaic has no direct u16/i8 -> f32 convert
        codes = c_ref[...].astype(jnp.int32).astype(jnp.float32)
        os_ref[i:i + 1, :] = w_ref[:, i:i + 1] * codes
        oi_ref[i:i + 1, :] = d_ref[...]


@functools.partial(jax.jit, static_argnames=("g", "interpret"))
def _impact_gather_pallas(codes, docids, rows, row_w, *, g, interpret):
    Q, R = rows.shape  # R is a multiple of g (caller pads with row 0)
    block = codes.shape[1]
    kernel = functools.partial(_impact_gather_kernel, g=g)
    # Mosaic wants a block's last two dims divisible by (8, 128) or equal
    # to the array's: one gathered [BLOCK] row and one [g] weight group
    # become whole trailing (1, BLOCK) / (1, g) planes of a reshaped
    # array, the leading (gather) dims squeezed out of the kernel's view
    codes3 = codes.reshape(-1, 1, block)
    docids3 = docids.reshape(-1, 1, block)
    w4 = row_w.reshape(Q, R // g, 1, g)

    def _row_spec(gi):
        return pl.BlockSpec(
            (None, 1, block),
            lambda q, j, r, _gi=gi: (r[q, j * g + _gi], _I0, _I0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, R // g),
        in_specs=(
            [pl.BlockSpec((None, None, 1, g),
                          lambda q, j, r: (q, j, _I0, _I0))]
            + [_row_spec(gi) for gi in range(g)]
            + [_row_spec(gi) for gi in range(g)]
        ),
        out_specs=[
            pl.BlockSpec((None, g, block), lambda q, j, r: (q, j, _I0)),
            pl.BlockSpec((None, g, block), lambda q, j, r: (q, j, _I0)),
        ],
    )
    out_s, out_i = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, R, block), jnp.float32),
            jax.ShapeDtypeStruct((Q, R, block), jnp.int32),
        ],
        interpret=interpret,
    )(rows, w4, *([codes3] * g), *([docids3] * g))
    return out_i.reshape(Q, R * block), out_s.reshape(Q, R * block)


@jax.jit
def _impact_gather_xla(codes, docids, rows, row_w):
    """XLA arm: identical semantics (row gathers are the fast gather
    class on TPU too — see ops/scoring.term_score_blocks)."""
    Q, R = rows.shape
    block = codes.shape[1]
    scores = row_w[:, :, None] * codes[rows].astype(jnp.float32)
    return (docids[rows].reshape(Q, R * block),
            scores.reshape(Q, R * block))


def impact_gather(
    codes: jax.Array,   # [num_blocks, BLOCK] u16|i8 impact codes
    docids: jax.Array,  # [num_blocks, BLOCK] i32 (pad: num_docs)
    rows: jax.Array,    # [Q, R] i32 flat block rows (0-padded, row 0 dead)
    row_w: jax.Array,   # [Q, R] f32 dequant weight (boost·idf·ubf/qmax)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """-> (ids [Q, R·BLOCK] i32, scores [Q, R·BLOCK] f32): the flattened
    per-lane candidates of a batch of impact-tier disjunctions. Padding
    rows (row 0, weight 0) emit docid == num_docs at score 0 — dead lanes
    for every downstream consumer."""
    Q, R = rows.shape
    block = codes.shape[1]
    g = min(_IMPACT_G, max(R, 1))
    pad = (-R) % g
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
        row_w = jnp.pad(row_w, ((0, 0), (0, pad)))
    if interpret is None:
        if not use_pallas(score_bytes=Q * (R + pad) * block * 8):
            return _impact_gather_xla(codes, docids, rows, row_w)
        interpret = jax.default_backend() != "tpu"
    return _impact_gather_pallas(
        codes, docids, rows, row_w, g=g, interpret=bool(interpret))


def use_pallas(score_bytes: int | None = None) -> bool:
    flag = os.environ.get("ES_TPU_PALLAS", "auto")
    if flag == "0":
        return False
    if flag in ("1", "force"):
        return True
    if jax.default_backend() != "tpu":
        return False
    if score_bytes is None:
        return True
    return score_bytes >= PALLAS_SCORE_BYTES_THRESHOLD


def scan_topk(
    q: jax.Array,  # [B, D] f32
    mat_t: jax.Array,  # [D, N] f32
    live: jax.Array,  # [N] bool/float mask
    k: int,
    *,
    transform: str = "identity",
    aux_doc: jax.Array | None = None,  # [N] per-doc transform input
    aux_q: jax.Array | None = None,  # [B] per-query transform input
    count_positive: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (top_v [B, k] f32, top_i [B, k] i32, totals [B] i32).

    totals counts `score > 0 & live` when count_positive (BM25 match
    semantics: all term weights > 0) else counts live lanes (kNN candidate
    counts).
    """
    B, D = q.shape
    N = mat_t.shape[1]
    k = max(1, min(k, N))
    if aux_doc is None:
        aux_doc = jnp.zeros((N,), jnp.float32)
    if aux_q is None:
        aux_q = jnp.zeros((B,), jnp.float32)
    tiles = _pick_tiles(B, D, N, k) if k <= MAX_FUSED_K else None
    if interpret is None:
        if not use_pallas(score_bytes=4 * B * N) or tiles is None:
            return scan_topk_xla(
                q, mat_t, live, aux_doc, aux_q,
                k=k, transform=transform, count_positive=count_positive,
            )
        interpret = jax.default_backend() != "tpu"
    if tiles is None:  # explicit interpret request but shape won't fit
        return scan_topk_xla(
            q, mat_t, live, aux_doc, aux_q,
            k=k, transform=transform, count_positive=count_positive,
        )
    return _scan_topk_pallas(
        q, mat_t, live, aux_doc, aux_q,
        k=k, transform=transform, count_positive=count_positive,
        interpret=bool(interpret), tiles=tiles,
    )
