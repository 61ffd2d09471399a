"""Device-side BM25 scoring ops over blocked-CSR postings.

TPU-first inversion of the reference's hot loop (reference behavior:
search/internal/ContextIndexSearcher.java:411-431 — per-segment
`BulkScorer.score` pulling doc-at-a-time postings through BM25 and a top-k
heap). Here the same math runs data-parallel:

    gather postings blocks -> vectorized BM25 over [B, 128] lanes
    -> scatter-add into a dense per-doc score accumulator -> lax.top_k

The dense accumulator has N+1 slots; slot N is a dead slot that absorbs all
padding lanes (padding docids == N), so no masking branches exist anywhere in
the kernel. Scoring is exact (no early termination); block-max pruning is a
later optimization that *filters the block list* host/device-side rather than
branching inside the kernel (SURVEY.md hard part #2).

BM25 formula parity (Lucene 9 BM25Similarity, wired as ES's default at
server/.../index/similarity/SimilarityService.java:43-58):

    idf(t)  = ln(1 + (docCount - df + 0.5) / (df + 0.5))
    tfn     = tf / (tf + k1 * (1 - b + b * dl / avgdl))   [norms present]
    tfn     = tf / (tf + k1)                              [norms omitted]
    score   = boost * idf * tfn

with dl the 1-byte-quantized doc length (index/smallfloat.py) and avgdl the
exact sumTotalTermFreq/docCount. k1=1.2, b=0.75 defaults.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DEAD_SLOT_PAD = 1  # dense accumulators are sized N + 1


def bm25_idf(doc_count: int, df: int) -> float:
    """Host-side idf — THE single BM25 idf implementation: query planning
    (query/nodes, ops/batched) and the impact-tier weight derivation all
    source this function, so dfs-stats overrides flow identically into
    every scoring path. doc_count = docs with >=1 term in the field."""
    if df <= 0:
        return 0.0
    return math.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))


def impact_enabled() -> bool:
    """ES_TPU_IMPACT routing for the eager impact-scored sparse tier
    (BM25S): 'auto' (default) engages on TPU backends only — the CPU
    tier-1 suite keeps exercising the exact BM25 reference paths —
    '1'/'force' engages everywhere (tests, bench A/B arms), '0' disables.
    The tier is selection-complete but quantized (see index/pack.py error
    model); explain / scripted similarity / non-default k1,b escalate to
    the exact path regardless of this flag."""
    import os

    import jax as _jax

    mode = os.environ.get("ES_TPU_IMPACT", "auto")
    if mode == "0":
        return False
    if mode in ("1", "force"):
        return True
    return _jax.default_backend() == "tpu"


def term_score_blocks(
    post_docids: jax.Array,  # [num_blocks, BLOCK] int32
    post_tfs: jax.Array,  # [num_blocks, BLOCK] float32
    post_dls: jax.Array,  # [num_blocks, BLOCK] float32 (dl per posting)
    rows: jax.Array,  # [B] int32 block rows for this term (0-padded)
    weight: jax.Array,  # scalar f32: boost * idf
    avgdl: jax.Array | float,  # scalar
    num_docs: int,
    k1: float = 1.2,
    b: float = 0.75,
    has_norms: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Score one term's postings blocks.

    The doc length rides IN the postings block (`post_dls`), so BM25 is pure
    FMA over gathered rows — no random-access norms gather, which profiling
    shows is ~100x slower than row gathers on TPU.

    Returns (scores[N+1] f32, match[N+1] bool). Padding lanes (docid == N,
    tf == 0) score exactly 0 and scatter into the dead slot.
    """
    docids = post_docids[rows]  # [B, 128]
    tfs = post_tfs[rows]  # [B, 128]
    dls = post_dls[rows] if has_norms else None
    return score_posting_arrays(
        docids, tfs, dls, weight, avgdl, num_docs,
        k1=k1, b=b, has_norms=has_norms,
    )


def score_posting_arrays(
    docids: jax.Array,  # [B, BLOCK] int32 (pad: num_docs)
    tfs: jax.Array,  # [B, BLOCK] float32 (pad: 0)
    dls: jax.Array | None,  # [B, BLOCK] float32 (None when has_norms=False)
    weight: jax.Array,
    avgdl: jax.Array | float,
    num_docs: int,
    k1: float = 1.2,
    b: float = 0.75,
    has_norms: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Score explicit posting arrays (the tail of term_score_blocks)."""
    if has_norms:
        denom = tfs + k1 * (1.0 - b + b * dls / avgdl)
    else:
        denom = tfs + k1
    # tf==0 padding -> 0/k1' = 0
    block_scores = weight * tfs / denom
    flat_ids = docids.reshape(-1)
    scores = jnp.zeros(num_docs + DEAD_SLOT_PAD, jnp.float32).at[flat_ids].add(
        block_scores.reshape(-1), mode="drop"
    )
    match = jnp.zeros(num_docs + DEAD_SLOT_PAD, bool).at[flat_ids].set(
        (tfs > 0).reshape(-1), mode="drop"
    )
    return scores, match


def match_scores(
    dev: dict,  # one shard's pack arrays (post_docids, ..., dense_tfn)
    params: tuple,  # query/nodes.match_params: the padded lists and scalars
    num_docs: int,
    k1: float,
    b: float,
    has_norms: bool,
    impact: bool,
) -> tuple[jax.Array, jax.Array]:
    """Score a `match` (a term, or a bool of terms on one field) from its
    two padded lists; every program of the solo path's family is this one
    computation at another (dense tier, rows tier).

    Sparse terms: their posting-block rows lie in ONE flat list with a
    weight a row (padding: the reserved row 0, whose lanes carry docid N and
    tf 0), gathered once and scatter-added once. A document is in a term's
    blocks once, so the scatter adds at most one value a term into a slot,
    each the f32 product the per-term path computed (`impact`: wscale x
    code, a pure gather and sum; else BM25 over tf and the dl that rides in
    the block). Dense terms: their precomputed tfn rows, weighted and added
    one after another in list order (padding: weight 0, no match). A
    document's match count is the number of terms that hold it; it matches
    where the count reaches the plan's threshold (1 for a disjunction, the
    term count for a conjunction, else minimum_should_match).

    Returns (scores[N+1] f32, match[N+1] bool), dead slot N as
    term_score_blocks has it."""
    rows, rw, rs, dr, dw, dok, thr, boost, avgdl = params
    n1 = num_docs + DEAD_SLOT_PAD
    docids = dev["post_docids"][rows].reshape(-1)  # [R * 128]
    if impact:
        codes = dev["impact_codes"][rows]
        vals = rs[:, None] * codes.astype(jnp.float32)
        hit = codes > 0
    else:
        tfs = dev["post_tfs"][rows]
        if has_norms:
            denom = tfs + k1 * (1.0 - b + b * dev["post_dls"][rows] / avgdl)
        else:
            denom = tfs + k1
        # tf==0 padding -> 0/k1' = 0
        vals = rw[:, None] * tfs / denom
        hit = tfs > 0
    scores = jnp.zeros(n1, jnp.float32).at[docids].add(
        vals.reshape(-1), mode="drop")
    count = jnp.zeros(n1, jnp.int32).at[docids].add(
        hit.reshape(-1).astype(jnp.int32), mode="drop")
    if dr.shape[0]:
        s, c = scores[:num_docs], count[:num_docs]
        for i in range(dr.shape[0]):
            tfn = dev["dense_tfn"][dr[i]]  # [N]; tfn > 0 iff tf > 0
            s = s + dw[i] * tfn
            c = c + ((tfn > 0) & (dok[i] != 0)).astype(jnp.int32)
        scores = scores.at[:num_docs].set(s)
        count = count.at[:num_docs].set(c)
    match = count >= thr
    return jnp.where(match, boost * scores, 0.0), match


# lanes a block of the two-level selection: one vreg row, so the [G, W] view
# of the score row is a layout-free reshape
SELECT_BLOCK = 128


def top_k_with_total(
    scores: jax.Array,  # [N+1] f32
    match: jax.Array,  # [N+1] bool
    live: jax.Array,  # [N] bool
    k: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Global top-k by (score desc, docid asc) + exact total hit count.

    `lax.top_k` breaks score ties by lowest index, and index == docid, which
    reproduces Lucene's (score, docid) tie-break order exactly
    (reference behavior: TopScoreDocCollector via
    search/query/QueryPhaseCollectorManager.java:416).

    Selected in two levels, every op plain XLA (legal under vmap and inside
    parallel/spmd.manual_shard_region alike): the masked row, padded with
    -inf, is viewed as [G, W] blocks of W consecutive docids; level 1 takes
    each block's maximum and the min(k, G) blocks first in (maximum desc,
    block asc); level 2 gathers those blocks in ASCENDING block order, so
    the flat candidate order is docid order and `lax.top_k`'s lowest-index
    tie-break is still docid asc. One pass over the row and two selections
    over G and k*W values replace a selection over all N.

    Why not `lax.top_k` over the whole row: the TPU compiler turns it into
    its fast `TopK` call only where the operand is rank 2, which a rank-1
    row is under one `vmap` and nowhere else. Inside a manual region (a
    shard a chip) or unbatched (query/executor) it becomes a stable sort of
    all N (score, index) pairs: at N = 294,912 on a v5e 342 us against
    `TopK`'s 24, and ~20 s to compile, a program (scripts/topk_micro.py;
    PERF.md section 6, PR 30). Here every selection is small, so either
    lowering is cheap: 33 us at rank 2, 18 at rank 1.

    Exact, not approximate: let document e of block g be in the true top-k
    by (score desc, docid asc) with g not chosen. Then k chosen blocks g'
    precede g: max(g') > max(g), or max(g') == max(g) and g' < g. Each holds
    a document scoring max(g') >= score(e) and, on equality, of a smaller
    docid (blocks are contiguous docid ranges, g' < g). So k documents beat
    e: contradiction. (With G <= k every block is chosen.) The same floats
    are compared, never recomputed, so values, ids (where the value is
    finite) and total are bit-identical to `lax.top_k` over the masked row.
    A -inf entry's id is some masked lane below N: the flat top-k fills from
    the lowest candidate positions, and the padding lanes are the highest.
    """
    n = live.shape[0]
    ok = match[:n] & live
    total = jnp.sum(ok, dtype=jnp.int32)
    masked = jnp.where(ok, scores[:n], -jnp.inf)
    top_scores, top_ids = top_k_of_row(masked, k)
    return top_scores, top_ids, total


def top_k_of_row(masked: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The two-level selection of `top_k_with_total` over one masked row
    [n] (-inf where a lane is out): (values [k], lanes [k]), bit-identical
    to `lax.top_k(masked, k)` where the value is finite, ties to the lowest
    lane. `vmap` it over a batch's rows: every selection inside is small,
    so it compiles and runs fast at any rank (a `lax.top_k` over the long
    axis of a rank-3 operand, a batch under a shard axis, is sorted whole)."""
    n = masked.shape[0]
    w = SELECT_BLOCK
    g = -(-n // w)
    blocks = jnp.pad(masked, (0, g * w - n),
                     constant_values=-jnp.inf).reshape(g, w)
    _, chosen = jax.lax.top_k(blocks.max(axis=1), min(k, g))
    chosen = jnp.sort(chosen)
    top_scores, pos = jax.lax.top_k(blocks[chosen].reshape(-1), k)
    w32 = jnp.int32(w)
    top_ids = chosen[jax.lax.div(pos, w32)] * w32 + jax.lax.rem(pos, w32)
    return top_scores, top_ids
