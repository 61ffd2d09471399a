"""Pallas fused scan+topk kernel vs the XLA reference (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.ops.kernels import scan_topk, scan_topk_xla


def _run_both(q, mat_t, live, k, **kw):
    got = scan_topk(
        jnp.asarray(q),
        jnp.asarray(mat_t),
        jnp.asarray(live),
        k,
        interpret=True,
        **kw,
    )
    aux_doc = kw.get("aux_doc")
    aux_q = kw.get("aux_q")
    B = q.shape[0]
    N = mat_t.shape[1]
    want = scan_topk_xla(
        jnp.asarray(q),
        jnp.asarray(mat_t),
        jnp.asarray(live),
        jnp.zeros(N, jnp.float32) if aux_doc is None else jnp.asarray(aux_doc),
        jnp.zeros(B, jnp.float32) if aux_q is None else jnp.asarray(aux_q),
        k=k,
        transform=kw.get("transform", "identity"),
        count_positive=kw.get("count_positive", True),
    )
    return [np.asarray(x) for x in got], [np.asarray(x) for x in want]


def _check(got, want):
    gv, gi, gt = got
    wv, wi, wt = want
    np.testing.assert_allclose(gv, wv, rtol=1e-5, atol=1e-6)
    # ids must agree wherever the score is finite (dead lanes have arbitrary id)
    finite = np.isfinite(wv)
    np.testing.assert_array_equal(gi[finite], wi[finite])
    np.testing.assert_array_equal(gt, wt)


def test_matmul_identity_basic(rng):
    B, D, N, k = 5, 16, 300, 10
    q = rng.normal(size=(B, D)).astype(np.float32)
    mat = np.abs(rng.normal(size=(D, N))).astype(np.float32)
    live = np.ones(N, bool)
    live[rng.choice(N, 40, replace=False)] = False
    _check(*_run_both(q, mat, live, k))


def test_one_row_operand(rng):
    # D = 1: every query scales the one row of scores
    B, N, k = 9, 700, 7
    q = rng.uniform(0.5, 2.0, size=(B, 1)).astype(np.float32)
    scores = rng.normal(size=(1, N)).astype(np.float32)
    live = rng.random(N) > 0.3
    _check(*_run_both(q, scores, live, k))


def test_tie_break_lowest_docid():
    # equal scores everywhere: top-k must be docids 0..k-1 in order
    scores = np.ones((1, 257), np.float32)
    live = np.ones(257, bool)
    (gv, gi, gt), _ = _run_both(np.ones((2, 1), np.float32), scores, live, 5)
    np.testing.assert_array_equal(gi, np.tile(np.arange(5), (2, 1)))
    np.testing.assert_array_equal(gt, [257, 257])


def test_k_larger_than_matches(rng):
    scores = np.full((1, 40), -1.0, np.float32)
    scores[:, 3] = 2.0
    live = np.zeros(40, bool)
    live[:8] = True
    (gv, gi, gt), (wv, wi, wt) = _run_both(
        np.ones((3, 1), np.float32), scores, live, 6, count_positive=True)
    _check((gv, gi, gt), (wv, wi, wt))
    assert gt.tolist() == [1, 1, 1]  # only docid 3 scores > 0


@pytest.mark.parametrize("sim", ["cosine", "dot_product", "l2_norm", "max_inner_product"])
def test_vector_transforms(rng, sim):
    B, D, N, k = 4, 8, 130, 5
    q = rng.normal(size=(B, D)).astype(np.float32)
    vecs = rng.normal(size=(N, D)).astype(np.float32)
    live = np.ones(N, bool)
    sq = (vecs * vecs).sum(-1)
    if sim == "cosine":
        aux_doc = 1.0 / np.sqrt(np.maximum(sq, 1e-30))
        aux_q = 1.0 / np.sqrt(np.maximum((q * q).sum(-1), 1e-30))
    elif sim == "l2_norm":
        aux_doc = sq
        aux_q = (q * q).sum(-1)
    else:
        aux_doc = np.zeros(N)
        aux_q = np.zeros(B)
    got, want = _run_both(
        q, vecs.T.copy(), live, k,
        transform=sim,
        aux_doc=aux_doc.astype(np.float32),
        aux_q=aux_q.astype(np.float32),
        count_positive=False,
    )
    _check(got, want)
    # cross-check against the reference scoring op
    from elasticsearch_tpu.ops.vector import knn_scores

    full = np.stack(
        [np.asarray(knn_scores(jnp.asarray(vecs), jnp.asarray(sq), jnp.asarray(q[i]), sim))
         for i in range(B)]
    )
    order = np.argsort(-full, axis=1, kind="stable")[:, :k]
    np.testing.assert_allclose(got[0], np.take_along_axis(full, order, 1), rtol=1e-5)


def test_unaligned_shapes(rng):
    # B, N deliberately not multiples of any tile size
    B, D, N, k = 11, 7, 1037, 13
    q = rng.normal(size=(B, D)).astype(np.float32)
    mat = rng.normal(size=(D, N)).astype(np.float32)
    live = rng.random(N) > 0.5
    _check(*_run_both(q, mat, live, k, count_positive=False))


def _select_oracle(scores, match, live, k):
    """NumPy (score desc, docid asc) over the masked row + exact total."""
    n = live.shape[0]
    ok = match[:n] & live
    masked = np.where(ok, scores[:n], -np.inf).astype(np.float32)
    order = np.lexsort((np.arange(n), -masked))[:k]
    return masked[order], order, int(ok.sum())


def _check_select(scores, match, live, k):
    from elasticsearch_tpu.ops.scoring import top_k_with_total

    n = live.shape[0]
    gv, gi, gt = [np.asarray(x) for x in top_k_with_total(
        jnp.asarray(scores), jnp.asarray(match), jnp.asarray(live), k)]
    wv, wi, wt = _select_oracle(scores, match, live, k)
    np.testing.assert_array_equal(gv, wv)  # the same floats, not close ones
    finite = np.isfinite(wv)  # a dead entry's id is any masked lane
    np.testing.assert_array_equal(gi[finite], wi[finite])
    assert gi.dtype == np.int32 and (gi >= 0).all() and (gi < n).all()
    assert gt == wt


def _row(n, fill=0.0):
    return (np.full(n + 1, fill, np.float32), np.ones(n + 1, bool),
            np.ones(n, bool))


def _case_ties_straddle_blocks():
    # the k-th score is shared by documents of five blocks, the last
    # lane of one and the first of the next among them
    scores, match, live = _row(12_800)
    scores[[127, 128, 5_000, 9_999, 12_799]] = 1.0
    scores[[300, 7_000]] = 2.0
    return scores, match, live, 4


def _case_late_block_high_max_low_tie():
    # block 90 has the highest maximum AND a document tied at the k-th
    # score with documents of earlier blocks: taken in (maximum desc)
    # order its tie would come first; in docid order it comes last
    scores, match, live = _row(12_800)
    scores[90 * 128 + 5] = 9.0
    scores[90 * 128 + 1] = 1.0
    scores[[3 * 128 + 7, 40 * 128]] = 1.0
    scores[[10 * 128, 60 * 128 + 127]] = 5.0
    return scores, match, live, 5


def _case_fewer_than_k_matches():
    scores, match, live = _row(12_800, 1.0)
    match[:] = False
    match[[17, 4_000, 12_799]] = True
    live[4_000] = False
    return scores, match, live, 10


def _case_no_match():
    scores, match, live = _row(12_800, 1.0)
    match[:] = False
    return scores, match, live, 10


def _case_n_not_a_multiple_of_the_block():
    # the best document sits in the short last block, beside the padding
    rng = np.random.default_rng(5)
    scores, match, live = _row(12_801 + 38)
    scores[:] = np.round(rng.normal(size=scores.shape), 1)
    scores[12_801 + 37] = 50.0
    live[rng.choice(live.shape[0] - 1, 900, replace=False)] = False
    return scores, match, live, 10


def _case_k_is_one():
    scores, match, live = _row(1_283)
    scores[[700, 1_282]] = 3.0
    return scores, match, live, 1


def _case_cells_size():
    rng = np.random.default_rng(6)
    n = 294_912
    scores = np.round(np.abs(rng.normal(size=n + 1)), 2).astype(np.float32)
    return scores, rng.random(n + 1) > 0.4, rng.random(n) > 0.01, 10


def _case_deep_page_of_a_large_row():
    rng = np.random.default_rng(7)
    n = 140_000
    scores = np.round(rng.normal(size=n + 1), 1).astype(np.float32)
    return scores, rng.random(n + 1) > 0.2, rng.random(n) > 0.3, 100


def _case_small_pack():
    rng = np.random.default_rng(8)
    n = 700
    scores = np.round(rng.normal(size=n + 1), 2).astype(np.float32)
    return scores, rng.random(n + 1) > 0.2, rng.random(n) > 0.3, 9


def _case_k_blocks_cover_the_row():
    # G = 79 blocks <= k: every block is a candidate
    scores, match, live = _case_n_not_a_multiple_of_the_block()[:3]
    return scores[:10_001], match[:10_001], live[:10_000], 100


@pytest.mark.parametrize("case", [
    _case_ties_straddle_blocks,
    _case_late_block_high_max_low_tie,
    _case_fewer_than_k_matches,
    _case_no_match,
    _case_n_not_a_multiple_of_the_block,
    _case_k_is_one,
    _case_cells_size,
    _case_deep_page_of_a_large_row,
    _case_small_pack,
    _case_k_blocks_cover_the_row,
], ids=lambda c: c.__name__.removeprefix("_case_"))
def test_top_k_with_total_against_numpy(case, monkeypatch):
    """(score desc, docid asc) + exact total against NumPy, on the CPU: one
    path for every (n, k), and the retired switch steers nothing."""
    monkeypatch.setenv("ES_TPU_FUSED_TOPK", "0")
    _check_select(*case())


def test_top_k_with_total_is_lax_top_k_over_the_masked_row(rng):
    """Two levels against `lax.top_k` over the whole masked row, on a row of
    many ties: bit-identical values, ids and total."""
    import jax

    from elasticsearch_tpu.ops.scoring import top_k_with_total

    n, k = 20_000, 9
    scores = jnp.asarray(
        np.round(rng.normal(size=n + 1), 1).astype(np.float32))  # many ties
    match = jnp.asarray(rng.random(n + 1) > 0.2)
    live = jnp.asarray(rng.random(n) > 0.3)
    ok = match[:n] & live
    wv, wi = jax.lax.top_k(jnp.where(ok, scores[:n], -jnp.inf), k)
    gv, gi, gt = top_k_with_total(scores, match, live, k)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    assert int(gt) == int(ok.sum())


def test_tiered_candidates_matches_xla_arm(rng):
    """Pallas (interpret) and XLA arms of the tiered selection agree."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.kernels import (
        split_bf16, tiered_candidates,
    )

    B, D, N, kb = 6, 32, 900, 16
    q = rng.normal(size=(B, D)).astype(np.float32)
    mat = np.abs(rng.normal(size=(D, N))).astype(np.float32)
    hi, lo = split_bf16(jnp.asarray(mat))
    live = rng.random(N) > 0.25
    got = tiered_candidates(
        jnp.asarray(q), hi, lo, jnp.asarray(live), kb,
        count_positive=True, interpret=True,
    )
    want = tiered_candidates(
        jnp.asarray(q), hi, lo, jnp.asarray(live), kb,
        count_positive=True, interpret=None,  # CPU -> XLA arm
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-7)
