"""Stage-level C1 profile on real TPU at bench shapes (round-5 kernel work).

Times each component of the fused pipeline independently, amortized over
queued executions (a single-call timing carries the fixed dispatch+fetch
overhead). Prints one JSON line.

Stages:
  dense3   stacked split-bf16 dense matmul (the shipped 3-logical-pass)
  dense1   single-pass bf16 matmul (candidate cheaper selection tier)
  gather   CSR row gather + partial scores (phase A)
  sortkey  window key build + 2-op lax.sort + searchsorted
  kernel   fused_tile_candidates at the shipped geometry
  merge    f32 top_k margin + rank_topk + canonical rescore
"""

from __future__ import annotations

import functools
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")
import bench  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from elasticsearch_tpu.ops import fused as F  # noqa: E402
from elasticsearch_tpu.ops.batched import BatchTermSearcher  # noqa: E402
from elasticsearch_tpu.query.executor import ShardSearcher  # noqa: E402

REPS = 10


def _sync(out):
    """Device barrier: fetch ONE element of one output leaf — a host
    fetch of a post-queue scalar cannot return before the work is done."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf.ravel()[:1])


def timed(fn, *args, reps=REPS):
    """Amortized wall time of `reps` queued executions, with the fixed
    dispatch+fetch round trip differenced out via a 1-rep baseline."""
    _sync(fn(*args))  # warm

    def run(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        _sync(out)
        return time.perf_counter() - t0

    t1 = min(run(1) for _ in range(3))
    tn = run(reps + 1)
    return (tn - t1) / reps


def main():
    from elasticsearch_tpu.utils.jax_env import enable_compile_cache

    enable_compile_cache()
    rng = np.random.default_rng(42)
    print("[profile] building 1M corpus + pack...", file=sys.stderr)
    lens, tok = bench.build_corpus(rng)
    pack, m = bench.build_pack(lens, tok)
    searcher = ShardSearcher(pack, mappings=m)
    bts = BatchTermSearcher(searcher)
    fts = F.FusedTermSearcher(bts)
    queries = bench.sample_queries(rng, lens, tok, F.QC)
    k = 10

    plan = F.plan_fused(pack, "body", queries, k)
    fa = fts._arrays()
    n = pack.num_docs
    tile_n = fts._tile_n
    qsub = fts._qsub
    n_pad = ((n + tile_n - 1) // tile_n) * tile_n
    njc = n_pad // tile_n
    t = F.tile_t_for(njc)
    R = plan.rows.shape[0]
    V = pack.dense_tfn.shape[0]
    res = {"R": R, "V": V, "njc": njc, "tile_n": tile_n, "qsub": qsub,
           "t": t, "nreal": plan.nreal}
    print(f"[profile] shapes {res}", file=sys.stderr)

    # rebuild the dense W matrix the way the device path does (plan.W is
    # no longer materialized host-side)
    Wnp = np.zeros((F.QC, V), np.float32)
    for qi in range(F.QC):
        for ti in range(plan.dense_rows.shape[1]):
            Wnp[qi, plan.dense_rows[qi, ti]] += plan.dense_w[qi, ti]
    W = jnp.asarray(Wnp)
    rows = jnp.asarray(plan.rows)
    row_q = jnp.asarray(plan.row_q)
    row_w = jnp.asarray(plan.row_w)

    # ---- dense tiers (tiers passed as ARGS: a closure capture embeds the
    # 5.4GB device arrays as compile-time constants and kills the run) ----
    @jax.jit
    def dense3(W, tier):
        # 3-pass reference (round-4 default): 2-pass stack + Wl@T16
        Whf = F._mask_hi(W)
        Wh = Whf.astype(jnp.bfloat16)
        Wl = (W - Whf).astype(jnp.bfloat16)
        W2 = jnp.concatenate([Wh, Wh], axis=1)
        return (jnp.matmul(W2, tier, preferred_element_type=jnp.float32)
                + jnp.matmul(Wl, jax.lax.slice_in_dim(tier, 0, V, axis=0),
                             preferred_element_type=jnp.float32))

    @jax.jit
    def dense1(W, tier):
        Wh = F._mask_hi(W).astype(jnp.bfloat16)
        return jnp.matmul(Wh, tier, preferred_element_type=jnp.float32)

    @jax.jit
    def dense2(W, tier):
        # the SHIPPED selection tier: one matmul over the [2V, N] stack
        Wh = F._mask_hi(W).astype(jnp.bfloat16)
        W2 = jnp.concatenate([Wh, Wh], axis=1)
        return jnp.matmul(W2, tier, preferred_element_type=jnp.float32)

    tier_stack = fa["tier16_stack"]
    res["dense3_ms"] = round(timed(dense3, W, tier_stack) * 1e3, 2)
    print(f"[profile] dense3 {res['dense3_ms']}", file=sys.stderr)
    res["dense1_ms"] = round(
        timed(dense1, W, tier_stack[:V]) * 1e3, 2)
    print(f"[profile] dense1 {res['dense1_ms']}", file=sys.stderr)

    # ---- phase A gather + partials --------------------------------------
    avgdl = pack.avgdl("body")

    @jax.jit
    def gather(rows, row_w, pd, pt, pl):
        docids = pd[rows]
        tfs = pt[rows]
        dls = pl[rows]
        denom = tfs + 1.2 * (1.0 - 0.75 + 0.75 * dls / avgdl)
        parts = row_w[:, None] * tfs / denom
        return docids, parts

    ga = (fa["post_docids"], fa["post_tfs"], fa["post_dls"])
    res["gather_ms"] = round(timed(gather, rows, row_w, *ga) * 1e3, 2)
    print(f"[profile] gather {res['gather_ms']}", file=sys.stderr)
    docids, parts = gather(rows, row_w, *ga)

    # ---- sort + ptr ------------------------------------------------------
    nsub = F.QC // qsub
    qb, db, sb = F._key_bits(n_pad, qsub, nsub)
    nreal_q = 1 << max(plan.nreal - 1, 1).bit_length()
    mean_win = max(1, nreal_q * F.BLOCK // ((F.QC // qsub) * njc))
    bude = min(64 * 1024, max(2048, 1 << (2 * mean_win - 1).bit_length()))
    bud = bude // 128
    res["bud"] = bud
    njf = n_pad // F.FINE_N

    @jax.jit
    def sortkey(docids, parts, row_q):
        q2 = row_q[:, None]
        key = (((q2 >> qb) << sb) | (docids << qb) | (q2 & (qsub - 1)))
        key = jnp.where(docids >= n, jnp.int32(2**31 - 1), key)
        skey, sval = jax.lax.sort(
            (key.reshape(-1), parts.reshape(-1)), num_keys=1)
        bounds = ((jnp.arange(nsub, dtype=jnp.int32)[:, None] << sb)
                  | (jnp.arange(njf + 1, dtype=jnp.int32)[None, :]
                     * F.FINE_N << qb))
        ptr = jnp.searchsorted(skey, bounds.reshape(-1)).astype(jnp.int32)
        pad_n = 2 * bude + (-(skey.shape[0] + 2 * bude)) % bude
        sent = jnp.full((pad_n,), jnp.int32(2**31 - 1))
        keys2 = jnp.concatenate([skey, sent]).reshape(-1, 128)
        vals2 = jnp.concatenate(
            [jax.lax.bitcast_convert_type(sval, jnp.int32), sent]
        ).reshape(-1, 128)
        return keys2, vals2, ptr

    res["sortkey_ms"] = round(timed(sortkey, docids, parts, row_q) * 1e3, 2)
    print(f"[profile] sortkey {res['sortkey_ms']}", file=sys.stderr)
    keys2, vals2, ptr = jax.block_until_ready(sortkey(docids, parts, row_q))

    # sort-only ablation
    @jax.jit
    def sort_only(docids, parts, row_q):
        q2 = row_q[:, None]
        key = (((q2 >> qb) << sb) | (docids << qb) | (q2 & (qsub - 1)))
        key = jnp.where(docids >= n, jnp.int32(2**31 - 1), key)
        return jax.lax.sort((key.reshape(-1), parts.reshape(-1)), num_keys=1)

    res["sort_only_ms"] = round(
        timed(sort_only, docids, parts, row_q) * 1e3, 2)

    # ---- kernel ----------------------------------------------------------
    scores = dense2(W, tier_stack)
    kfn = jax.jit(functools.partial(
        F.fused_tile_candidates, t=t, bud=bud, tile_n=tile_n,
        qsub=qsub, interpret=False))
    scores = jax.block_until_ready(scores)
    res["kernel_ms"] = round(
        timed(kfn, scores, fa["live"], keys2, vals2, ptr) * 1e3, 2)
    print(f"[profile] kernel {res['kernel_ms']}", file=sys.stderr)
    cv, ci, totals, wlost = kfn(scores, fa["live"], keys2, vals2, ptr)

    # ---- merge + rescore -------------------------------------------------
    dense_rows = jnp.asarray(plan.dense_rows)
    dense_w = jnp.asarray(plan.dense_w)

    @jax.jit
    def merge(cv, ci, docids, parts, row_q, tier32, dense_rows, dense_w):
        kb_eff = min(F.KB, cv.shape[1])
        m_eff = min(kb_eff + 16, cv.shape[1])
        mv, sel = jax.lax.top_k(cv, m_eff)
        mi = jnp.take_along_axis(ci, sel, axis=1)
        kv, ki = F.rank_topk(mv, mi, kb_eff)
        cand_ok = kv > -jnp.inf
        resc = F.canonical_rescore(
            tier32, dense_rows, dense_w, row_q, docids, parts,
            ki, cand_ok)
        return F.rank_topk(resc, ki, k)

    res["merge_rescore_ms"] = round(
        timed(merge, cv, ci, docids, parts, row_q, fa["tier32"],
              dense_rows, dense_w) * 1e3, 2)
    print(f"[profile] merge {res['merge_rescore_ms']}", file=sys.stderr)

    # ---- dense-tier error/gap measurements ------------------------------
    res["dense2_ms"] = round(timed(dense2, W, tier_stack) * 1e3, 2)
    print(f"[profile] dense2 {res['dense2_ms']}", file=sys.stderr)

    # error of cheap selection tiers vs canonical f32 on REAL bench
    # scores, and the k-th..KB-th score gaps that bound the safety flag
    COLS = 100_000
    s3 = np.asarray(dense3(W, tier_stack)[:, :COLS])  # high-precision ref
    s1 = np.asarray(dense1(W, tier_stack[:V])[:, :COLS])
    s2 = np.asarray(dense2(W, tier_stack)[:, :COLS])
    nz = np.abs(s3) > 1e-6
    res["dense1_max_rel_err"] = float(
        np.max(np.abs((s1 - s3))[nz] / np.abs(s3)[nz]))
    res["dense2_max_rel_err"] = float(
        np.max(np.abs((s2 - s3))[nz] / np.abs(s3)[nz]))
    del s1, s2
    top = -np.sort(-s3, axis=1)[:, :80]
    del s3
    with np.errstate(invalid="ignore", divide="ignore"):
        gap32 = (top[:, 9] - top[:, 31]) / np.abs(top[:, 9])
        gap64 = (top[:, 9] - top[:, 63]) / np.abs(top[:, 9])
    res["gap_k10_kb32_p05"] = float(np.nanpercentile(gap32, 5))
    res["gap_k10_kb64_p05"] = float(np.nanpercentile(gap64, 5))
    print(f"[profile] errs/gaps {res['dense1_max_rel_err']:.2e} "
          f"{res['dense2_max_rel_err']:.2e} gap32p5 "
          f"{res['gap_k10_kb32_p05']:.4f} gap64p5 "
          f"{res['gap_k10_kb64_p05']:.4f}", file=sys.stderr)

    # ---- host planning cost (the wall-clock gap suspect) ----------------
    t0 = time.perf_counter()
    for _ in range(5):
        F.plan_fused(pack, "body", queries, k)
    res["plan_fused_ms"] = round((time.perf_counter() - t0) / 5 * 1e3, 2)
    print(f"[profile] plan {res['plan_fused_ms']}", file=sys.stderr)

    # ---- full msearch wall (host + device, 8 chunks) --------------------
    q4096 = bench.sample_queries(rng, lens, tok, 4096)
    fts.msearch("body", q4096, k)  # warm all geometries
    t0 = time.perf_counter()
    fts.msearch("body", q4096, k)
    res["msearch4096_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    res["msearch_wall_per_chunk_ms"] = round(
        (time.perf_counter() - t0) * 1e3 / 8, 2)
    print(f"[profile] msearch4096 {res['msearch4096_ms']}", file=sys.stderr)

    # ---- end-to-end current pipeline (C=1 scanned executable) -----------
    fn = fts._compiled_scan("body", 1, R, plan.dense_rows.shape[1], k,
                            plan.nreal, False)
    args = (fts._arrays(), np.float32(pack.avgdl("body")),
            plan.rows[None], plan.row_q[None],
            plan.row_w[None], plan.dense_rows[None], plan.dense_w[None])
    res["pipeline_ms"] = round(timed(fn, *args) * 1e3, 2)

    print(json.dumps(res))


if __name__ == "__main__":
    main()
