"""Headline benchmarks: BASELINE.json configs 1, 3, 4 and 5 on real TPU hardware
(config 2, the multi-term disjunction, is what benchmark/'s passage cells run).

Corpus scale and honesty (VERDICT round 1, Next-round #2):
  - 1,000,000 synthetic msmarco-passage-like docs (Zipf term distribution,
    Poisson(40) lengths over a 100k vocabulary) — large enough that the
    dense tier (~1k rows x 1M docs) and CSR postings stress HBM capacity
    and bandwidth, unlike the round-1 30k-doc toy. (Full msmarco is 8.8M
    passages; at that size the dense tier alone would exceed a single
    v5e chip's 16 GB HBM in f32 — the 8-chip sharded layout of config 5
    is the intended deployment for it.)
  - every batch pays full host-side planning (term lookups, row padding):
    a fresh query batch is planned per iteration, no plan reuse.
  - relevance gate: config 1 queries are also run through the bit-exact
    reference path; top-10 doc sets, order, and totals must agree (nDCG@10
    parity = identical rankings by construction, reported as a fraction).

Baselines. The reference repo publishes NO numbers (BASELINE.md): its
benchmarks/README.md delegates to external nightly Rally runs. Baselines
here are therefore explicit throughput MODELS of ES 8.14 on the 32-vCPU
host named by BASELINE.json, with the formula printed next to each number
(see BENCH_NOTES.md for derivations and sources of the per-core rates):
  C1  match BM25 top-10:   32 cores x 75M WAND-effective postings/s/core
                           x 0.6 multicore scaling / mean(sum df per query)
  C3  terms+date_histogram: 60M docs/s aggregate DocValues scan rate
                           (http_logs hourly_agg-class service times)
  C4  exact kNN cosine:    32 cores x 25 GFLOP/s/core effective over
                           2*D*N FLOP/query (f32 script_score exact scan)
  C5  8-shard _msearch:    C1's model on the same corpus split 8 ways
                           (identical total postings) — the TPU side runs
                           the 8 shards' batched programs on ONE chip
                           (serialized; on a v5e-8 they run one-per-chip,
                           validated by __graft_entry__.dryrun_multichip)

Prints ONE JSON line with the config-1 headline plus an `extras` object
carrying the other configs, latencies, MFU, and bandwidth estimates.
v5e peak rates used for utilization: 197 TFLOP/s bf16 matmul,
819 GB/s HBM (public TPU v5e spec).
"""

from __future__ import annotations

import gc
import json
import os
import signal
import sys
import time

import numpy as np

N_DOCS = 1_000_000
VOCAB = 100_000
DOC_LEN_MEAN = 40
Q_BATCH = 4096
N_BATCHES = int(os.environ.get("ES_BENCH_BATCHES", 6))
TERMS_PER_QUERY = 4
TOP_K = 10

if os.environ.get("ES_BENCH_SMOKE"):  # fast correctness pass (CI / CPU)
    N_DOCS, VOCAB, Q_BATCH, N_BATCHES = 20_000, 5_000, 256, 2

PEAK_BF16_FLOPS = 197e12
PEAK_HBM_BPS = 819e9

# ---- CPU baseline model parameters (documented in BENCH_NOTES.md) -------
CORES = 32
MULTICORE_EFF = 0.6
POSTINGS_PER_CORE = 75e6  # WAND-effective scored-postings/s/core (Lucene)
AGG_DOCS_PER_SEC = 60e6  # DocValues scan w/ global-ordinal terms + date rounding + sum, 32 cores aggregate
KNN_FLOPS_PER_CORE = 25e9  # effective f32 GFLOP/s/core for dot products


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build_corpus(rng, n_docs=N_DOCS):
    zipf = 1.0 / np.arange(1, VOCAB + 1)
    zipf /= zipf.sum()
    lens = rng.poisson(DOC_LEN_MEAN, size=n_docs).clip(4, None)
    tok = rng.choice(VOCAB, size=int(lens.sum()), p=zipf)
    return lens, tok


def sample_queries(rng, lens, tok, n_queries, terms_per_query=TERMS_PER_QUERY):
    """Query terms drawn from real documents (msmarco queries reference
    corpus content), deduplicated within a query."""
    starts = np.concatenate([[0], np.cumsum(lens[:-1])])
    docs = rng.integers(0, len(lens), size=n_queries)
    out = []
    for d in docs:
        s, ln = starts[d], lens[d]
        terms = tok[s + rng.integers(0, ln, size=terms_per_query)]
        out.append([(f"t{t}", 1.0) for t in dict.fromkeys(terms)])
    return out


def corpus_docs(lens, tok):
    """Materialize the synthetic corpus as parse_document-shaped docs.
    This is HARNESS work (token string joins over the whole corpus) —
    callers that profile the build hoist it out of the timed region so
    build_profile grades the ingest path, not the generator; r12 and
    earlier timed these joins inside the analyze stage (BENCH_NOTES
    round 20)."""
    term_strs = np.array([f"t{i}" for i in range(VOCAB)])
    doc_terms = term_strs[tok]
    off = 0
    docs = []
    for ln in lens:
        docs.append({"body": [" ".join(doc_terms[off : off + ln])]})
        off += ln
    return docs


def build_pack(lens, tok, dense_min_df=None, docs=None):
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.index.pack import PackBuilder

    m = Mappings({"properties": {"body": {"type": "text"}}})
    b = PackBuilder(m)
    if docs is None:
        docs = corpus_docs(lens, tok)
    # PR 16: batch-vectorized analysis (analysis/batched.py) replaces
    # the per-doc Analyzer.analyze loop; stage attribution (analyze or
    # build.analyze per ES_TPU_ANALYZE) happens inside the batch path
    b.add_documents_batch(docs)
    return b.build(dense_min_df=dense_min_df), m


def config1_match(searcher, m, lens, tok, rng):
    """match BM25 top-10, batched _msearch path, exact-result contract."""
    from elasticsearch_tpu.ops.batched import BatchTermSearcher

    bs = BatchTermSearcher(searcher)
    pack = searcher.pack
    V = pack.dense_tfn.shape[0] if pack.dense_tfn is not None else 0

    # mean postings touched per query (for the CPU baseline model)
    probe = sample_queries(rng, lens, tok, 2048)
    sum_df = np.mean(
        [
            sum(pack.term_blocks("body", t)[2] for t, _ in q)
            for q in probe
        ]
    )
    baseline_qps = CORES * MULTICORE_EFF * POSTINGS_PER_CORE / max(sum_df, 1.0)

    log(f"[c1] warmup (compiles {V}-row dense tier)...")
    # a full untimed WAVE: each batch can land on its own (R, Td) compile
    # key (pow2-quantized plan shapes), and a fresh key inside the timed
    # region costs a compile — warm the whole family first
    # (the persistent XLA cache makes this one-time across runs)
    warm_batches = [sample_queries(rng, lens, tok, Q_BATCH)
                    for _ in range(N_BATCHES)]
    bs.msearch_many("body", warm_batches, TOP_K)

    lat = []
    # sequential batches: honest per-batch latency (each fetch completes
    # before the next batch is planned)
    for it in range(max(N_BATCHES // 2, 1)):
        queries = sample_queries(rng, lens, tok, Q_BATCH)
        t0 = time.perf_counter()  # includes host planning
        s, i, t, ex = bs.msearch("body", queries, TOP_K)
        lat.append(time.perf_counter() - t0)
        log(f"[c1] batch {it}: {lat[-1]*1e3:.0f} ms, exact(pre-rerun) {ex.mean():.3f}")
    # pipelined serving throughput (the vs_baseline number): all batches'
    # programs dispatched before any result is fetched — the concurrent-
    # request regime a serving node runs in, identical to C3's discipline.
    # Planning still happens per batch INSIDE the timed region; only the
    # fixed per-execution dispatch+fetch overhead amortizes.
    batches = [sample_queries(rng, lens, tok, Q_BATCH)
               for _ in range(N_BATCHES)]
    t_all = time.perf_counter()
    results = bs.msearch_many("body", batches, TOP_K)
    elapsed = time.perf_counter() - t_all
    total_q = sum(len(b) for b in batches)
    qps = total_q / elapsed
    ex = np.concatenate([r[3] for r in results])
    log(f"[c1] pipelined {N_BATCHES} batches: {elapsed*1e3:.0f} ms, "
        f"first-pass ok {ex.mean():.4f}")

    # fused-vs-unfused A/B: the same pipelined wave with ES_TPU_FUSED_TOPK
    # disabled (out-of-kernel dense matmul, [Qc, N] scores round-tripping
    # HBM) — records what the in-kernel fusion buys on identical queries
    from elasticsearch_tpu.ops.kernels import fused_topk_enabled

    qps_unfused = None
    if fused_topk_enabled():
        fs = getattr(bs, "_fused", None)
        if fs is not None:
            # free the fused searcher's resident tier stack so the A/B
            # searcher's copy doesn't double the HBM footprint
            fs._fa = None
            fs._fa_live_of = None
        gc.collect()
        os.environ["ES_TPU_FUSED_TOPK"] = "0"
        try:
            bs0 = BatchTermSearcher(searcher)
            bs0.msearch_many("body", batches[:2], TOP_K)  # warm compiles
            t0 = time.perf_counter()
            bs0.msearch_many("body", batches, TOP_K)
            qps_unfused = total_q / (time.perf_counter() - t0)
            del bs0
        finally:
            os.environ.pop("ES_TPU_FUSED_TOPK", None)
        gc.collect()
        log(f"[c1] unfused-topk wave: {qps_unfused:.0f} QPS "
            f"(fused {qps:.0f})")

    # parity gate: fast path vs the independent exact path on a fresh
    # sample. The two paths sum in different orders, so docs whose f32
    # scores agree to ~1e-5 relative may swap ranks (fp-ties); a query
    # passes if every positional mismatch is such a tie — the same
    # contract the test suite enforces against the pure-Python oracle.
    gate = sample_queries(rng, lens, tok, min(512, Q_BATCH))
    sf, idf, tf_, _ = bs.msearch("body", gate, TOP_K, fast=True)
    se, ide, te = [np.asarray(x) for x in bs.run("body", bs.plan("body", gate, TOP_K))]

    def _rank_ok(q):
        fm, em = np.isfinite(sf[q]), np.isfinite(se[q])
        if fm.sum() != em.sum():
            return False
        for a, b_, ia, ib in zip(sf[q][fm], se[q][em], idf[q][fm], ide[q][em]):
            if ia != ib and abs(a - b_) > 1e-5 * max(abs(b_), 1.0):
                return False
        return True

    rank_parity = float(np.mean([_rank_ok(q) for q in range(len(gate))]))
    strict_parity = float(np.mean([
        np.array_equal(idf[q][np.isfinite(sf[q])], ide[q][np.isfinite(se[q])])
        for q in range(len(gate))
    ]))
    totals_parity = float(np.mean((tf_ == te) | (tf_ >= 10_000)))

    # ---- repeated-query (shard request cache) arm -----------------------
    # real query streams are heavily repetitive; the request cache
    # (elasticsearch_tpu/cache/) serves warm queries host-side without a
    # device dispatch. ShardSearcher.msearch is the cache-fronted entry
    # (bs.msearch above deliberately bypasses it so the headline numbers
    # stay uncached). Compile warmth comes from a DIFFERENT query set, so
    # the cold pass is post-compile but cache-cold.
    cache_arm = _cache_arm(searcher, lens, tok, rng)
    log(f"[c1] request-cache arm: {cache_arm}")

    # ---- impact-tier (BM25S) sub-arm ------------------------------------
    impact_arm = _impact_arm(searcher, lens, tok, rng, batches)
    log(f"[c1] impact arm: {impact_arm}")

    # ---- device-cost attribution ----------------------------------------
    # one profiled batch (small: attribution, not throughput) + the
    # sequential-batch latency percentiles through the new exponential
    # histograms — tier/kernel/cache context for every recorded number
    profile_arm = _profile_arm(
        lambda: bs.msearch("body", sample_queries(rng, lens, tok, 256),
                           TOP_K))
    latency_pcts = _hist_pcts("bench.c1.batch_ms", [x * 1e3 for x in lat])
    log(f"[c1] profile arm: {profile_arm} pcts: {latency_pcts}")

    # utilization accounting: logical dense-tier matmul flops + HBM traffic
    flops = 2.0 * total_q * V * N_DOCS
    mfu = flops / elapsed / PEAK_BF16_FLOPS
    # per batch: read dense tier per chunk + write/read scores ~3 passes
    n_chunks = max(1, Q_BATCH // bs._chunk_q(Q_BATCH))
    bytes_touched = N_BATCHES * (
        n_chunks * V * N_DOCS * 4 + 3 * Q_BATCH * N_DOCS * 4
    )
    hbm_util = bytes_touched / elapsed / PEAK_HBM_BPS
    return {
        "qps": round(qps, 1),
        "qps_note": "pipelined serving throughput over "
                    f"{N_BATCHES} concurrent 4096-query batches",
        "fused_topk": fused_topk_enabled(),
        "qps_unfused_topk": (round(qps_unfused, 1)
                             if qps_unfused is not None else None),
        "fused_topk_speedup": (round(qps / qps_unfused, 2)
                               if qps_unfused else None),
        "p50_batch_ms": round(float(np.median(lat)) * 1e3, 1),
        "qps_sequential": round(Q_BATCH / float(np.median(lat)), 1),
        "first_pass_ok": round(float(ex.mean()), 5),
        "batch_size": Q_BATCH,
        "mean_sum_df": round(float(sum_df)),
        "baseline_model_qps": round(baseline_qps, 1),
        "vs_baseline": round(qps / baseline_qps, 2),
        "rank_parity": rank_parity,
        "rank_parity_strict": strict_parity,
        "totals_contract": totals_parity,
        "dense_matmul_mfu": round(mfu, 4),
        "hbm_utilization": round(hbm_util, 3),
        "request_cache": cache_arm,
        "impact": impact_arm,
        "profile": profile_arm,
        "latency_pcts": latency_pcts,
    }


def _build_profile_arm(build_fn, docs):
    """PR 13 satellite: profile one corpus build through the write-path
    stage collector (monitoring/refresh_profile) — per-stage wall ms,
    docs/s, tail_fraction (0.0 by construction for a fresh full build).
    This is the HOST-build baseline the ROADMAP item-2 device port must
    beat, with the stage split saying which stage to port first.
    Returns (build_output, build_profile_record)."""
    from elasticsearch_tpu.monitoring.refresh_profile import (
        collect_build_stages)

    with collect_build_stages() as c:
        out = build_fn()
    wall_s, stages = c.finish()
    return out, {
        "wall_ms": round(wall_s * 1000, 1),
        "docs": int(docs),
        "docs_per_s": round(docs / max(wall_s, 1e-9), 1),
        "tail_fraction": 0.0,
        "stages_ms": {k: round(v * 1000, 2) for k, v in stages.items()},
    }


def _profile_arm(run_fn):
    """Run one batch under the device-cost collector (the `"profile":
    true` machinery) and summarize tier choice, per-kernel wall ms, and
    request-cache traffic — so every BENCH_*.json carries attribution and
    future perf PRs can see WHERE the time went, not just QPS. PR 5: the
    kernel events now carry the analytic cost model's FLOPs/bytes and the
    achieved MFU / bandwidth utilization per dispatch
    (elasticsearch_tpu/monitoring/costmodel + telemetry.time_kernel) —
    aggregated here as per-kernel roofline fractions."""
    from elasticsearch_tpu.monitoring.costmodel import device_peaks
    from elasticsearch_tpu.telemetry import collect_profile_events

    with collect_profile_events() as events:
        run_fn()
    kernels: dict = {}
    util: dict = {}
    tiers: dict = {}
    cache = {"hits": 0, "misses": 0}
    for e in events:
        if e["kind"] == "kernel":
            kernels[e["kernel"]] = round(
                kernels.get(e["kernel"], 0.0) + float(e.get("ms", 0.0)), 3)
            if "flops" in e:
                u = util.setdefault(
                    e["kernel"], {"ms": 0.0, "flops": 0.0, "bytes": 0.0})
                u["ms"] += float(e.get("ms", 0.0))
                u["flops"] += float(e["flops"])
                u["bytes"] += float(e.get("bytes", 0.0))
        elif e["kind"] == "tier":
            tiers[e["tier"]] = tiers.get(e["tier"], 0) + int(
                e.get("queries", 1))
        elif e["kind"] == "cache":
            cache["hits"] += int(e.get("hits", 0))
            cache["misses"] += int(e.get("misses", 0))
    peak_f, peak_b, kind = device_peaks()
    for u in util.values():
        sec = max(u["ms"] / 1e3, 1e-9)
        u["mfu"] = round(u["flops"] / sec / peak_f, 5)
        u["bw_util"] = round(u["bytes"] / sec / peak_b, 5)
        u["ms"] = round(u["ms"], 3)
    return {"tiers": tiers, "kernel_ms": kernels,
            "device_utilization": {"device_kind": kind, "kernels": util},
            "request_cache_events": cache,
            "xla_cost_check": _xla_cost_check(set(kernels))}


def _xla_cost_check(kernel_names=None):
    """PR 12: the in-record ground truth — per-kernel analytic-vs-XLA
    flops/bytes ratios from the compiled-program cross-check
    (monitoring/xla_introspect), restricted to the kernels this arm
    actually dispatched (plus their check statuses), so BENCH_r11+ and
    the eventual TPU stamp carry the drift alongside the MFU/bw numbers
    it underwrites. scripts/bench_regress.py treats >20% drift growth
    between records as advisory output."""
    from elasticsearch_tpu.monitoring.xla_introspect import drift_table

    table = drift_table()
    out = {"kernels": {}, "checked": 0, "exempt": 0}
    for kname, row in table.items():
        if kernel_names is not None and kname not in kernel_names:
            continue
        entry = {"status": row["status"]}
        if "flops_ratio" in row:
            entry["flops_ratio"] = row["flops_ratio"]
            entry["bytes_ratio"] = row.get("bytes_ratio")
            out["checked"] += 1
        elif row["status"] == "exempt":
            out["exempt"] += 1
        out["kernels"][kname] = entry
    return out


def _hist_pcts(name, values_ms):
    """Record latencies into a registry histogram and export its
    exponential-bucket percentiles (the p50/p99 every config now logs)."""
    from elasticsearch_tpu.telemetry import metrics

    for v in values_ms:
        metrics.histogram_record(name, float(v))
    h = metrics.snapshot()["histograms"][name]
    return {"p50_ms": round(h["p50"], 2), "p90_ms": round(h["p90"], 2),
            "p99_ms": round(h["p99"], 2), "n": h["count"]}


def _cache_arm(searcher, lens, tok, rng, n_q=512):
    """Cached-vs-uncached QPS + hit rate for a repeated query batch
    through the cache-fronted msearch entry (ShardSearcher.msearch)."""
    from elasticsearch_tpu.cache import request_cache

    rc = request_cache()
    if not rc.enabled:
        return {"enabled": False}
    warm_q = sample_queries(rng, lens, tok, n_q)
    searcher.msearch("body", warm_q, TOP_K)  # compile-warm, cache-cold next
    rq = sample_queries(rng, lens, tok, n_q)
    st0 = rc.stats()
    t0 = time.perf_counter()
    cold = searcher.msearch("body", rq, TOP_K)
    t_cold = time.perf_counter() - t0
    st_mid = rc.stats()
    t0 = time.perf_counter()
    warm = searcher.msearch("body", rq, TOP_K)
    t_warm = time.perf_counter() - t0
    st1 = rc.stats()
    assert np.array_equal(cold[0], warm[0]) and np.array_equal(
        cold[1], warm[1]), "cached results diverged from uncached"

    def _rate(a, b):
        lk = b["lookups"] - a["lookups"]
        return round((b["hit_count"] - a["hit_count"]) / max(lk, 1), 4)

    return {
        "enabled": True,
        "batch_size": n_q,
        "qps_uncached": round(n_q / t_cold, 1),
        "qps_cached": round(n_q / t_warm, 1),
        "cache_speedup": round(t_cold / t_warm, 2),
        "hit_rate_cold_pass": _rate(st0, st_mid),
        "hit_rate_warm_pass": _rate(st_mid, st1),
        "parity": "byte-identical (asserted)",
    }


def _impact_arm(searcher, lens, tok, rng, batches):
    """C1 impact-tier sub-arm (PR 8): the eager impact-scored sparse tier
    (BM25S) vs the raw-postings fast arm on IDENTICAL pipelined batches,
    with the fused dense pipeline disabled on both sides so the A/B
    isolates the sparse scoring family (run_impact vs run_fast). Records
    QPS both ways, rank parity at the fp-tie tolerance class (PR 6),
    quantization-error accounting against the documented bound
    (index/pack.py: per term ≤ idf·ubf/QMAX), the bytes/lane argument,
    and per-kernel bw_util via _profile_arm."""
    from elasticsearch_tpu.ops.batched import BatchTermSearcher
    from elasticsearch_tpu.ops.scoring import bm25_idf

    pack = searcher.pack
    if pack.impact_meta is None:
        return {"enabled": False, "note": "pack carries no impact tier"}
    saved = {k: os.environ.get(k) for k in ("ES_TPU_IMPACT", "ES_TPU_FUSED")}
    total_q = sum(len(b) for b in batches)
    out = {"dtype": pack.impact_meta["dtype"]}
    try:
        os.environ["ES_TPU_FUSED"] = "0"  # isolate the sparse family
        os.environ["ES_TPU_IMPACT"] = "0"
        bs_fast = BatchTermSearcher(searcher)
        bs_fast.msearch_many("body", batches[:2], TOP_K)  # warm compiles
        t0 = time.perf_counter()
        bs_fast.msearch_many("body", batches, TOP_K)
        qps_fast = total_q / (time.perf_counter() - t0)

        os.environ["ES_TPU_IMPACT"] = "force"
        bs_imp = BatchTermSearcher(searcher)
        bs_imp.msearch_many("body", batches[:2], TOP_K)
        t0 = time.perf_counter()
        bs_imp.msearch_many("body", batches, TOP_K)
        qps_imp = total_q / (time.perf_counter() - t0)

        profile = _profile_arm(
            lambda: bs_imp.msearch(
                "body", sample_queries(rng, lens, tok, 256), TOP_K))

        # ---- parity + quantization-error accounting ---------------------
        gate = sample_queries(rng, lens, tok, min(512, Q_BATCH))
        vi, ii, ti, _ = bs_imp.msearch("body", gate, TOP_K)
        os.environ["ES_TPU_IMPACT"] = "0"
        ve, ie, te, _ = bs_fast.msearch("body", gate, TOP_K)
        doc_count = (pack.field_stats.get("body", {}).get("doc_count")
                     or pack.num_docs)

        def _bound(q):  # Σ_t idf·ubf/qmax over the query's CSR terms
            b = 0.0
            for t, boost in gate[q]:
                if pack.dense_row_of("body", t) is not None:
                    continue
                _s, _n, df = pack.term_blocks("body", t)
                ws = pack.impact_wscale("body", t)
                if df > 0 and ws is not None:
                    b += boost * bm25_idf(doc_count, df) * ws
            return b

        max_err = 0.0
        bound_viol = 0
        rank_ok = 0
        for q in range(len(gate)):
            fm, em = np.isfinite(vi[q]), np.isfinite(ve[q])
            ok = fm.sum() == em.sum() and ti[q] == te[q]
            bq = _bound(q)
            for a, b_, ia, ib in zip(vi[q][fm], ve[q][em],
                                     ii[q][fm], ie[q][em]):
                err = abs(a - b_)
                max_err = max(max_err, err)
                if err > 2 * bq + 1e-6:
                    bound_viol += 1
                if ia != ib and err > 1e-4 * max(abs(b_), 1.0):
                    ok = False
            rank_ok += bool(ok)
        code_bytes = {"uint16": 2, "int8": 1}[pack.impact_meta["dtype"]]
        out.update({
            "qps_impact": round(qps_imp, 1),
            "qps_fast_same_batches": round(qps_fast, 1),
            "impact_speedup": round(qps_imp / max(qps_fast, 1e-9), 2),
            "rank_parity_fp_tie": round(rank_ok / len(gate), 4),
            "quantization": {
                "max_abs_score_err": round(float(max_err), 8),
                "mean_per_query_bound": round(float(np.mean(
                    [_bound(q) for q in range(len(gate))])), 8),
                "bound_violations": bound_viol,
            },
            "postings_bytes_per_lane": {
                "impact": 4 + code_bytes, "raw_bm25": 12},
            "profile": profile,
        })
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _c3_corpus(rng, n):
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.parallel.stacked import build_stacked_pack

    log(f"[c3] building http_logs-like corpus ({n} docs)...")
    m = Mappings({"properties": {
        "status": {"type": "keyword"},
        "clientip": {"type": "keyword"},
        "@timestamp": {"type": "date"},
        "size": {"type": "long"},
    }})
    statuses = np.array(["200", "200", "200", "200", "304", "404", "500", "301"])
    ips = rng.integers(0, 60_000, size=n)  # high-cardinality keyword
    t0ms = 1_420_070_400_000
    times = t0ms + rng.integers(0, 30 * 86_400_000, size=n)
    sizes = rng.integers(100, 100_000, size=n)
    st = statuses[rng.integers(0, len(statuses), size=n)]
    docs = [
        (str(i), {
            "status": st[i],
            "clientip": f"10.{ips[i] >> 8 & 255}.{ips[i] & 255}.{ips[i] % 251}",
            "@timestamp": int(times[i]),
            "size": int(sizes[i]),
        })
        for i in range(n)
    ]
    return build_stacked_pack(docs, m, num_shards=1)


def _c3_measure(ss, n, aggs, batch=32):
    """One corpus point: sequential p50 AND pipelined service time.

    The pipelined number is the serving-throughput measurement: `batch`
    requests dispatched before any result is fetched (search_batch), so the
    fixed dispatch+fetch latency amortizes — this is what a serving node
    does under concurrent load. Both numbers are reported; vs_baseline
    uses the pipelined service time,
    p50_ms keeps the honest single-request latency. Round 5 deepens the
    pipeline 8 -> 32: the round-4 decomposition (service(1M) 19.3 ms,
    service(4M) 33.7 ms) puts the per-request scan at ~4.8 ms with
    ~116 ms of fixed per-wave cost — depth 32 divides the fixed term by
    4, the regime a serving node at 32-deep concurrency runs in."""
    reqs = [dict(query=None, size=0, aggs=aggs) for _ in range(batch)]
    ss.search(None, size=0, aggs=aggs)  # warm/compile
    ss.search_batch(reqs)  # warm the batched wave too
    lat = []
    for _ in range(6):
        t0 = time.perf_counter()
        r = ss.search(None, size=0, aggs=aggs)
        lat.append(time.perf_counter() - t0)
    p50 = float(np.median(lat))
    svc = []
    for _ in range(3):
        t0 = time.perf_counter()
        rs = ss.search_batch(reqs)
        svc.append((time.perf_counter() - t0) / batch)
    service = min(svc)
    r = rs[-1]
    baseline_ms = n / AGG_DOCS_PER_SEC * 1e3
    return {
        "p50_ms": round(p50 * 1e3, 1),
        "pipelined_service_ms": round(service * 1e3, 1),
        "pipeline_depth": batch,
        "docs_per_s": round(n / service / 1e6, 1),
        "unit_docs_per_s": "M docs/s",
        "baseline_model_ms": round(baseline_ms, 1),
        "vs_baseline": round(baseline_ms / (service * 1e3), 2),
        "vs_baseline_p50": round(baseline_ms / (p50 * 1e3), 2),
        "buckets": len(r.aggregations["by_status"]["buckets"]),
    }


def config3_aggs(rng):
    """terms + date_histogram over http_logs-like corpora at 1M and 4M
    docs: the second point shows docs/s scaling as the fixed dispatch
    overhead amortizes into a larger device scan (VERDICT r3 #2)."""
    from elasticsearch_tpu.parallel.sharded import StackedSearcher

    aggs = {
        "by_status": {
            "terms": {"field": "status"},
            "aggs": {
                "over_time": {"date_histogram": {
                    "field": "@timestamp", "calendar_interval": "day"}},
                "bytes": {"sum": {"field": "size"}},
            },
        }
    }
    n1 = N_DOCS
    sp = _c3_corpus(rng, n1)
    out = _c3_measure(StackedSearcher(sp, mesh=None), n1, aggs)
    del sp
    gc.collect()
    if not os.environ.get("ES_BENCH_SMOKE"):
        n2 = 4 * N_DOCS
        sp2 = _c3_corpus(rng, n2)
        out["scale_4m"] = _c3_measure(StackedSearcher(sp2, mesh=None), n2, aggs)
        del sp2
        gc.collect()
    return out


def config4_knn(rng):
    """dense_vector exact cosine kNN, top-10. Default arm: the tiered
    split-bf16 scan (ops/vector.TieredKnnScanner — 2 bf16 MXU passes +
    in-VMEM top-KB + f32 rescore of survivors, exactness preserved by the
    margin-flag fallback); ES_TPU_FUSED_TOPK=0 reverts to the f32-HIGHEST
    fused scan. Both arms are timed so the tiering win is on record."""
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.kernels import fused_topk_enabled, scan_topk
    from elasticsearch_tpu.ops.vector import TieredKnnScanner

    n, dims, q_n = N_DOCS, 384, 1024
    log(f"[c4] building {n}x{dims} vector corpus...")
    vecs = rng.standard_normal((n, dims), dtype=np.float32)
    sq = (vecs * vecs).sum(axis=1)
    inv = 1.0 / np.sqrt(sq)
    mat_t = jnp.asarray(vecs.T)  # [D, N]
    aux_doc = jnp.asarray(inv)
    live = jnp.ones((n,), bool)
    tiered = TieredKnnScanner(vecs, sq, "cosine") if fused_topk_enabled() \
        else None

    flag_rate = 0.0

    def run_batch(qv):
        nonlocal flag_rate
        if tiered is not None:
            v, i, t, ok = tiered.search(qv, TOP_K)
            flag_rate = max(flag_rate, float(1.0 - ok.mean()))
            return v
        qinv = 1.0 / np.linalg.norm(qv, axis=1)
        out = scan_topk(
            jnp.asarray(qv), mat_t, live, TOP_K,
            transform="cosine", aux_doc=aux_doc, aux_q=jnp.asarray(qinv),
            count_positive=False,
        )
        return np.asarray(out[0])

    def time_arm(runner, iters=6):
        runner(rng.standard_normal((q_n, dims), dtype=np.float32))  # warm
        lat, total_q = [], 0
        t_all = time.perf_counter()
        for _ in range(iters):
            qv = rng.standard_normal((q_n, dims), dtype=np.float32)
            t0 = time.perf_counter()
            runner(qv)
            lat.append(time.perf_counter() - t0)
            total_q += q_n
        return total_q / (time.perf_counter() - t_all), lat, total_q

    qps, lat, total_q = time_arm(run_batch)
    baseline_qps = CORES * MULTICORE_EFF * KNN_FLOPS_PER_CORE / (2.0 * dims * n)
    flops = 2.0 * total_q * dims * n
    elapsed = total_q / qps
    # device-cost attribution: one small profiled batch through the new
    # accounting (vector.knn_tiered carries the cost model's FLOPs/bytes,
    # so THIS is the recorded C4 roofline fraction — the "driver-recorded
    # device-bound proof" VERDICT asked for, vs the analytic `mfu` below)
    c4_profile = _profile_arm(
        lambda: run_batch(rng.standard_normal((256, dims),
                                              dtype=np.float32)))
    out = {
        "qps": round(qps, 1),
        "p50_batch_ms": round(float(np.median(lat)) * 1e3, 1),
        "batch_size": q_n,
        "tiered": tiered is not None,
        "flag_rate_max": round(flag_rate, 5),
        "baseline_model_qps": round(baseline_qps, 1),
        "vs_baseline": round(qps / baseline_qps, 2),
        "mfu": round(flops / elapsed / PEAK_BF16_FLOPS, 4),
        "profile": c4_profile,
        "latency_pcts": _hist_pcts("bench.c4.batch_ms",
                                   [x * 1e3 for x in lat]),
    }
    if tiered is not None:
        # A/B: the f32-HIGHEST arm on the same shapes
        def run_f32(qv):
            qinv = 1.0 / np.linalg.norm(qv, axis=1)
            o = scan_topk(
                jnp.asarray(qv), mat_t, live, TOP_K,
                transform="cosine", aux_doc=aux_doc,
                aux_q=jnp.asarray(qinv), count_positive=False,
            )
            return np.asarray(o[0])

        qps0, lat0, _tq = time_arm(run_f32, iters=3)
        out["qps_unfused_topk"] = round(qps0, 1)
        out["fused_topk_speedup"] = round(qps / qps0, 2)
    out["ann"] = _c4_ann_arm(rng, n, 384, q_n, time_arm)
    return out


def _c4_ann_arm(rng, n, dims, q_n, time_arm):
    """PR 7 ANN + int8-scan arms: device-resident IVF (ann/) over a
    CLUSTERED corpus (embedding spaces cluster; IVF on uniform noise is
    the known degenerate case the exact arms above already cover).
    Records recall@10 vs the exact oracle at the default nprobe,
    QPS speedup vs the exact scan of the SAME corpus, and per-kernel
    bw_util through the device-cost collector — the ISSUE-7 acceptance
    attribution."""
    from elasticsearch_tpu.ann import AnnSearcher, build_ann
    from elasticsearch_tpu.ops.kernels import scan_topk

    import jax.numpy as jnp

    nlist = max(16, int(n ** 0.5 * 0.75))
    log(f"[c4-ann] clustered corpus {n}x{dims}, nlist={nlist}...")
    centers = rng.standard_normal((nlist, dims)).astype(np.float32) * 4.0
    assign = rng.integers(0, nlist, size=n)
    vecs = (centers[assign]
            + rng.standard_normal((n, dims)).astype(np.float32) * 0.6)
    sq = (vecs * vecs).sum(axis=1)
    t0 = time.perf_counter()
    # build_profile (PR 13): stage-partitioned C4 ANN build baseline
    # (build.kmeans vs build.ann_tiles is THE split the device port
    # attacks — batched kmeans as matmul+argmin waves)
    ann, c4_build = _build_profile_arm(
        lambda: build_ann(vecs, np.ones(n, bool), nlist=nlist), n)
    build_s = time.perf_counter() - t0
    searcher = AnnSearcher(ann, vecs, sq, "cosine")

    def run_ann(qv, tier="int8"):
        return searcher.search(qv, TOP_K, num_candidates=100, tier=tier)[0]

    mat_t = jnp.asarray(vecs.T)
    aux_doc = jnp.asarray(1.0 / np.sqrt(np.maximum(sq, 1e-30)))
    live = jnp.ones((n,), bool)

    def run_exact(qv):
        qinv = 1.0 / np.linalg.norm(qv, axis=1)
        o = scan_topk(jnp.asarray(qv), mat_t, live, TOP_K,
                      transform="cosine", aux_doc=aux_doc,
                      aux_q=jnp.asarray(qinv), count_positive=False)
        return np.asarray(o[0]), np.asarray(o[1])

    # recall@10 vs the exact oracle at the DEFAULT nprobe
    qr = (vecs[rng.integers(0, n, 64)]
          + rng.standard_normal((64, dims)).astype(np.float32) * 0.1)
    _ev, ei = run_exact(qr)
    recall = {}
    for tier in ("int8", "bf16"):
        _av, ai, _at = searcher.search(qr, TOP_K, num_candidates=100,
                                       tier=tier)
        recall[tier] = round(float(np.mean([
            len(set(ei[b].tolist()) & set(ai[b].tolist())) / TOP_K
            for b in range(len(qr))])), 4)
    qps_ann, lat_ann, _ = time_arm(run_ann, iters=6)
    qps_bf16, _l, _ = time_arm(lambda qv: run_ann(qv, "bf16"), iters=3)
    qps_exact, _l2, _ = time_arm(lambda qv: run_exact(qv)[0], iters=3)
    profile = _profile_arm(lambda: run_ann(
        rng.standard_normal((256, dims), dtype=np.float32)))
    return {
        "nlist": nlist,
        "tile": ann["tile"],
        "default_nprobe_nc100": True,
        "build_s": round(build_s, 1),
        "build_profile": c4_build,
        "recall_at_10": recall,
        "qps_int8": round(qps_ann, 1),
        "qps_bf16": round(qps_bf16, 1),
        "qps_exact_same_corpus": round(qps_exact, 1),
        "ann_speedup_vs_exact": round(qps_ann / max(qps_exact, 1e-9), 2),
        "p50_batch_ms": round(float(np.median(lat_ann)) * 1e3, 1),
        "batch_size": q_n,
        "profile": profile,
        "latency_pcts": _hist_pcts("bench.c4.ann_batch_ms",
                                   [x * 1e3 for x in lat_ann]),
    }


def config5_8shard(rng):
    """_msearch over an 8M-doc corpus split into 8 x 1M-doc shards — the
    corpus that NEEDS the mesh (VERDICT r4 C5: at 1M docs an 8-way split
    is pure overhead; at 8M the dense tier + postings of a single shard
    alone fill a chip's working set, so the only single-chip alternative
    is serial shard-at-a-time execution). The one real chip times each
    shard's batched program with its arrays resident (per-shard build/
    upload excluded and reported — on a v5e-8 every chip holds its shard
    resident, validated by __graft_entry__.dryrun_multichip); the
    coordinator merge is measured on host and the collective-merge
    fraction on the 8-device virtual mesh (scripts/c5_mesh_probe.py).

    projection = mean-shard QPS x 8 x (1 - merge_overhead_frac), i.e.
    per-chip efficiency carried over from the measured single-chip rate.
    """
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.index.pack import PackBuilder
    from elasticsearch_tpu.ops.batched import BatchTermSearcher
    from elasticsearch_tpu.query.executor import ShardSearcher

    S = 8
    n_per = N_DOCS
    # own deterministic stream: the C5 corpus must be identical whether
    # the bench runs all configs or `bench.py c5` alone (and the shard-
    # pack cache below keys on that determinism)
    rng = np.random.default_rng(4242)
    log(f"[c5] building {S}x{n_per} sharded corpus...")
    lens8, tok8 = build_corpus(rng, n_docs=S * n_per)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    term_strs = np.array([f"t{i}" for i in range(VOCAB)])
    starts = np.concatenate([[0], np.cumsum(lens8[:-1])])
    q_n = Q_BATCH  # full-width batches: the fixed per-execution overhead
    # amortizes exactly as in C1 (1024-query batches measured ~295 ms vs
    # ~550 ms for 4096 — 2.4x better per-query)
    n_iters = 2
    batches = [sample_queries(rng, lens8, tok8, q_n) for _ in range(n_iters)]
    warm = sample_queries(rng, lens8, tok8, q_n)

    # CPU baseline model on the FULL 8M corpus: sum_df measured per shard
    # and summed (identical postings split 8 ways)
    sum_df_total = 0.0
    shard_times = []  # [S][n_iters]
    per_shard = []  # device outputs of the LAST iteration per shard
    cache_arm = {"enabled": False}
    doc_base = 0
    import hashlib as _hl

    cache_root = os.environ.get("ES_BENCH_C5_CACHE", "/tmp/es_bench_c5")
    # the cache key carries the pack-LAYOUT token: any pack-format/schema
    # change (new component, renamed array, FORMAT bump) changes the
    # token, so a stale cached corpus can never silently feed the record
    from elasticsearch_tpu.index.packio import pack_layout_token

    cache_key = (f"{S}x{n_per}v{VOCAB}l{DOC_LEN_MEAN}s4242-"
                 f"{pack_layout_token()}")
    for s in range(S):
        lo, hi = s * n_per, (s + 1) * n_per
        # shard packs are a pure function of the deterministic corpus:
        # cache them (index/packio components) so re-runs skip the
        # ~3-4 min/shard host build — the single biggest bench cost
        cdir = os.path.join(cache_root, cache_key, f"shard{s}")
        man_p = os.path.join(cdir, "manifest.json")
        pack = None
        from elasticsearch_tpu.index import packio

        if os.path.exists(man_p):
            try:
                man = json.load(open(man_p))
                pack = packio.deserialize_pack(
                    man, lambda d: open(os.path.join(cdir, d), "rb").read())
                log(f"[c5] shard {s}: loaded from cache")
            except Exception:  # noqa: BLE001 - stale/corrupt cache
                pack = None
        if pack is None:
            b = PackBuilder(m)
            off = int(starts[lo])
            for ln in lens8[lo:hi]:
                b.add_document(
                    {"body": [" ".join(term_strs[tok8[off:off + ln]])]})
                off += ln
            pack = b.build()
            del b
            try:
                os.makedirs(cdir, exist_ok=True)

                def _put(payload: bytes) -> str:
                    digest = _hl.sha256(payload).hexdigest()
                    p = os.path.join(cdir, digest)
                    if not os.path.exists(p):
                        with open(p, "wb") as f:
                            f.write(payload)
                    return digest

                man = packio.serialize_pack(pack, _put)
                json.dump(man, open(man_p + ".tmp", "w"))
                os.replace(man_p + ".tmp", man_p)
            except Exception:  # noqa: BLE001 - cache is best-effort
                pass
        searcher = ShardSearcher(pack, mappings=m)
        bs = BatchTermSearcher(searcher)
        probe = batches[0][:256]
        sum_df_total += float(np.mean([
            sum(pack.term_blocks("body", t)[2] for t, _ in q)
            for q in probe
        ]))
        # warm/compile EXCLUDED: run the exact timed batches once so
        # every compile key they touch is cached before timing
        bs.msearch("body", warm, TOP_K)
        for queries in batches:
            bs.msearch("body", queries, TOP_K)
        times = []
        outs = None
        for queries in batches:
            t0 = time.perf_counter()
            outs = bs.msearch("body", queries, TOP_K)
            times.append(time.perf_counter() - t0)
        shard_times.append(times)
        per_shard.append((np.asarray(outs[0]), np.asarray(outs[1])))
        if s == 0:
            # device-cost attribution, measured once while shard 0's
            # searcher is resident (tier chosen, kernel ms, cache events)
            c5_profile = _profile_arm(
                lambda: bs.msearch("body", warm[:256], TOP_K))
            log(f"[c5] profile arm (shard 0): {c5_profile}")
        if s == 0:
            # repeated-query (request cache) arm, measured on shard 0 only
            # (per-shard entries are exactly the C5 cache design; one
            # shard bounds the arm's cost while its searcher is resident)
            cache_arm = _cache_arm(searcher, lens8[lo:hi],
                                   tok8[int(starts[lo]):
                                        int(starts[lo]) + int(lens8[lo:hi].sum())],
                                   np.random.default_rng(7), n_q=512)
            log(f"[c5] request-cache arm (shard 0): {cache_arm}")
        del bs, searcher, pack
        gc.collect()
        log(f"[c5] shard {s}: batch times {[round(x*1e3) for x in times]} ms")
        doc_base += n_per
    baseline_qps = CORES * MULTICORE_EFF * POSTINGS_PER_CORE / max(
        sum_df_total, 1.0)

    # coordinator merge of the last iteration, (score desc, shard asc,
    # doc asc) — the reference's SearchPhaseController order
    t0 = time.perf_counter()
    allv = np.stack([p[0] for p in per_shard])  # [S, Q, k]
    alli = np.stack([p[1] for p in per_shard])
    flat_v = allv.transpose(1, 0, 2).reshape(q_n, -1)
    flat_i = alli.transpose(1, 0, 2).reshape(q_n, -1)
    flat_s = np.broadcast_to(
        np.repeat(np.arange(S), TOP_K)[None, :], flat_v.shape)
    order = np.lexsort((flat_i, flat_s, -flat_v), axis=1)[:, :TOP_K]
    m_v = np.take_along_axis(flat_v, order, axis=1)
    t_merge = time.perf_counter() - t0
    assert m_v.shape == (q_n, TOP_K)

    per_batch = [sum(shard_times[s][i] for s in range(S))
                 for i in range(n_iters)]
    serial_s = float(np.median(per_batch))
    qps_serial = q_n / serial_s
    mean_shard_ms = serial_s / S * 1e3

    # collective-overhead measurement: production sharded program on the
    # 8-device VIRTUAL mesh, shard-local vs device-side global merge
    import subprocess

    # the probe is a CPU tool (8 virtual devices): its environment says so
    # outright, so it never asks for the chip this process holds. A
    # failure propagates to _guard, which ends the run non-zero.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "scripts", "c5_mesh_probe.py")],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"c5_mesh_probe exited {out.returncode}: "
            f"{out.stderr.strip().splitlines()[-1:]}")
    probe_r = json.loads(out.stdout.strip().splitlines()[-1])
    frac = probe_r.get("merge_overhead_frac")
    projected = (
        round(q_n / (serial_s / S) * (1.0 - frac), 1)
        if frac is not None else None
    )
    # the C5/MULTICHIP record criteria (ROADMAP item 5): the mesh
    # projection against BOTH alternatives, with the merge measured
    # ON-DEVICE (sharded.global_merge / the pjit all-gather program) and
    # byte/rank parity asserted between the pjit, shard_map and
    # single-device paths inside the probe
    record = {
        "mesh_projected_qps": projected,
        "vs_single_chip_serial": (round(projected / max(qps_serial, 1e-9), 2)
                                  if projected else None),
        "vs_8m_cpu_model": (round(projected / max(baseline_qps, 1e-9), 2)
                            if projected else None),
        "merge_frac_on_device": frac,
        "merge_measured_on_device": probe_r.get("t_device_merge_ms")
        is not None,
        "parity": probe_r.get("parity"),
        "allgather": probe_r.get("allgather"),
        # PR 11: the fused Pallas arm on the one-program route (embedded
        # shard_map region + in-program merge) — byte parity vs the
        # shard_map oracle and its mfu/bw_util/ici_util attribution,
        # from the same mesh probe
        "fused_sharded": probe_r.get("fused"),
        "landed": bool(projected is not None
                       and projected > qps_serial
                       and projected > baseline_qps),
        "basis": "mesh = measured mean-shard rate x S x (1 - merge_frac); "
                 "merge_frac = on-device global merge vs shard-local "
                 "compute on the 8-device virtual mesh. On a CPU smoke "
                 "the shard rate is host-bound, so vs_8m_cpu_model is a "
                 "TPU criterion (BENCH_NOTES r14); vs_single_chip_serial "
                 "holds on any platform (S-way concurrency minus the "
                 "measured merge fraction).",
    }
    return {
        "corpus_docs": S * n_per,
        "shards": S,
        "qps_1chip_serial": round(qps_serial, 1),
        "mean_shard_batch_ms": round(mean_shard_ms, 1),
        "host_merge_ms": round(t_merge * 1e3, 2),
        "batch_size": q_n,
        "baseline_model_qps_8m": round(baseline_qps, 1),
        "request_cache": cache_arm,
        "profile": c5_profile,
        "latency_pcts": _hist_pcts(
            "bench.c5.shard_batch_ms",
            [x * 1e3 for times in shard_times for x in times]),
        "mesh_probe": probe_r,
        "record": record,
        "projection": {
            "formula": "q_n / mean_shard_batch_time * (1 - merge_frac)",
            "projected_qps_v5e8": projected,
            "vs_baseline": (round(projected / baseline_qps, 2)
                            if projected else None),
            "basis": "each chip holds one resident 1M-doc shard and runs "
                     "the measured single-chip rate; merge fraction from "
                     "the 8-device virtual-mesh probe's ON-DEVICE global "
                     "merge; per-shard build/upload excluded (one-time "
                     "residency)",
        },
    }


def _tenant_attribution(svc, engine):
    """PR 19: the per-tenant device-ms attribution block the serving
    arms record. Walks the flight recorder and asserts IN-RECORD that
    every wave's tenant shares sum EXACTLY (`==`, never approximately)
    to that wave's device segment, then reports the bounded per-tenant
    ledger. `sum_shares_over_wall` is fsum-over-fsum, so the 1.0 it
    records is bit-exact, not a tolerance."""
    import math

    from elasticsearch_tpu.tenancy.metering import shares_sum

    sums, walls = [], []
    for w in svc.flight_recorder()["waves"]:
        mix = w.get("tenants") or {}
        if not mix or w.get("kind") == "degradation":
            continue
        if not isinstance(next(iter(mix.values())), dict):
            continue
        s = shares_sum(v["device_ms"] for v in mix.values())
        wall = w["segments_ms"]["device"]
        assert s == wall, (s, wall, w)
        sums.append(s)
        walls.append(wall)
    wall_total = math.fsum(walls)
    ratio = (math.fsum(sums) / wall_total) if wall_total else 1.0
    assert ratio == 1.0, ratio
    rows = engine.metering.rows()
    return {
        "waves_checked": len(sums),
        "sum_shares_over_wall": ratio,  # asserted == 1.0 above
        "ledger_rows": len(rows),  # top-K bounded (+ _other fold row)
        "per_tenant_device_ms": {
            t: r["device_ms"] for t, r in sorted(
                rows.items(), key=lambda kv: -kv[1]["device_ms"])},
    }


def config6_serving(rng):
    """C6 closed-loop serving arm (ROADMAP item 3): N concurrent clients
    against the continuous-batching front end vs today's per-request
    dispatch. Both arms run the IDENTICAL request stream through the same
    single engine thread (the REST `call` discipline); the only variable
    is whether concurrent requests coalesce into packed device waves.
    Records QPS, p50/p99, wave occupancy, and per-kernel MFU for both
    arms — the occupancy→MFU argument of BENCH_NOTES round 10."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from elasticsearch_tpu.engine.engine import Engine

    n_docs = 4_000 if os.environ.get("ES_BENCH_SMOKE") else 100_000
    n_clients = 64 if os.environ.get("ES_BENCH_SMOKE") else 512
    reqs_per_client = 4
    n_reqs = n_clients * reqs_per_client

    log(f"[c6] building {n_docs}-doc engine index...")
    lens, tok = build_corpus(rng, n_docs=n_docs)
    import shutil
    import tempfile

    data_dir = tempfile.mkdtemp(prefix="es_bench_c6_")
    engine = Engine(data_dir)
    idx = engine.create_index("c6", {"properties": {"body": {"type": "text"}}})
    term_strs = np.array([f"t{i}" for i in range(VOCAB)])
    doc_terms = term_strs[tok]
    off = 0
    for ln in lens:
        idx.index_doc(None, {"body": " ".join(doc_terms[off:off + ln])})
        off += ln
    idx.refresh()
    idx.searcher  # force-merge: the term lane packs on a sealed base

    # request stream: term-lane-eligible match queries (1-3 terms drawn
    # from real docs), the serving steady state. One fixed stream, both
    # arms replay it identically.
    qs = sample_queries(rng, lens, tok, n_reqs, terms_per_query=3)
    bodies = [{"query": {"match": {"body": " ".join(t for t, _ in q)}},
               "size": TOP_K} for q in qs]

    pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="c6-engine")

    def _closed_loop(issue_fn, name):
        """n_clients closed-loop threads drain the shared stream; returns
        (qps, per-request wall-ms list)."""
        lat_ms = [0.0] * n_reqs
        it = iter(range(n_reqs))
        lock = threading.Lock()

        def client(cid):
            while True:
                with lock:
                    i = next(it, None)
                if i is None:
                    return
                t0 = time.perf_counter()
                issue_fn(i, cid)
                lat_ms[i] = (time.perf_counter() - t0) * 1e3

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_all
        qps = n_reqs / elapsed
        log(f"[c6] {name}: {n_reqs} reqs / {elapsed:.2f}s = {qps:.0f} QPS")
        return qps, lat_ms

    # per-arm device utilization comes from the PR-5 cumulative registry
    # counters (es.kernel.<n>.flops/bytes + .ms histogram sums): the
    # closed-loop arms run on client/engine threads, outside any one
    # thread's profile-event collector — the registry sees all of them
    def _util_delta(before, after):
        from elasticsearch_tpu.monitoring.costmodel import device_peaks

        peak_f, peak_b, kind = device_peaks()
        bc, ac = before["counters"], after["counters"]
        bh, ah = before["histograms"], after["histograms"]
        kernels = {}
        for name, v in ac.items():
            if not (name.startswith("es.kernel.")
                    and name.endswith(".flops")):
                continue
            kern = name[len("es.kernel."):-len(".flops")]
            flops = v - bc.get(name, 0.0)
            if flops <= 0:
                continue
            byts = (ac.get(f"es.kernel.{kern}.bytes", 0.0)
                    - bc.get(f"es.kernel.{kern}.bytes", 0.0))
            ms = (ah.get(f"es.kernel.{kern}.ms", {}).get("sum", 0.0)
                  - bh.get(f"es.kernel.{kern}.ms", {}).get("sum", 0.0))
            sec = max(ms / 1e3, 1e-9)
            kernels[kern] = {"ms": round(ms, 3),
                             "mfu": round(flops / sec / peak_f, 5),
                             "bw_util": round(byts / sec / peak_b, 5)}
        return {"device_kind": kind, "kernels": kernels}

    from elasticsearch_tpu.telemetry import metrics as _metrics

    # ---- arm A: per-request dispatch (today's REST model) ----------------
    def solo(i, _cid):
        b = bodies[i]
        return pool.submit(engine.search_multi, "c6", query=b["query"],
                           size=b["size"]).result()

    solo(0, 0)  # compile-warm the solo plan family
    snap0 = _metrics.snapshot()
    a_qps, a_lat = _closed_loop(solo, "per-request")
    a_util = _util_delta(snap0, _metrics.snapshot())

    # ---- arm B: continuous-batching serving front end --------------------
    svc = engine.serving
    svc.bind_executor(pool.submit)
    svc.set_enabled(True)
    entries = [svc.classify("c6", b, {}) for b in bodies]
    assert all(e is not None for e in entries), "stream must be wave-eligible"
    # warm the power-of-two wave-tier compile family with untimed bursts
    for burst in (1, 8, 64, min(256, n_clients)):
        futs = [svc.submit(dict(entries[i]), tenant="warm")
                for i in range(burst)]
        for f in futs:
            f.result(timeout=600)

    b_results = [None] * n_reqs

    def coalesced(i, cid):
        b_results[i] = svc.submit(
            entries[i], tenant=f"client-{cid % 8}").result(timeout=600)

    snap1 = _metrics.snapshot()
    b_qps, b_lat = _closed_loop(coalesced, "serving")
    b_util = _util_delta(snap1, _metrics.snapshot())
    st = svc.stats()

    # ---- parity gates ----------------------------------------------------
    # (1) the coalescing contract, asserted byte-level: a request packed
    # into a shared wave returns EXACTLY what it returns dispatched alone
    # through the same path (pipeline idle -> wave of 1). This is what
    # coalescing itself must never change.
    sample = rng.integers(0, n_reqs, size=64)
    for i in sample:
        alone = json.dumps(svc.submit(dict(entries[int(i)]),
                                      tenant="gate").result(timeout=600),
                           sort_keys=True)
        assert json.dumps(b_results[int(i)], sort_keys=True) == alone, (
            f"coalesced result diverged from solo-wave on request {i}")
    # (2) vs the classic per-request executor: the term-lane kernel and
    # the compiled plan sum BM25 terms in different fp orders (~1e-7
    # relative score skew, same contract as the C1 fused gate), so this
    # level is rank parity with fp-tie tolerance, recorded not assumed.
    rank_ok = 0
    gate_n = 128
    for i in rng.integers(0, n_reqs, size=gate_n):
        b = bodies[int(i)]
        classic = engine.search_multi("c6", query=b["query"],
                                      size=b["size"])
        co = b_results[int(i)]
        ch = [(h["_id"], h["_score"]) for h in classic["hits"]["hits"]]
        gh = [(h["_id"], h["_score"]) for h in co["hits"]["hits"]]
        rank_ok += (
            classic["hits"]["total"] == co["hits"]["total"]
            and len(ch) == len(gh)
            and all(a_id == g_id
                    or abs(a_s - g_s) <= 1e-5 * max(abs(a_s), 1.0)
                    for (a_id, a_s), (g_id, g_s) in zip(ch, gh)))
    rank_parity = rank_ok / gate_n

    tattr = _tenant_attribution(svc, engine)
    svc.stop()
    engine.close()
    pool.shutdown(wait=True)
    shutil.rmtree(data_dir, ignore_errors=True)

    return {
        "docs": n_docs,
        "clients": n_clients,
        "requests": n_reqs,
        "per_request": {
            "qps": round(a_qps, 1),
            "latency": _hist_pcts("bench.c6.per_request.ms", a_lat),
            "device_utilization": a_util,
        },
        "serving": {
            "qps": round(b_qps, 1),
            "latency": _hist_pcts("bench.c6.serving.ms", b_lat),
            "device_utilization": b_util,
            "waves": st["waves"],
            "avg_wave_size": round(st["wave"]["avg_size"], 1),
            "avg_term_occupancy": st["wave"]["avg_term_occupancy"],
            # PR 11: ≤1 dispatch + ≤1 fetch per wave is the end-to-end
            # fusion contract (r09 term lanes fetched inside begin, so a
            # mixed wave cost ≥2 blocking rounds and serialized the
            # scheduler thread; see BENCH_NOTES round 15)
            "host_transitions_per_wave": {
                kk: round(vv, 3) for kk, vv in
                st["wave"]["host_transitions_per_wave"].items()},
            "term_packed": st["term_packed"],
            "shed": st["shed"],
        },
        "speedup": round(b_qps / max(a_qps, 1e-9), 2),
        "tenant_attribution": tattr,
        "parity": {
            "coalesced_vs_solo_wave": "byte-identical (64-sample asserted)",
            "rank_parity_vs_classic": rank_parity,
        },
        "basis": "identical request stream, identical single engine "
                 "thread; arm B coalesces concurrent requests into padded "
                 "power-of-two device waves (serving/)",
    }


def _analyze_readout(idx, ind):
    """PR 16 ingest readout: where analysis time went (host `analyze`
    loop vs batched/device `build.analyze`), what fraction of the write
    path it is, and how much of it was hidden under builds by the
    depth-1 analyze/build overlap (summed per-profile overlap ms)."""
    from elasticsearch_tpu.analysis.batched import analyze_mode
    from elasticsearch_tpu.monitoring.refresh_profile import recorder_for

    stage_ms = ind.get("stage_ms") or {}
    analyze_ms = {k: v for k, v in stage_ms.items()
                  if k in ("analyze", "build.analyze")}
    total = sum(stage_ms.values())
    profs = recorder_for(idx).profiles()["profiles"]
    overlap = sum(p.get("analyze_overlap_ms", 0.0) for p in profs)
    return {
        "mode": analyze_mode(),
        "stage_ms": {k: round(v, 3) for k, v in analyze_ms.items()},
        "fraction_of_write_path": (
            round(sum(analyze_ms.values()) / total, 6) if total else None),
        "overlap_ms": round(overlap, 3),
    }


def _ingest_burst_ab(rng, n_docs):
    """Pure write-path A/B (PR 16): one corpus through a fresh 2-shard
    in-memory engine index via batched `_bulk` + one refresh — auto
    analysis (native/batched/device per backend, depth-1 analyze/build
    overlap across the 2 shard builders) vs the ES_TPU_ANALYZE=host
    per-doc oracle. No search load: this isolates the ingest docs/s the
    closed loop can't (there, wall is search-bound). The refresh
    profiles carry the overlap timestamps the acceptance asks for."""
    from elasticsearch_tpu.engine.engine import Engine
    from elasticsearch_tpu.monitoring.refresh_profile import recorder_for

    lens2, tok2 = build_corpus(rng, n_docs=n_docs)
    term_strs = np.array([f"t{i}" for i in range(VOCAB)])
    doc_terms = term_strs[tok2]
    bodies = []
    off = 0
    for ln in lens2:
        bodies.append(" ".join(doc_terms[off:off + ln]))
        off += ln
    def one_run(env):
        saved = os.environ.pop("ES_TPU_ANALYZE", None)
        if env:
            os.environ["ES_TPU_ANALYZE"] = env
        try:
            engine = Engine(None)
            idx = engine.create_index(
                "ingest_ab", {"properties": {"body": {"type": "text"}}},
                settings={"number_of_shards": 2})
            t0 = time.perf_counter()
            chunk = 1000
            for s in range(0, len(bodies), chunk):
                ops = [("index", "ingest_ab", f"d{s + j}", {"body": b})
                       for j, b in enumerate(bodies[s:s + chunk])]
                res = engine.bulk(ops)
                assert not res["errors"], res
            idx.refresh()
            wall = time.perf_counter() - t0
            profs = recorder_for(idx).profiles()["profiles"]
            stages: dict = {}
            overlap = 0.0
            for p in profs:
                for k, v in (p.get("stages_ms") or {}).items():
                    stages[k] = stages.get(k, 0.0) + v
                overlap += p.get("analyze_overlap_ms", 0.0)
            return {
                "wall_ms": round(wall * 1e3, 1),
                "docs_per_s": round(len(bodies) / wall, 1),
                "stages_ms": {k: round(v, 2) for k, v in stages.items()},
                "analyze_overlap_ms": round(overlap, 2),
            }
        finally:
            os.environ.pop("ES_TPU_ANALYZE", None)
            if saved is not None:
                os.environ["ES_TPU_ANALYZE"] = saved

    # One untimed pass compiles the build-kernel shape family (csr
    # scatter, impact quantize) so neither timed arm pays the one-time
    # XLA compile — the preflight discipline applied to the write path.
    # Then alternate the arms over REPS repetitions and keep each arm's
    # best (min-wall) rep: on a shared CPU host the run-to-run scatter
    # (~15% of wall from scheduler/allocator noise) exceeds the ~10%
    # analysis delta, and the min statistic is the standard way to read
    # through it (the per-rep walls are recorded so the scatter is
    # visible, not hidden).
    one_run(None)
    reps = 3
    arms = (("batched_auto", None), ("host_perdoc", "host"))
    runs: dict = {label: [] for label, _ in arms}
    for _ in range(reps):
        for label, env in arms:
            runs[label].append(one_run(env))
    out = {}
    for label, _ in arms:
        best = min(runs[label], key=lambda r: r["wall_ms"])
        best["rep_walls_ms"] = [r["wall_ms"] for r in runs[label]]
        out[label] = best
    out["ingest_speedup"] = round(
        out["host_perdoc"]["wall_ms"]
        / max(out["batched_auto"]["wall_ms"], 1e-9), 2)
    return out


def config7_mixed(rng):
    """C7 closed-loop mixed read/write arm (ROADMAP item 2 done-
    criterion, PR 15): N writer clients sustain bursts + refreshes while
    512 search clients run closed-loop through the serving front end —
    writes build LSM tail segments with the DEVICE build kernels, and
    background segment folds ride the serving queue as the low-weight
    `_merge` tenant, so heavy indexing and heavy search share the chip
    under one scheduler. Records: search QPS + p50/p99 against the
    `slo.*` floors, sustained docs/s ingest (wall + recorder EMA),
    tail-tier fraction samples (bounded), segment/fold counters, and
    the per-kernel mfu/bw_util of the `build.*` device stages through
    the PR-13 cost-model entries. CPU smokes are host-bound as always —
    TPU is the criterion (BENCH_NOTES round 19)."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from elasticsearch_tpu.engine.engine import Engine

    smoke = bool(os.environ.get("ES_BENCH_SMOKE"))
    n_docs = 4_000 if smoke else 100_000
    n_search_clients = 64 if smoke else 512
    n_writers = 2 if smoke else 8
    reqs_per_client = 4
    n_reqs = n_search_clients * reqs_per_client
    docs_per_burst = 32

    log(f"[c7] building {n_docs}-doc engine index...")
    lens, tok = build_corpus(rng, n_docs=n_docs)
    # in-memory engine: per-doc WAL fsync would measure the filesystem,
    # not the build path this arm grades (documented basis)
    engine = Engine(None)
    idx = engine.create_index(
        "c7", {"properties": {"body": {"type": "text"}}})
    term_strs = np.array([f"t{i}" for i in range(VOCAB)])
    doc_terms = term_strs[tok]
    off = 0
    for ln in lens:
        idx.index_doc(None, {"body": " ".join(doc_terms[off:off + ln])})
        off += ln
    idx.refresh()
    idx.searcher  # sealed base: writers build tail segments beside it

    pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="c7-engine")
    svc = engine.serving
    svc.bind_executor(pool.submit)
    svc.set_enabled(True)
    # the write SLO floors this arm is graded against (slo.write.* —
    # prebuilt watch fires on breach in production)
    floors = {"search_p99_ms": float(
        engine.settings.get("slo.search.p99_ms") or 0) or 60_000.0,
        "write_tail_fraction": 0.5, "write_refresh_lag_ms": 30_000.0}
    engine.settings.update({"transient": {
        "slo.write.tail_fraction": floors["write_tail_fraction"],
        "slo.write.refresh_lag_ms": floors["write_refresh_lag_ms"]}})

    qs = sample_queries(rng, lens, tok, n_reqs, terms_per_query=3)
    bodies = [{"query": {"match": {"body": " ".join(t for t, _ in q)}},
               "size": TOP_K} for q in qs]
    entries = [svc.classify("c7", b, {}) for b in bodies]
    assert all(e is not None for e in entries), "stream must be wave-eligible"
    for burst in (1, 8, min(64, n_search_clients)):  # compile warm
        futs = [svc.submit(dict(entries[i]), tenant="warm")
                for i in range(burst)]
        for f in futs:
            f.result(timeout=600)

    # ---- closed-loop mixed run ------------------------------------------
    from elasticsearch_tpu.telemetry import metrics as _metrics

    stop_writers = threading.Event()
    written = {"docs": 0}
    wlock = threading.Lock()
    tail_samples: list[float] = []
    lag_samples: list[float] = []

    def _write_burst(wid, burst_no, n):
        # one batched _bulk per burst (PR 16): index-name resolution and
        # pipeline-settings lookups amortize across the run instead of
        # repeating per doc — the log/metrics-firehose front door
        ops = [("index", "c7", f"c7w{wid}_{burst_no}_{j}",
                {"body": " ".join(
                    f"t{int(x)}" for x in
                    np.random.default_rng(
                        wid * 100_003 + burst_no * 131 + j)
                    .integers(0, VOCAB, 8))})
               for j in range(n)]
        res = engine.bulk(ops)
        assert not res["errors"], res
        idx.refresh()

    def writer(wid):
        burst_no = 0
        while not stop_writers.is_set():
            pool.submit(_write_burst, wid, burst_no,
                        docs_per_burst).result(timeout=600)
            with wlock:
                written["docs"] += docs_per_burst
            st = engine.indexing_stats()
            tail_samples.append(st["tail_fraction"])
            lag_samples.append(st["refresh_lag_ms"])
            burst_no += 1

    lat_ms = [0.0] * n_reqs
    it = iter(range(n_reqs))
    slock = threading.Lock()

    def search_client(cid):
        while True:
            with slock:
                i = next(it, None)
            if i is None:
                return
            t0 = time.perf_counter()
            r = svc.submit(dict(entries[i]),
                           tenant=f"client-{cid % 8}").result(timeout=600)
            lat_ms[i] = (time.perf_counter() - t0) * 1e3
            assert "hits" in r

    snap0 = _metrics.snapshot()
    writers = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    searchers = [threading.Thread(target=search_client, args=(c,))
                 for c in range(n_search_clients)]
    t_all = time.perf_counter()
    for t in writers + searchers:
        t.start()
    for t in searchers:
        t.join()
    stop_writers.set()
    for t in writers:
        t.join()
    elapsed = time.perf_counter() - t_all
    # let any queued background fold drain before reading final state
    svc.drain(timeout_s=60)
    snap1 = _metrics.snapshot()
    qps = n_reqs / elapsed
    ingest_rate = written["docs"] / elapsed
    log(f"[c7] {n_reqs} searches + {written['docs']} writes / "
        f"{elapsed:.2f}s = {qps:.0f} search QPS @ {ingest_rate:.0f} docs/s")

    # ---- readouts --------------------------------------------------------
    from elasticsearch_tpu.monitoring.costmodel import device_peaks

    peak_f, peak_b, kind = device_peaks()
    bc, ac = snap0["counters"], snap1["counters"]
    bh, ah = snap0["histograms"], snap1["histograms"]
    build_util = {}
    for name, v in ac.items():
        if not (name.startswith("es.kernel.build.")
                and name.endswith(".flops")):
            continue
        kern = name[len("es.kernel."):-len(".flops")]
        flops = v - bc.get(name, 0.0)
        byts = (ac.get(f"es.kernel.{kern}.bytes", 0.0)
                - bc.get(f"es.kernel.{kern}.bytes", 0.0))
        ms = (ah.get(f"es.kernel.{kern}.ms", {}).get("sum", 0.0)
              - bh.get(f"es.kernel.{kern}.ms", {}).get("sum", 0.0))
        if ms <= 0 and flops <= 0:
            continue
        sec = max(ms / 1e3, 1e-9)
        build_util[kern] = {"ms": round(ms, 3),
                            "mfu": round(flops / sec / peak_f, 6),
                            "bw_util": round(byts / sec / peak_b, 6)}

    latency = _hist_pcts("bench.c7.search.ms", lat_ms)
    ind = engine.indexing_stats()
    st = svc.stats()
    tiers = idx.tier_stats()
    # correctness gate: every acknowledged write is visible after the
    # final refresh (writers refreshed each burst; a last refresh folds
    # the residue)
    pool.submit(idx.refresh).result(timeout=600)
    total = pool.submit(
        lambda: idx.search(query={"match_all": {}}, size=1)
        ["hits"]["total"]["value"]).result(timeout=600)
    assert total == n_docs + written["docs"], (total, written)

    max_tail = max(tail_samples, default=0.0)
    result = {
        "docs": n_docs,
        "writers": n_writers,
        "search_clients": n_search_clients,
        "requests": n_reqs,
        "docs_written": written["docs"],
        "search": {
            "qps": round(qps, 1),
            "latency": latency,
        },
        "ingest": {
            "docs_per_s": round(ingest_rate, 1),
            "docs_per_s_ema": ind.get("docs_per_s_ema"),
            "refresh_kinds": ind.get("refresh_kinds"),
            "refresh_lag_ms_max": round(max(lag_samples, default=0.0), 2),
            "analyze": _analyze_readout(idx, ind),
            "burst_ab": _ingest_burst_ab(rng, n_docs),
        },
        "tiers": {
            "tail_fraction_max": round(max_tail, 6),
            "tail_fraction_final": tiers["tail_fraction"],
            "segments_final": tiers["segments"],
            "segment_merges": idx.counters.get("segment_merge_total", 0),
            "merge_failures": idx.counters.get("merge_failures", 0),
            "merge_waves": st.get("merges", 0),
        },
        "slo": {
            "floors": floors,
            "search_p99_within": latency["p99_ms"]
            <= floors["search_p99_ms"],
            "tail_fraction_within": max_tail
            <= floors["write_tail_fraction"],
            "refresh_lag_within": max(lag_samples, default=0.0)
            <= floors["write_refresh_lag_ms"],
        },
        "device_utilization": {"device_kind": kind,
                               "kernels": build_util},
        "xla_cost_check": _xla_cost_check(set(build_util)),
        "basis": "in-memory engine (WAL fsync excluded — the arm grades "
                 "the build path); writers and waves share ONE engine "
                 "thread (the REST discipline); background segment folds "
                 "ride the serving queue as the `_merge` tenant; device "
                 "build kernels per index/device_build "
                 "(ES_TPU_DEVICE_BUILD)",
    }
    svc.stop()
    engine.close()
    pool.shutdown(wait=True)
    return result


def config8_superpack(rng):
    """C8 tenant-superpack arm (PR 17): ~1,000 SMALL tenant indices
    share size-class superpacks and serve through the SAME compiled
    tenant-gather programs, so compiled-program count is O(size-classes)
    instead of O(tenants). Phases: (1) build + fold every tenant,
    (2) row-level BIT parity of the tenant-gather lane vs the per-index
    sharded oracle on a tenant sample, (3) closed-loop serving QPS with
    superpacks ON, (4) the same request stream with superpacks OFF
    (per-index dispatch baseline) including service-level response
    parity on a sample. Records QPS-per-tenant and HBM-per-tenant for
    both dispatch modes, the compiled-program count against its
    size-class bound, and the `superpack.tenant_gather` cost-model
    cross-check. Half the tenants use a narrower vocabulary so TWO
    block size classes exist — the bucketing itself is exercised."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from elasticsearch_tpu.engine.engine import Engine
    from elasticsearch_tpu.parallel.sharded import msearch_sharded

    smoke = bool(os.environ.get("ES_BENCH_SMOKE"))
    n_tenants = 60 if smoke else 1000
    docs_per_tenant = 24
    n_search_clients = 32 if smoke else 256
    reqs_per_client = 4
    n_reqs = n_search_clients * reqs_per_client
    env_prev = os.environ.get("ES_TPU_SUPERPACK")
    os.environ["ES_TPU_SUPERPACK"] = "1"
    try:
        log(f"[c8] building {n_tenants} small tenant indices...")
        engine = Engine(None)
        names = []
        t_build = time.perf_counter()
        for t in range(n_tenants):
            trng = np.random.default_rng(10_000 + t)
            # alternate vocab width -> two block size classes on purpose
            vocab = 40 if t % 2 else 20
            name = f"tenant{t:04d}"
            engine.create_index(
                name, {"properties": {"body": {"type": "text"}}})
            ops = [("index", name, str(j),
                    {"body": " ".join(
                        f"w{int(x)}" for x in trng.integers(0, vocab, 6))})
                   for j in range(docs_per_tenant)]
            res = engine.bulk(ops)
            assert not res["errors"], res
            engine.indices[name].refresh()
            names.append(name)
        build_s = time.perf_counter() - t_build

        mgr = engine.superpacks
        t_fold = time.perf_counter()
        adopted = sum(1 for n_ in names
                      if mgr.adopt(engine.indices[n_]))
        fold_s = time.perf_counter() - t_fold
        assert adopted == n_tenants, (adopted, n_tenants)
        st0 = mgr.stats()
        n_classes = st0["size_classes"]
        assert n_classes >= 2, st0  # the bucketing is actually exercised
        log(f"[c8] {adopted} tenants folded into {n_classes} size "
            f"classes in {fold_s:.2f}s")

        # ---- row-level bit parity vs the per-index sharded oracle -------
        sample = names[:: max(1, n_tenants // 50)]
        queries = [[("w3", 1.0), ("w7", 1.0)], [("w1", 1.0)]]
        for name in sample:
            ss = engine.indices[name]._searcher
            v_sp, _, i_sp, t_sp = mgr.msearch(name, "body", queries, TOP_K)
            v_px, _, i_px, t_px = msearch_sharded(ss, "body", queries,
                                                  TOP_K)
            kk = min(v_sp.shape[-1], v_px.shape[-1])
            assert np.array_equal(
                np.asarray(v_sp)[..., :kk].view(np.uint32),
                np.asarray(v_px)[..., :kk].view(np.uint32)), name
            assert np.array_equal(np.asarray(i_sp)[..., :kk],
                                  np.asarray(i_px)[..., :kk]), name
            assert np.array_equal(np.asarray(t_sp), np.asarray(t_px)), name

        # ---- serving closed loop: superpack ON --------------------------
        pool = ThreadPoolExecutor(max_workers=1,
                                  thread_name_prefix="c8-engine")
        svc = engine.serving
        svc.bind_executor(pool.submit)
        svc.set_enabled(True)
        bodies = [{"query": {"match": {
            "body": f"w{i % 20} w{(i * 7) % 20}"}}, "size": TOP_K}
            for i in range(n_reqs)]
        entries = [svc.classify(names[i % n_tenants], b, {})
                   for i, b in enumerate(bodies)]
        assert all(e is not None for e in entries)

        def _closed_loop():
            lat = [0.0] * n_reqs
            out = [None] * n_reqs
            it = iter(range(n_reqs))
            lk = threading.Lock()

            def client(cid):
                while True:
                    with lk:
                        i = next(it, None)
                    if i is None:
                        return
                    t0 = time.perf_counter()
                    r = svc.submit(dict(entries[i]),
                                   tenant=names[i % n_tenants]) \
                        .result(timeout=600)
                    lat[i] = (time.perf_counter() - t0) * 1e3
                    out[i] = r
            ths = [threading.Thread(target=client, args=(c,))
                   for c in range(n_search_clients)]
            t_all = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            return n_reqs / (time.perf_counter() - t_all), lat, out

        for i in range(min(32, n_reqs)):  # compile warm
            svc.submit(dict(entries[i]), tenant="warm").result(timeout=600)
        qps_on, lat_on, out_on = _closed_loop()
        svc.drain(timeout_s=60)
        programs = mgr.compiled_program_count()
        # the tentpole contract: programs bounded by size classes x wave
        # shape tiers (Q pow2 tiers), NEVER by tenant count
        bound = n_classes * 8
        assert programs <= bound, (programs, bound)
        assert programs < n_tenants, (programs, n_tenants)
        st1 = mgr.stats()

        # ---- the same stream, per-index dispatch (superpack OFF) --------
        os.environ["ES_TPU_SUPERPACK"] = "0"
        for i in range(min(32, n_reqs)):
            svc.submit(dict(entries[i]), tenant="warm").result(timeout=600)
        qps_off, lat_off, out_off = _closed_loop()
        svc.drain(timeout_s=60)
        parity_n = min(64, n_reqs)
        for i in range(parity_n):  # service-level response parity
            assert out_on[i]["hits"] == out_off[i]["hits"], i
        hbm_px = [sum(int(a.nbytes) for a in
                      engine.indices[n_]._searcher.dev.values()
                      if hasattr(a, "nbytes"))
                  for n_ in names]

        latency_on = _hist_pcts("bench.c8.superpack.ms", lat_on)
        latency_off = _hist_pcts("bench.c8.per_index.ms", lat_off)
        tattr = _tenant_attribution(svc, engine)
        result = {
            "tenants": n_tenants,
            "docs_per_tenant": docs_per_tenant,
            "build_s": round(build_s, 2),
            "fold_s": round(fold_s, 2),
            "size_classes": n_classes,
            "compiled_programs": programs,
            "program_bound": bound,
            "parity": {
                "row_bitwise_tenants": len(sample),
                "service_responses": parity_n,
                "equal": True,  # asserted above
            },
            "superpack": {
                "qps": round(qps_on, 1),
                "qps_per_tenant": round(qps_on / n_tenants, 4),
                "latency": latency_on,
                "hbm_bytes_per_tenant": st1["hbm_bytes_per_tenant"],
                "padded_waste_pct": st1["padded_waste_pct"],
                "folds": mgr.counters.get("folds", 0),
            },
            "per_index": {
                "qps": round(qps_off, 1),
                "qps_per_tenant": round(qps_off / n_tenants, 4),
                "latency": latency_off,
                "hbm_bytes_per_tenant": int(np.mean(hbm_px)),
            },
            "qps_vs_per_index": round(qps_on / max(qps_off, 1e-9), 3),
            "tenant_attribution": tattr,
            "xla_cost_check": _xla_cost_check({"superpack.tenant_gather"}),
            "basis": "in-memory engine; one engine thread (REST "
                     "discipline); ON/OFF toggled via ES_TPU_SUPERPACK "
                     "between identical request streams; HBM-per-tenant "
                     "= shared-pack bytes / members (superpack) vs mean "
                     "per-index device bytes (baseline); CPU smokes are "
                     "host-bound — TPU is the criterion",
        }
        svc.stop()
        engine.close()
        pool.shutdown(wait=True)
        return result
    finally:
        if env_prev is None:
            os.environ.pop("ES_TPU_SUPERPACK", None)
        else:
            os.environ["ES_TPU_SUPERPACK"] = env_prev


def config9_planner(rng):
    """C9 adaptive-planner mixed-trace arm (PR 18, ROADMAP item 4): one
    interleaved C1 (match) + C4 (kNN) + C7 (write burst + refresh)
    request trace is replayed under FOUR routings — the three static
    arm pins (fused / impact / exact, via planner repricers, the
    planner's model mode off) and the adaptive planner (model mode on,
    efficiency EMAs warmed by the static passes' own `time_kernel`
    observations). Each routing runs on a freshly built engine index
    (identical corpus + trace), so the only variable is the routing.
    Records per-routing QPS + p50/p99 and arm-decision counts, the
    planner's decision-latency percentiles (the < 100 µs budget), and
    the residual distribution (histogram pcts + per-kernel |residual|
    EMA). The acceptance read: planner QPS >= every static routing
    (equal-p99 basis) within the CPU-smoke noise floor."""
    from elasticsearch_tpu.engine.engine import Engine
    from elasticsearch_tpu.planner import execution_planner
    from elasticsearch_tpu.telemetry import metrics as _metrics

    smoke = bool(os.environ.get("ES_BENCH_SMOKE"))
    n_docs = 2_000 if smoke else 50_000
    dims = 16 if smoke else 64
    n_ops = 48 if smoke else 400
    n_warm = 6
    prev_fused = os.environ.get("ES_TPU_FUSED")
    prev_impact = os.environ.get("ES_TPU_IMPACT")
    os.environ["ES_TPU_FUSED"] = "force"   # all three arms eligible on
    os.environ["ES_TPU_IMPACT"] = "force"  # CPU (impact is auto=TPU-only)
    pl = execution_planner()

    log(f"[c9] building {n_docs}-doc mixed corpus (text + {dims}-d vectors)")
    lens, tok = build_corpus(rng, n_docs=n_docs)
    term_strs = np.array([f"t{i}" for i in range(VOCAB)])
    doc_terms = term_strs[tok]
    starts = np.concatenate([[0], np.cumsum(lens[:-1])])
    vecs = rng.normal(size=(n_docs, dims)).astype(np.float32)
    qs = sample_queries(rng, lens, tok, n_ops + n_warm, terms_per_query=3)
    knn_qs = rng.normal(size=(n_ops + n_warm, dims)).astype(np.float32)

    def _op_kind(i):
        # 1-in-8 write burst (C7), 1-in-4 kNN (C4), the rest match (C1)
        return ("write" if i % 8 == 7 else
                "knn" if i % 4 == 2 else "match")

    def _build():
        from concurrent.futures import ThreadPoolExecutor

        engine = Engine(None)
        idx = engine.create_index("c9", {"properties": {
            "body": {"type": "text"},
            "vec": {"type": "dense_vector", "dims": dims,
                    "similarity": "l2_norm",
                    "index_options": {"type": "ivf", "nlist": 8}},
        }})
        for i in range(n_docs):
            s, ln = starts[i], lens[i]
            idx.index_doc(None, {
                "body": " ".join(doc_terms[s:s + ln]),
                "vec": [float(x) for x in vecs[i]]})
        idx.refresh()
        idx.searcher  # seal the base: the dense tier gates the fused arm
        # the serving front end is the arm-routed dispatch path (waves
        # run the executor msearch the planner sites live on); kNN and
        # writes ride the same single engine thread (REST discipline)
        pool = ThreadPoolExecutor(max_workers=1,
                                  thread_name_prefix="c9-engine")
        svc = engine.serving
        svc.bind_executor(pool.submit)
        svc.set_enabled(True)
        return engine, idx, svc, pool

    def _do_op(engine, idx, svc, pool, i, routing):
        kind = _op_kind(i)
        if kind == "match":
            body = {"query": {"match": {"body": " ".join(
                t for t, _ in qs[i])}}, "size": TOP_K}
            entry = svc.classify("c9", body, {})
            assert entry is not None, "match stream must be wave-eligible"
            r = svc.submit(entry, tenant="c9").result(timeout=600)
            assert "hits" in r
        elif kind == "knn":
            r = pool.submit(
                lambda: idx.search(knn={
                    "field": "vec",
                    "query_vector": [float(x) for x in knn_qs[i]],
                    "k": TOP_K})).result(timeout=600)
            assert "hits" in r
        else:
            def _burst():
                ops = [("index", "c9", f"c9_{routing}_{i}_{j}",
                        {"body": " ".join(
                            f"t{int(x)}" for x in
                            np.random.default_rng(i * 131 + j)
                            .integers(0, VOCAB, 8))})
                       for j in range(16)]
                res = engine.bulk(ops)
                assert not res["errors"], res
                idx.refresh()
                # fold the tail immediately (an aggressive merge
                # policy): unfolded tails push every wave entry onto
                # the tiered lane, which bypasses the arm-routed term
                # lane this config exists to measure
                idx.searcher
            pool.submit(_burst).result(timeout=600)

    pins = {"static_fused": (), "static_impact": ("fused",),
            "static_exact": ("fused", "impact"), "planner": ()}

    def _run(routing):
        engine, idx, svc, pool = _build()
        pl.configure(enabled=(routing == "planner"))
        for a in pins[routing]:
            pl.add_repricer(a, "bench-c9", lambda: True)
        try:
            for i in range(n_warm):  # compile warm, all op kinds
                _do_op(engine, idx, svc, pool, n_ops + i, routing + "_w")
            d0 = dict(pl.stats()["decisions"])
            lat = []
            t_all = time.perf_counter()
            for i in range(n_ops):
                t0 = time.perf_counter()
                _do_op(engine, idx, svc, pool, i, routing)
                lat.append((time.perf_counter() - t0) * 1e3)
            elapsed = time.perf_counter() - t_all
        finally:
            for a in pins[routing]:
                pl.remove_repricer(a, "bench-c9")
            svc.stop()
            engine.close()
            pool.shutdown(wait=True)
        d1 = pl.stats()["decisions"]
        decided = {a: d1.get(a, 0) - d0.get(a, 0)
                   for a in ("fused", "impact", "exact")
                   if d1.get(a, 0) - d0.get(a, 0)}
        return {"qps": round(n_ops / elapsed, 1),
                "latency": _hist_pcts(f"bench.c9.{routing}.ms", lat),
                "decisions": decided}

    try:
        routings = {}
        # static pins first: their time_kernel observations warm the
        # efficiency EMAs the adaptive pass then prices arms with
        for routing in ("static_fused", "static_impact", "static_exact",
                        "planner"):
            log(f"[c9] replaying trace under routing={routing}...")
            routings[routing] = _run(routing)
            log(f"[c9] {routing}: {routings[routing]}")
    finally:
        pl.configure(enabled=True)
        for key, prev in (("ES_TPU_FUSED", prev_fused),
                          ("ES_TPU_IMPACT", prev_impact)):
            if prev is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prev

    snap = _metrics.snapshot()["histograms"]
    dec_h = snap.get("es.planner.decision_us") or {}
    res_h = snap.get("es.planner.residual") or {}
    pst = pl.stats()
    residual_kernels = {
        k: {"abs_ema": st["residual_abs_ema"], "n": st["predictions"]}
        for k, st in pst["kernels"].items() if "residual_abs_ema" in st}
    planner_qps = routings["planner"]["qps"]
    static_best = max(v["qps"] for k, v in routings.items()
                      if k != "planner")
    return {
        "docs": n_docs,
        "trace_ops": n_ops,
        "op_mix": {"match": sum(_op_kind(i) == "match"
                                for i in range(n_ops)),
                   "knn": sum(_op_kind(i) == "knn"
                              for i in range(n_ops)),
                   "write_bursts": sum(_op_kind(i) == "write"
                                       for i in range(n_ops))},
        "routings": routings,
        "planner_vs_best_static": round(
            planner_qps / max(static_best, 1e-9), 4),
        "planner_matches_or_beats": planner_qps >= static_best * 0.9,
        "decision_us": {"p50": round(dec_h.get("p50", 0.0), 2),
                        "p90": round(dec_h.get("p90", 0.0), 2),
                        "p99": round(dec_h.get("p99", 0.0), 2),
                        "n": dec_h.get("count", 0),
                        "within_budget": dec_h.get("p50", 0.0) < 100.0},
        "residual": {"p50": round(res_h.get("p50", 0.0), 4),
                     "p90": round(res_h.get("p90", 0.0), 4),
                     "n": res_h.get("count", 0),
                     "kernels": residual_kernels},
        "basis": "identical interleaved trace per routing on a freshly "
                 "built in-memory engine index; static pins via planner "
                 "repricers (model mode off), adaptive pass EMA-warm "
                 "from the static passes' per-wave decision attribution "
                 "(flight recorder -> observe_wall); ES_TPU_FUSED="
                 "ES_TPU_IMPACT=force so all three arms stay eligible "
                 "on CPU; write bursts fold tails immediately so waves "
                 "stay on the arm-routed term lane; 10% noise tolerance "
                 "on the matches-or-beats read (CPU smokes are "
                 "host-bound — TPU is the criterion)",
    }


def config10_esql(rng):
    """C10 ESQL dataflow arm (PR 20, ROADMAP item 5 substrate): a
    FROM | WHERE | STATS | SORT query mix over a C3-style http_logs
    corpus driven through the profiled ESQL engine. Every query runs
    under `"profile": true`, so the record carries the per-operator
    wall decomposition (contiguous segments summing exactly to each
    query wall), the peak live materialization bytes (host table +
    HBM gauge at operator boundaries), and input rows/s per shape —
    the whole-column numbers the paged-operator port must beat on
    peak_bytes while holding rows/s."""
    from elasticsearch_tpu.engine.engine import Engine
    from elasticsearch_tpu.esql import esql_query

    smoke = bool(os.environ.get("ES_BENCH_SMOKE"))
    n = 4_000 if smoke else 200_000
    reps = 2 if smoke else 5
    log(f"[c10] building {n}-doc http_logs-like engine index...")
    engine = Engine(None)
    try:
        idx = engine.create_index("logs_esql", {"properties": {
            "status": {"type": "keyword"},
            "clientip": {"type": "keyword"},
            "@timestamp": {"type": "date"},
            "size": {"type": "long"},
        }})
        statuses = np.array(
            ["200", "200", "200", "200", "304", "404", "500", "301"])
        ips = rng.integers(0, 60_000, size=n)
        t0ms = 1_420_070_400_000
        times = t0ms + rng.integers(0, 30 * 86_400_000, size=n)
        sizes = rng.integers(100, 100_000, size=n)
        st = statuses[rng.integers(0, len(statuses), size=n)]
        chunk = 2_000
        for s in range(0, n, chunk):
            ops = [("index", "logs_esql", str(i), {
                "status": st[i],
                "clientip": (f"10.{ips[i] >> 8 & 255}"
                             f".{ips[i] & 255}.{ips[i] % 251}"),
                "@timestamp": int(times[i]),
                "size": int(sizes[i]),
            }) for i in range(s, min(s + chunk, n))]
            res = engine.bulk(ops)
            assert not res["errors"], res
        idx.refresh()
        queries = {
            "where_stats_sort": (
                'FROM logs_esql | WHERE size >= 50000 '
                '| STATS c = COUNT(*), b = SUM(size) BY status '
                '| SORT status'),
            "topn": ('FROM logs_esql | SORT size DESC | LIMIT 10 '
                     '| KEEP clientip, size'),
            "where_topn": (
                'FROM logs_esql | WHERE status == "404" '
                '| SORT size DESC | LIMIT 10 | KEEP clientip, size'),
            "eval_stats": ('FROM logs_esql | EVAL kb = size / 1024 '
                           '| STATS m = MAX(kb), a = AVG(kb)'),
        }
        out = {"n_docs": n, "reps": reps, "queries": {}}
        for name, q in queries.items():
            esql_query(engine, {"query": q})  # warm (jit, collect paths)
            best = None
            for _ in range(reps):
                prof = esql_query(engine, {"query": q,
                                           "profile": True})["profile"]
                if best is None or prof["wall_ms"] < best["wall_ms"]:
                    best = prof
            wall_s = best["wall_ms"] / 1e3
            out["queries"][name] = {
                "wall_ms": round(best["wall_ms"], 3),
                "rows_out": best["rows"],
                "input_rows_per_s": round(n / max(wall_s, 1e-9), 1),
                "peak_live_bytes": best["peak_live_bytes"],
                "dominant_operator": best["dominant_operator"],
                "operator_ms": {
                    o["operator"]: round(o["took_ms"], 3)
                    for o in best["drivers"][0]["operators"]},
                "operator_bytes": {
                    o["operator"]: o["bytes_materialized"]
                    for o in best["drivers"][0]["operators"]},
            }
            log(f"[c10] {name}: wall={best['wall_ms']:.1f}ms "
                f"peak={best['peak_live_bytes']}b "
                f"dom={best['dominant_operator']}")
        rec = engine.esql_recorder.stats()
        out["recorder"] = {
            "queries": rec["queries"],
            "peak_bytes_hwm": rec["peak_bytes_hwm"],
            "dominant_operator": rec["dominant_operator"],
            "breaker_trips": rec["breaker_trips"],
        }
        out["basis"] = (
            "per-query profile walls are the contiguous per-operator "
            "decomposition (sum == wall asserted in-engine); "
            "peak_live_bytes is host table bytes + HBM live gauge at "
            "operator boundaries — the whole-column materialization "
            "the item-5 paged port is graded against; best-of-reps "
            "per shape; CPU smokes are host-bound (non-criteria)")
        return out
    finally:
        engine.close()


def preflight():
    """Compile every kernel geometry the bench will dispatch BEFORE any
    timed run (VERDICT r3 #8: round 3 lost a config mid-bench to an
    x64-only Mosaic rejection that interpret-mode tests tolerate). AOT
    lowering from ShapeDtypeStructs needs no corpus: a compile failure
    surfaces here in seconds, not after the 1M-doc build."""
    import jax

    from elasticsearch_tpu.ops import fused as F
    from elasticsearch_tpu.ops.kernels import scan_topk_xla
    from elasticsearch_tpu.utils.jax_env import ensure_x64

    ensure_x64()
    if jax.default_backend() != "tpu":
        # the bench measures the chip; off it there is nothing to record
        # (tests/test_chip_compile.py holds the chip-less compiles)
        raise SystemExit(
            f"[preflight] backend is {jax.default_backend()!r}, not tpu: "
            "bench.py measures the device and does not fall back")
    jnp_sds = jax.ShapeDtypeStruct
    import jax.numpy as jnp

    compiled = 0
    qsub = F._cfg_qsub()
    # representative dense-tier width for the in-kernel-matmul geometry
    # (V ~ 896 at the 1M bench corpus; a Mosaic rejection is shape-class,
    # not exact-shape, so the approximation still catches it)
    vp2 = -(-2 * 896 // 128) * 128
    inkernel = F.fused_topk_enabled()
    tile_n = F._cfg_tile()
    if inkernel and os.environ.get("ES_TPU_FUSED_TILE") is None:
        tile_n = min(tile_n, F.auto_tile_matmul(vp2, qsub))
    for n_docs in sorted({N_DOCS, 20_000}):
        n_pad = ((n_docs + tile_n - 1) // tile_n) * tile_n
        njc = n_pad // tile_n
        njf = n_pad // F.FINE_N
        t = F.tile_t_for(njc)
        # the full bud quantization range of FusedTermSearcher._compiled
        # (bude in pow2 [2048, 65536]) — a bud-specific Mosaic rejection
        # is exactly the failure class this exists to catch
        for bud in (16, 32, 64, 128, 256, 512):
            rows = 8 * bud
            score_ops = (
                dict(scores=None,
                     w=jnp_sds((F.QC, vp2), jnp.bfloat16),
                     tstack=jnp_sds((vp2, n_pad), jnp.bfloat16))
                if inkernel
                else dict(scores=jnp_sds((F.QC, n_pad), jnp.float32))
            )
            fn = F.fused_tile_candidates.lower(
                live=jnp_sds((1, n_pad), jnp.float32),
                keys=jnp_sds((rows, 128), jnp.int32),
                vals=jnp_sds((rows, 128), jnp.int32),
                ptr=jnp_sds(((F.QC // qsub) * (njf + 1),), jnp.int32),
                t=t, bud=bud, tile_n=tile_n, qsub=qsub, interpret=False,
                **score_ops,
            )
            fn.compile()
            compiled += 1
    # tiered kNN selection kernel (c4) at its bench shape
    from elasticsearch_tpu.ops.kernels import (
        KB_TIERED, _pick_tiles, _tiered_candidates_pallas,
    )

    tiles = _pick_tiles(1024, 384, N_DOCS, KB_TIERED)
    if tiles is not None:
        _tiered_candidates_pallas.lower(
            jnp_sds((1024, 384), jnp.bfloat16),
            jnp_sds((384, N_DOCS), jnp.bfloat16),
            jnp_sds((384, N_DOCS), jnp.bfloat16),
            jnp_sds((N_DOCS,), jnp.bool_),
            jnp_sds((N_DOCS,), jnp.float32),
            jnp_sds((1024,), jnp.float32),
            kb=KB_TIERED, transform="cosine", count_positive=False,
            interpret=False, tiles=tiles,
        ).compile()
        compiled += 1
    # vector scan path (c4): pallas or xla depending on the score-bytes
    # threshold — compile the xla reference shape eagerly
    import functools

    jax.jit(functools.partial(
        scan_topk_xla, k=TOP_K, transform="cosine", count_positive=False,
    )).lower(
        jnp_sds((1024, 384), jnp.float32),
        jnp_sds((384, 200_000), jnp.float32),
        jnp_sds((200_000,), jnp.bool_),
        jnp_sds((200_000,), jnp.float32),
        jnp_sds((1024,), jnp.float32),
    ).compile()
    compiled += 1
    log(f"[preflight] {compiled} kernel geometries compiled")
    return compiled


def _summary_line(extras, partial: bool) -> str:
    """THE parseable record. Printed after EVERY config (partial=True) and
    once at the end, so the last JSON line on stdout always carries every
    config completed so far — a timeout can no longer zero the record
    (VERDICT r5 weak #1: BENCH_r05.json died rc=124/parsed=null with
    C1-C4 finished but unprinted)."""
    c1 = extras.get("match_bm25", {})
    body = {
        "metric": "bm25_match_top10_qps_1M_docs",
        "value": c1.get("qps", 0.0),
        "unit": "queries/s",
        "vs_baseline": c1.get("vs_baseline", 0.0),
        "extras": extras,
    }
    if partial:
        body["partial"] = True
    return json.dumps(body)


def _write_record(extras, partial: bool) -> None:
    """Write the record-so-far to ES_BENCH_RECORD (default
    ./bench_record.json) ATOMICALLY: serialize to a temp file in the same
    directory, fsync, rename. Called after EVERY config and from the
    signal handlers, so even an rc=124 that outraces the stdout flush
    leaves a complete, parseable JSON file of every finished config —
    the file can never exist half-written (rename is atomic) and never
    goes missing once the first config lands."""
    path = os.environ.get("ES_BENCH_RECORD", "bench_record.json")
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(_summary_line(extras, partial) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError as e:  # an unwritable record dir must not kill the run
        log(f"[bench] record write to {path} failed: {e}")


def main():
    # one or more config names (e.g. `bench.py c5 c6` -> ONE record
    # carrying both arms); no args = the full suite
    configs = set(sys.argv[1:]) or None

    def _want(name):
        return configs is None or name in configs

    from elasticsearch_tpu.utils.jax_env import enable_compile_cache

    enable_compile_cache()
    n_preflight = preflight()
    rng = np.random.default_rng(42)
    log(f"[corpus] generating {N_DOCS} docs...")
    lens, tok = build_corpus(rng)
    extras = {"preflight_geometries": n_preflight}

    def _flush_record(signum, frame):
        # SIGTERM/SIGALRM (driver timeout): flush the record-so-far as
        # the final line before dying (stdout AND the atomic record file)
        _write_record(extras, partial=True)
        print(_summary_line(extras, partial=True), flush=True)
        log(f"[bench] killed by signal {signum}; partial record flushed")
        os._exit(124)

    signal.signal(signal.SIGTERM, _flush_record)
    signal.signal(signal.SIGALRM, _flush_record)

    def _guard(name, fn):
        """One config's crash must never cost the whole bench line, and
        every completed config is flushed to stdout IMMEDIATELY as part
        of a full (partial-marked) summary line."""
        try:
            extras[name] = fn()
            log(f"[{name}] {extras[name]}")
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc(file=sys.stderr)
            extras[name] = {"error": f"{type(e).__name__}: {e}"}
        _write_record(extras, partial=True)  # temp-file + rename per config
        print(_summary_line(extras, partial=True), flush=True)

    if _want("c1"):
        log("[pack] building 1M-doc text pack...")
        t0 = time.perf_counter()
        # build_profile (PR 13): the C1 host-build baseline record — the
        # per-stage split the item-2 device port is graded against.
        # Corpus string materialization happens before the timed region
        # (PR 16): it is generator work, not ingest
        _c1_docs = corpus_docs(lens, tok)
        (pack, m), c1_build = _build_profile_arm(
            lambda: build_pack(lens, tok, docs=_c1_docs), N_DOCS)
        extras.setdefault("build_profile", {})["c1_pack"] = c1_build
        _write_record(extras, partial=True)
        log(f"[pack] built in {time.perf_counter()-t0:.0f}s; "
            f"dense tier {None if pack.dense_tfn is None else pack.dense_tfn.shape}; "
            f"stages {c1_build['stages_ms']}")
        from elasticsearch_tpu.query.executor import ShardSearcher

        searcher = ShardSearcher(pack, mappings=m)
        _guard("match_bm25",
               lambda: config1_match(searcher, m, lens, tok, rng))
        del searcher, pack
        gc.collect()

    if _want("c3"):
        _guard("terms_date_histogram", lambda: config3_aggs(rng))
        gc.collect()

    if _want("c4"):
        _guard("knn_cosine_exact", lambda: config4_knn(rng))
        gc.collect()

    if _want("c5"):
        _guard("msearch_8shard", lambda: config5_8shard(rng))
        c1q = extras.get("match_bm25", {}).get("qps")
        if c1q and "error" not in extras.get("msearch_8shard", {}):
            extras["msearch_8shard"]["c1_single_chip_1m_qps"] = c1q

    if _want("c6"):
        _guard("serving_closed_loop", lambda: config6_serving(rng))
        gc.collect()

    if _want("c7"):
        _guard("mixed_read_write", lambda: config7_mixed(rng))
        gc.collect()

    if _want("c8"):
        _guard("tenant_superpack", lambda: config8_superpack(rng))
        gc.collect()

    if _want("c9"):
        _guard("planner_mixed_trace", lambda: config9_planner(rng))
        gc.collect()

    if _want("c10"):
        _guard("esql_dataflow", lambda: config10_esql(rng))
        gc.collect()

    _write_record(extras, partial=False)
    print(_summary_line(extras, partial=False))
    failed = sorted(n for n, v in extras.items()
                    if isinstance(v, dict) and "error" in v)
    if failed:
        log(f"[bench] configs failed: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
