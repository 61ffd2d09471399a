"""Analytic per-kernel cost model: FLOPs + bytes moved per dispatch.

The reference never needs this — its hot loop is a CPU doc-at-a-time
iterator and its monitoring collectors read JVM stats
(monitor/jvm/JvmStats.java). A device engine is judged differently: a
kernel is "fast" only as a fraction of the chip's peak (VERDICT r5: C4
kNN at ~2% of roofline; BM25S https://arxiv.org/pdf/2407.03618 and
GPUSparse https://arxiv.org/pdf/2606.26441 both report achieved-vs-peak,
not QPS alone). This module derives FLOPs and HBM traffic from the
shapes/dtypes already in hand at each dispatch site; telemetry.time_kernel
divides them by the measured wall time and the device's peak rates to
report achieved MFU and bandwidth utilization per kernel per call.

Conventions (documented, asserted by tests/test_monitoring.py):
  - a matmul [M,K]@[K,N] is 2*M*K*N FLOPs per pass (multiply+add);
  - selection/compare work counts 2 ops per scanned element (compare +
    select) — top-k is bandwidth-bound, the ops term keeps its MFU
    honest instead of zero;
  - bytes = operand reads + result writes at their storage dtypes, each
    operand counted ONCE (tiled re-reads from VMEM are free by design —
    that is what the kernels are shaped to guarantee);
  - MFU is reported against the device's peak *bf16* matmul rate (the
    chip's headline number) regardless of compute dtype, so an f32 path
    can never look better than the bf16 path it competes with.

Every `time_kernel` dispatch name in ops/, parallel/, query/ and ann/
MUST have an entry in KERNEL_COSTS (tier-1 lint: test_monitoring.py
walks the call sites). An entry of None marks a wrapper span whose inner kernels carry
the accounting — a deliberate choice, not a missing model.
"""

from __future__ import annotations

import math
import os

# ---------------------------------------------------------------------------
# device peak rates
# ---------------------------------------------------------------------------

# device_kind, exactly as `jax.devices()[0].device_kind` reports it ->
# (peak bf16 matmul FLOP/s, peak HBM bytes/s, peak per-chip ICI bytes/s —
# links x per-link rate, both directions summed the way the HBM number
# is). One row per kind that has been seen on a chip, with the public
# spec-sheet numbers of that chip. A TPU kind missing here is an error,
# never a default: a new device must not inherit another's roofline, and
# its row is added with the string the device itself reports.
DEVICE_PEAKS: dict[str, tuple[float, float, float]] = {
    # v5e: kind as reported on the chip (chip_smoke.py, PR 22); peaks
    # from the Cloud TPU v5e page (197 TFLOP/s bf16, 819 GB/s HBM,
    # 4 ICI links x 400 Gbps)
    "TPU v5 lite": (197e12, 819e9, 200e9),
}

# CPU fallback: a nominal 32-vCPU host (AVX2 f32 FMA ~100 GFLOP/s/core
# is generous; utilization numbers on CPU are illustrative only — the
# cost model's flops/bytes stay exact, only the denominator is nominal)
CPU_PEAK_FLOPS = 3.2e12
CPU_PEAK_BW = 100e9

# virtual CPU meshes move "collectives" through memcpy; nominal only
CPU_PEAK_ICI = 50e9


def ici_peak() -> float:
    """-> peak ICI bytes/s of the resident device kind (ES_TPU_PEAK_ICI
    overrides; CPU/virtual meshes get the nominal memcpy figure)."""
    env = os.environ.get("ES_TPU_PEAK_ICI")
    if env:
        return float(env)
    _f, _b, kind = device_peaks()
    row = DEVICE_PEAKS.get(kind)
    return row[2] if row is not None else CPU_PEAK_ICI

_peaks_cache: tuple[float, float, str] | None = None


def device_peaks() -> tuple[float, float, str]:
    """-> (peak_flops, peak_bytes_per_s, device_kind). Environment
    overrides ES_TPU_PEAK_FLOPS / ES_TPU_PEAK_BW win (a new device kind
    must not silently inherit another's roofline)."""
    global _peaks_cache
    if _peaks_cache is not None and not (
            os.environ.get("ES_TPU_PEAK_FLOPS")
            or os.environ.get("ES_TPU_PEAK_BW")):
        return _peaks_cache
    import jax

    d = jax.devices()[0]
    kind = getattr(d, "device_kind", d.platform) or d.platform
    flops, bw = CPU_PEAK_FLOPS, CPU_PEAK_BW
    if d.platform == "tpu":
        if kind not in DEVICE_PEAKS:
            raise ValueError(
                f"no peak rates for TPU device kind [{kind}] — add its "
                "row to monitoring/costmodel.DEVICE_PEAKS")
        flops, bw, _ici = DEVICE_PEAKS[kind]
    env_f = os.environ.get("ES_TPU_PEAK_FLOPS")
    env_b = os.environ.get("ES_TPU_PEAK_BW")
    if env_f:
        flops = float(env_f)
    if env_b:
        bw = float(env_b)
    out = (flops, bw, kind)
    if not (env_f or env_b):
        _peaks_cache = out
    return out


# ---------------------------------------------------------------------------
# primitive costs (the unit-tested building blocks)
# ---------------------------------------------------------------------------

def matmul_cost(m: int, k: int, n: int, *, passes: int = 1,
                a_bytes: int = 2, b_bytes: int = 2,
                out_bytes: int = 4) -> dict:
    """[M,K]@[K,N] done `passes` times (the split-bf16 tier runs 2 logical
    passes: Wh@T16 + Wh@T16lo). Each pass re-reads both operands (they are
    distinct arrays in the split scheme) and the result is written once."""
    return {
        "flops": 2.0 * m * k * n * passes,
        "bytes": float(passes * (m * k * a_bytes + k * n * b_bytes)
                       + m * n * out_bytes),
    }


def topk_scan_cost(q: int, n: int, *, score_bytes: int = 4) -> dict:
    """Top-k over a [q, n] score field: one read of the scores, 2 ops
    (compare + select) per element. What is kept beside the pass (a running
    top-k in VMEM; the k candidate blocks of ops/scoring.top_k_with_total)
    is small against it, so k does not appear."""
    return {
        "flops": 2.0 * q * n,
        "bytes": float(q * n * score_bytes),
    }


def sparse_bm25_cost(rows: int, *, block: int = 128,
                     lane_bytes: int = 12, out_n: int = 0) -> dict:
    """Blocked-CSR BM25 over `rows` posting blocks: each [BLOCK] lane is
    one (docid i32, tf f32, dl f32) read = 12 bytes, scored by ~6 FLOPs
    (mul, add, mul, add, div, mul — ops/scoring.score_posting_arrays) and
    scatter-added (1 op). out_n > 0 adds the dense accumulator write."""
    lanes = rows * block
    return {
        "flops": 7.0 * lanes,
        "bytes": float(lanes * lane_bytes + out_n * 4),
    }


def impact_gather_cost(q_rows: int, *, block: int = 128,
                       code_bytes: int = 2) -> dict:
    """Impact-tier gather+dequant (ops/kernels.impact_gather): each lane
    reads (docid i32 + code u16|i8) = 4 + code_bytes and writes the
    (docid i32, score f32) candidate pair = 8 bytes; 1 FLOP/lane (the
    dequant multiply) + 1 op of lane bookkeeping. q_rows = total gathered
    block rows across the batch (Q·Ts·B). Compare sparse_bm25_cost's
    12 B + 7 FLOPs/lane — the bytes/query argument of the BM25S tier."""
    lanes = q_rows * block
    return {
        "flops": 2.0 * lanes,
        "bytes": float(lanes * (4 + code_bytes + 8)),
    }


def impact_sum_cost(q: int, n: int, *, cands: int = 0) -> dict:
    """The impact arm's candidate tail (fast_topk_from_candidates): the
    dominating terms are the [q, cands] multi-operand sort (modeled as
    log2(cands) compare+select passes over the 8-byte (docid, score)
    lanes) and the dense-tier selection scan over [q, n]."""
    import math

    parts = [topk_scan_cost(q, n)]
    if cands:
        passes = max(1.0, math.log2(max(cands, 2)))
        parts.append({
            "flops": 2.0 * q * cands * passes,
            "bytes": float(q * cands * 8 * 3),  # read+sort+write passes
        })
    return _merge(*parts)


def knn_tiered_cost(b: int, d: int, n: int, *, kb: int = 128) -> dict:
    """TieredKnnScanner (ops/vector): 2 bf16 matmul passes over the split
    [D, N] corpus (hi + lo halves), then an f32 rescore of the [b, kb]
    survivors (gather [b, kb, D] rows + one einsum)."""
    sel = matmul_cost(b, d, n, passes=2, a_bytes=2, b_bytes=2, out_bytes=0)
    resc_flops = 2.0 * b * kb * d
    resc_bytes = float(b * kb * d * 4 + b * kb * 8)
    return {
        "flops": sel["flops"] + resc_flops + 2.0 * b * n,  # + selection scan
        "bytes": sel["bytes"] + resc_bytes,
    }


def ann_gather_scan_cost(b: int, p: int, l: int, d: int, *,
                         tier: str = "int8") -> dict:
    """The batched ANN gather-scan (ann/kernels): every (query, probed
    cluster) pair DMAs its [L, D] tile at the tier's storage dtype —
    int8 codes + 8 B/slot scale+offset, or the split-bf16 hi+lo pair at
    4D B/slot — plus 12 B/slot of order/live/aux metadata. Unlike the
    full-corpus scans, tiles ARE re-read per probing query (that is the
    gather), so bytes scale with b*p*l, not the corpus. FLOPs: the
    quantized matmul (2*slots*d), the int8 affine correction or the
    second bf16 pass, and 2 ops/slot of selection."""
    slots = float(b * p * l)
    if tier == "int8":
        tile_bytes = slots * (d * 1 + 8)
        mm_flops = 2.0 * slots * d + 2.0 * slots  # matmul + affine fma
    else:  # bf16 hi+lo pair: two passes over 2-byte tiles
        tile_bytes = slots * (2 * d * 2)
        mm_flops = 2.0 * 2.0 * slots * d
    return {
        "flops": mm_flops + 2.0 * slots,  # + selection scan
        "bytes": tile_bytes + slots * 12 + b * d * 4,
    }


def ann_rescore_cost(b: int, kb: int, d: int) -> dict:
    """f32 rescore of ANN survivors: [b, kb, d] row gather + one einsum
    + the (score, id) result writes — the rescore term of
    knn_tiered_cost standing alone."""
    return {
        "flops": 2.0 * b * kb * d,
        "bytes": float(b * kb * d * 4 + b * kb * 8),
    }


def knn_scan_cost(b: int, d: int, n: int) -> dict:
    """f32-HIGHEST exact scan (the escalation arm): one f32 matmul over
    the full corpus + the streamed selection."""
    mm = matmul_cost(b, d, n, passes=1, a_bytes=4, b_bytes=4, out_bytes=0)
    return {
        "flops": mm["flops"] + 2.0 * b * n,
        "bytes": mm["bytes"] + float(b * n * 4),
    }


# ---------------------------------------------------------------------------
# per-dispatch-site registry
# ---------------------------------------------------------------------------

def _merge(*costs: dict) -> dict:
    return {
        "flops": sum(c["flops"] for c in costs),
        "bytes": sum(c["bytes"] for c in costs),
    }


def _fused_pallas_scan(fields: dict) -> dict | None:
    """The fused dense-tier pipeline (ops/fused._fused_pipeline): split-
    bf16 2-pass matmul (in-kernel: tier read once as the stacked
    [2V, N] bf16 operand) + per-tile top-t selection + sparse one-hot
    scatter when posting rows ride along."""
    q = fields.get("queries")
    v = fields.get("v")
    n = fields.get("num_docs")
    if not (q and v and n):
        return None
    dense = matmul_cost(q, v, n, passes=2, a_bytes=2, b_bytes=2, out_bytes=0)
    sel = topk_scan_cost(q, n, score_bytes=0)  # scores stay in VMEM
    parts = [dense, sel]
    rows = fields.get("rows")
    if rows:
        parts.append(sparse_bm25_cost(int(rows)))
    return _merge(*parts)


def _compiled_plan(fields: dict) -> dict | None:
    """Per-query compiled plan (query/executor): dense accumulator
    scatter + selection over [1, N]. Coarse by design — the
    query's term mix is not in the fields; the selection pass dominates."""
    n = fields.get("num_docs")
    if not n:
        return None
    q = fields.get("queries", 1)
    return _merge(topk_scan_cost(q, n),
                  {"flops": 2.0 * q * n, "bytes": float(q * n * 4)})


def _batched_disjunction(fields: dict) -> dict | None:
    """Batched sparse path (ops/batched run/run_fast): postings gather +
    BM25 + per-query candidate selection."""
    q = fields.get("queries")
    n = fields.get("num_docs")
    if not (q and n):
        return None
    rows = fields.get("rows", 0)
    parts = [topk_scan_cost(q, n)]
    if rows:
        parts.append(sparse_bm25_cost(int(rows), out_n=n))
    return _merge(*parts)


def _sharded_spmd(fields: dict) -> dict | None:
    """SPMD scatter/gather searches (parallel/sharded search_batch): one
    program evaluates every shard; num_docs is the TOTAL docs scanned
    (S * n_max)."""
    n = fields.get("num_docs")
    if not n:
        return None
    q = fields.get("queries", fields.get("requests", 1))
    return _merge(topk_scan_cost(q, n),
                  {"flops": 2.0 * q * n, "bytes": float(q * n * 4)})


def _impact_gather(fields: dict) -> dict | None:
    rows = fields.get("rows")
    if not rows:
        return None
    return impact_gather_cost(int(rows),
                              code_bytes=int(fields.get("code_bytes", 2)))


def _impact_sum(fields: dict) -> dict | None:
    q, n = fields.get("queries"), fields.get("num_docs")
    if not (q and n):
        return None
    return impact_sum_cost(q, n, cands=int(fields.get("cands", 0)))


def _impact_sharded(fields: dict) -> dict | None:
    """One SPMD program: code-block gather+dequant per shard + the
    candidate tail; num_docs is the total scanned (S · n_max)."""
    q, n = fields.get("queries"), fields.get("num_docs")
    rows = fields.get("rows")
    if not (q and n and rows):
        return None
    return _merge(
        impact_gather_cost(int(rows),
                           code_bytes=int(fields.get("code_bytes", 2))),
        topk_scan_cost(q, n),
    )


def _knn_tiered(fields: dict) -> dict | None:
    b, d, n = fields.get("queries"), fields.get("dims"), fields.get("num_docs")
    if not (b and d and n):
        return None
    return knn_tiered_cost(b, d, n, kb=fields.get("kb", 128))


def _knn_scan(fields: dict) -> dict | None:
    b, d, n = fields.get("queries"), fields.get("dims"), fields.get("num_docs")
    if not (b and d and n):
        return None
    return knn_scan_cost(b, d, n)


def _ann_centroid_probe(fields: dict) -> dict | None:
    """[B, D] @ [D, C] f32 routing matmul + per-centroid selection."""
    b, d, c = fields.get("queries"), fields.get("dims"), fields.get("nlist")
    if not (b and d and c):
        return None
    mm = matmul_cost(b, d, c, passes=1, a_bytes=4, b_bytes=4, out_bytes=0)
    return _merge(mm, {"flops": 2.0 * b * c, "bytes": float(b * c * 4)})


def _ann_gather_scan(fields: dict) -> dict | None:
    b, d = fields.get("queries"), fields.get("dims")
    p, l = fields.get("nprobe"), fields.get("tile")
    if not (b and d and p and l):
        return None
    return ann_gather_scan_cost(b, p, l, d,
                                tier=fields.get("scan_tier", "int8"))


def _ann_rescore(fields: dict) -> dict | None:
    b, d, kb = fields.get("queries"), fields.get("dims"), fields.get("kb")
    if not (b and d and kb):
        return None
    return ann_rescore_cost(b, kb, d)


# ---------------------------------------------------------------------------
# write-path build stages (PR 13): the refresh/build pipeline gets the
# same flops/bytes accounting the query kernels carry, so the ROADMAP
# item-2 device port has a host baseline with per-stage attribution on
# day one. On the host these run as numpy loops — the MFU/bandwidth
# fractions are honest "how far from the roofline is this stage" numbers
# the port must close, not utilization claims.
# ---------------------------------------------------------------------------

def kmeans_build_cost(n: int, d: int, c: int, *, iters: int = 8) -> dict:
    """Lloyd k-means (ops/vector.kmeans_ivf): per iteration one [N,D]@[D,C]
    f32 distance matmul, a 2-ops/element argmax over [N,C], and the
    centroid scatter update reading the [N,D] corpus once more."""
    mm = matmul_cost(n, d, c, passes=iters, a_bytes=4, b_bytes=4,
                     out_bytes=0)
    return {
        "flops": mm["flops"] + 2.0 * n * c * iters + 2.0 * n * d * iters,
        "bytes": mm["bytes"] + float(iters * (n * 4 + c * d * 4)),
    }


def csr_assemble_build_cost(postings: int, *, n_docs: int = 0) -> dict:
    """Blocked-postings scatter (index/pack.py build): every posting is
    read from the flat CSR ((docid i32, tf f32) = 8 B) and written into
    its blocked lane ((docid, tf, dl) = 12 B); 2 ops/posting of index
    arithmetic; plus the per-doc norm gather."""
    return {
        "flops": 2.0 * postings,
        "bytes": float(postings * (8 + 12) + n_docs * 4),
    }


def norms_build_cost(n_docs: int, nfields: int) -> dict:
    """Smallfloat norm quantization (index/smallfloat.quantize_lengths):
    one i64 length read + one u8 norm write per (doc, field) lane, 2
    ops/lane for the quantize bucket search."""
    lanes = n_docs * max(nfields, 1)
    return {"flops": 2.0 * lanes, "bytes": float(lanes * (8 + 1))}


def impact_quantize_build_cost(rows: int, *, block: int = 128,
                               code_bytes: int = 2) -> dict:
    """Impact-code derivation over the blocked postings ([rows, BLOCK]
    lanes): tfn = tf/(tf + k_base + k_slope·dl) then scale+round+clip —
    ~6 FLOPs/lane; reads (tf f32, dl f32), writes one code. Identical
    model for the host derivation (pack.py, basis="host") and the
    on-device elementwise pass (sharded.refresh_impacts,
    basis="device") — the split between the two IS the attribution."""
    lanes = rows * block
    return {"flops": 6.0 * lanes, "bytes": float(lanes * (8 + code_bytes))}


def ann_tiles_build_cost(c: int, l: int, d: int) -> dict:
    """ANN tile packing (ann/index.build_ann): every [C, L] slot gathers
    its f32 vector row, scalar-quantizes it to int8 (~4 ops/element:
    min/max scan + affine + round) and writes codes + scale/offset/order
    metadata."""
    slots = float(c * l)
    return {
        "flops": 4.0 * slots * d,
        "bytes": slots * (d * 4 + d * 1 + 12),
    }


def device_put_build_cost(nbytes: float) -> dict:
    """Pack upload (sharded.stacked_to_device / update_live): a pure
    host→device transfer — zero FLOPs, judged on bandwidth only (the
    denominator is the HBM peak; PCIe/DMA peaks are below it, so the
    fraction is conservative)."""
    return {"flops": 0.0, "bytes": float(nbytes)}


def merge_build_cost(docs: int, *, nbytes: float = 0.0) -> dict:
    """Tier merge (engine._merge_tiers): a wrapper over a full rebuild —
    the inner stages carry the precise accounting; this entry keeps the
    merge-level roofline honest as one read of the old resident pack plus
    one write of its replacement, with 2 ops/doc of visibility
    bookkeeping."""
    return {"flops": 2.0 * docs, "bytes": float(2.0 * nbytes)}


def segment_merge_build_cost(docs: int, *, nbytes: float = 0.0) -> dict:
    """LSM tail-segment fold (engine._merge_tail_segments, PR 15): a
    wrapper over the union rebuild of the tail segments ONLY — the
    inner build.* stages (csr_assemble, impact_quantize, device_put…)
    carry the precise accounting; same read-old + write-new convention
    as build.merge, scoped to the tail bytes instead of the base."""
    return {"flops": 2.0 * docs, "bytes": float(2.0 * nbytes)}


def analyze_build_cost(nbytes: int) -> dict:
    """Batch text analysis (analysis/batched.py, PR 16): tokenization +
    term hashing over the burst's packed byte tensor. Bytes-based
    convention (BENCH_NOTES round 20) — work scales with input
    CHARACTERS, not docs: ~16 ops/byte (char-class tests, case fold,
    two segmented polynomial hash lanes with their scan combines) and
    ~3× the input bytes of traffic (read the char tensor once, write
    the boundary masks and two u32 hash lanes amortized over scan
    tiles). The identical model prices the device kernel
    (basis="device") and the batched host pass (basis="host") — the
    split between the two IS the attribution, like build.impact_quantize."""
    nbytes = float(max(int(nbytes), 1))
    return {"flops": 16.0 * nbytes, "bytes": 3.0 * nbytes}


def allgather_merge_cost(s: int, q: int, k: int, *,
                         id_bytes: int = 8) -> dict:
    """The on-device coordinator merge (PR 10): every shard's [q, k]
    (score f32, id i64) rows all-gather across the s mesh devices, then
    one lax.top_k over the [q, s*k] gathered field. ici_bytes is the
    total row volume crossing the interconnect once (s*q*k rows of
    4+id_bytes B — BENCH_NOTES round 14); HBM bytes are the gathered
    read + merged [q, k] write; 2 ops/element of selection."""
    rows = float(s * q * k)
    ici = rows * (4 + id_bytes)
    return {
        "flops": 2.0 * rows,
        "bytes": ici + float(q * k * (4 + id_bytes + 4)),
        "ici_bytes": ici,
    }


def _sharded_allgather_topk(fields: dict) -> dict | None:
    """One pjit SPMD program: per-shard scan (impact gather or raw-BM25
    disjunction, by tier) + the in-program all-gather top-k merge."""
    s = fields.get("shards")
    q, n = fields.get("queries"), fields.get("num_docs")
    k = fields.get("k")
    if not (s and q and n and k):
        return None
    if fields.get("tier") == "impact":
        scan = _impact_sharded(fields)
    else:
        scan = _batched_disjunction(fields)
    if scan is None:
        scan = topk_scan_cost(q, n)
    merge = allgather_merge_cost(int(s), int(q), int(k))
    out = _merge(scan, merge)
    out["ici_bytes"] = merge["ici_bytes"]
    return out


def _sharded_global_merge(fields: dict) -> dict | None:
    """The standalone merge program (probe / out-of-program rows)."""
    s, q, k = fields.get("shards"), fields.get("queries"), fields.get("k")
    if not (s and q and k):
        return None
    return allgather_merge_cost(int(s), int(q), int(k))


def _fused_sharded_allgather(fields: dict) -> dict | None:
    """The PR-11 fused one-program route: the per-shard fused Pallas
    pipeline (split-bf16 in-kernel matmul + per-tile selection, num_docs
    is the TOTAL padded docs scanned S·n_pad) inside an embedded
    shard_map region, plus the in-program all-gather top-k merge —
    ici_bytes judged against the interconnect peak like the other
    collective kernels."""
    s, k = fields.get("shards"), fields.get("k")
    scan = _fused_pallas_scan(fields)
    if not (s and k) or scan is None:
        return None
    merge = allgather_merge_cost(int(s), int(fields["queries"]), int(k))
    out = _merge(scan, merge)
    out["ici_bytes"] = merge["ici_bytes"]
    return out


def _serving_wave(fields: dict) -> dict | None:
    """The end-to-end serving wave (PR 11): every lane's compiled
    programs dispatched in one phase and pulled by ONE combined fetch —
    this span wraps that fetch, so its wall time is the wave's device
    execution. Modeled coarsely as the dominant scan over the wave's
    total (queries × resident docs) plus the all-gather merge; per-lane
    precision lives in the per-kernel entries, this one keeps the
    wave-level roofline honest."""
    s = fields.get("shards")
    q, n = fields.get("queries"), fields.get("num_docs")
    k = fields.get("k")
    if not (s and q and n and k):
        return None
    scan = topk_scan_cost(int(q), int(n))
    merge = allgather_merge_cost(int(s), int(q), int(k))
    out = _merge(scan, merge)
    out["ici_bytes"] = merge["ici_bytes"]
    return out


def _build_kmeans(fields: dict) -> dict | None:
    n, d, c = fields.get("n"), fields.get("dims"), fields.get("nlist")
    if not (n and d and c):
        return None
    return kmeans_build_cost(int(n), int(d), int(c),
                             iters=int(fields.get("iters", 8)))


def _build_csr_assemble(fields: dict) -> dict | None:
    p = fields.get("postings")
    if p is None:
        return None
    return csr_assemble_build_cost(int(p),
                                   n_docs=int(fields.get("num_docs", 0)))


def _build_norms(fields: dict) -> dict | None:
    n = fields.get("num_docs")
    if n is None:
        return None
    return norms_build_cost(int(n), int(fields.get("nfields", 1)))


def _build_impact_quantize(fields: dict) -> dict | None:
    rows = fields.get("rows")
    if rows is None:
        return None
    return impact_quantize_build_cost(
        int(rows), code_bytes=int(fields.get("code_bytes", 2)))


def _build_ann_tiles(fields: dict) -> dict | None:
    c, l, d = fields.get("nlist"), fields.get("tile"), fields.get("dims")
    if not (c and l and d):
        return None
    return ann_tiles_build_cost(int(c), int(l), int(d))


def _build_device_put(fields: dict) -> dict | None:
    nbytes = fields.get("nbytes")
    if nbytes is None:
        return None
    return device_put_build_cost(float(nbytes))


def _build_merge(fields: dict) -> dict | None:
    docs = fields.get("docs")
    if docs is None:
        return None
    return merge_build_cost(int(docs),
                            nbytes=float(fields.get("nbytes", 0.0)))


def _build_segment_merge(fields: dict) -> dict | None:
    docs = fields.get("docs")
    if docs is None:
        return None
    return segment_merge_build_cost(int(docs),
                                    nbytes=float(fields.get("nbytes", 0.0)))


def _build_analyze(fields: dict) -> dict | None:
    nbytes = fields.get("nbytes")
    if nbytes is None:
        return None
    return analyze_build_cost(int(nbytes))


def _esql_stats_exchange(fields: dict) -> dict | None:
    """STATS partial-aggregation exchange (esql/exchange.py): per-shard
    one-hot [G,R]x[R] matmul partials per value view (double columns one
    view; long columns ship i64 + hi/lo f64 = 3 views; the bare count
    rides the group one-hot), then the [S,...] collective merge. Useful
    work only — the padded R already prices the padding the layout pays,
    matching the dense-matmul convention of vector.knn_scan."""
    s, r, g = fields.get("shards"), fields.get("rows"), fields.get("groups")
    if not (s and r and g):
        return None
    s, r, g = int(s), int(r), int(g)
    dc = int(fields.get("dbl_cols", 0))
    lc = int(fields.get("long_cols", 0))
    views = dc + 3 * lc + 1
    flops = 2.0 * s * r * g * views
    bytes_ = (
        s * r * (4.0                      # group ordinals (i32)
                 + 9.0 * dc               # f64 values + ok mask
                 + 33.0 * lc)             # i64 + hi/lo f64 + ok mask
        + s * g * 8.0 * (4.0 * max(dc, 1) + 2.0 * lc)  # partial outputs
    )
    return {"flops": flops, "bytes": bytes_}


def _esql_topn_exchange(fields: dict) -> dict | None:
    """SORT|LIMIT top-n exchange (esql/topn.py): per-shard lexicographic
    lax.sort over K encoded rank keys + the row index, then the gathered
    re-sort of S*n winners. Sort flops priced as comparator work
    ~ rows*log2(rows) per key lane (the sharded.global_merge sort
    convention); bytes move each [K+1] key lane once in and once out."""
    s, r = fields.get("shards"), fields.get("rows")
    if not (s and r):
        return None
    s, r = int(s), int(r)
    k1 = int(fields.get("keys", 1)) + 1
    n = int(fields.get("n", 1)) or 1
    lg = max(math.log2(max(r, 2)), 1.0)
    lgm = max(math.log2(max(s * n, 2)), 1.0)
    flops = 2.0 * s * k1 * r * lg + 2.0 * k1 * (s * n) * lgm
    bytes_ = 2.0 * 8.0 * k1 * (s * r + s * n)
    return {"flops": flops, "bytes": bytes_}


# name -> cost fn (None = wrapper span; inner kernels carry the cost).
# Keys are the literal time_kernel(...) names at the dispatch sites —
# the tier-1 lint (tests/test_monitoring.py) enforces the bijection.
KERNEL_COSTS: dict[str, object] = {
    "fused.pallas_scan": _fused_pallas_scan,
    "fused.msearch": None,           # wraps fused.pallas_scan (+escalation)
    "batched.disjunction": _batched_disjunction,
    "batched.escalation": _batched_disjunction,
    "compiled_plan": _compiled_plan,
    "sharded.spmd_topk": _sharded_spmd,
    "sharded.exact_disjunction": _batched_disjunction,
    "sharded.fused_pipeline": _fused_pallas_scan,
    # pjit GSPMD path (PR 10): the one-program scan + all-gather merge,
    # and the standalone device merge — both carry an ici_bytes term
    # judged against the ICI peak (ici_util)
    "sharded.allgather_topk": _sharded_allgather_topk,
    "sharded.global_merge": _sharded_global_merge,
    # PR 17: tenant superpacks — one program scoring a wave that mixes
    # queries from many tenant lanes of a shared size-class layout; the
    # body is the batched disjunction over lane-indexed gathers, so the
    # same cost shape applies (num_docs = the class's padded doc width)
    "superpack.tenant_gather": _batched_disjunction,
    # PR 11: the fused Pallas arm riding the one-program route (embedded
    # shard_map region + in-program merge), and the serving wave's
    # single combined fetch — both collective entries with ici_util
    "sharded.fused_allgather_topk": _fused_sharded_allgather,
    "serving.wave_program": _serving_wave,
    # impact-scored sparse tier (BM25S, PR 8)
    "sparse.impact_gather": _impact_gather,
    "sparse.impact_sum": _impact_sum,
    "sharded.impact_disjunction": _impact_sharded,
    "sparse.tail_scan": _sharded_spmd,  # exact scan of the post-build tail
    "vector.knn_tiered": _knn_tiered,
    "vector.knn_scan": _knn_scan,
    "ann.centroid_probe": _ann_centroid_probe,
    "ann.gather_scan": _ann_gather_scan,
    "ann.rescore": _ann_rescore,
    "ann.tail_scan": _knn_scan,      # exact f32 scan of the tail tier
    # write-path build stages (PR 13): refresh/build gets the same
    # accounting — dispatched via monitoring/refresh_profile.build_stage
    # (the lint scans those literals too), host today, the item-2 port's
    # baseline tomorrow
    "build.kmeans": _build_kmeans,
    "build.impact_quantize": _build_impact_quantize,
    "build.csr_assemble": _build_csr_assemble,
    "build.norms": _build_norms,
    "build.ann_tiles": _build_ann_tiles,
    "build.device_put": _build_device_put,
    "build.merge": _build_merge,
    # PR 15: the LSM tail-segment fold (background device merge riding
    # the serving queue as the `_merge` tenant)
    "build.segment_merge": _build_segment_merge,
    # PR 16: batch text analysis — the former host `analyze` wall as a
    # costed dispatch (bytes-based; analysis/batched.analyze_burst)
    "build.analyze": _build_analyze,
    # PR 20: the ESQL device exchanges (esql/exchange.py, esql/topn.py) —
    # the only device dispatches in the whole pipe; host operators are
    # profiled by esql/profile.py and exempt here by design
    "esql.stats_exchange": _esql_stats_exchange,
    "esql.topn_exchange": _esql_topn_exchange,
}


def kernel_cost(name: str, fields: dict) -> dict | None:
    """-> {"flops", "bytes"} for one dispatch, or None (unknown name,
    wrapper entry, or shape fields missing)."""
    fn = KERNEL_COSTS.get(name)
    if fn is None:
        return None
    try:
        return fn(fields)
    except Exception:  # noqa: BLE001 - accounting must never fail a search
        return None


def utilization(name: str, fields: dict, seconds: float) -> dict | None:
    """-> {flops, bytes, mfu, bw_util[, ici_bytes, ici_util]} for one
    timed dispatch, or None. Collective kernels (an ici_bytes term in
    their cost) additionally report achieved ICI utilization against
    the interconnect peak."""
    cost = kernel_cost(name, fields)
    if cost is None:
        return None
    peak_f, peak_b, _kind = device_peaks()
    sec = max(seconds, 1e-9)
    out = {
        "flops": cost["flops"],
        "bytes": cost["bytes"],
        "mfu": cost["flops"] / sec / peak_f,
        "bw_util": cost["bytes"] / sec / peak_b,
    }
    if cost.get("ici_bytes"):
        out["ici_bytes"] = cost["ici_bytes"]
        out["ici_util"] = cost["ici_bytes"] / sec / ici_peak()
    return out
