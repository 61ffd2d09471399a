"""PR 5: device-utilization accounting + the self-monitoring pipeline.

Covers: the analytic cost model against hand-computed FLOPs/bytes
(dense matmul, top-k scan, kNN tiers, bf16 vs f32), time_kernel's
MFU/bandwidth attribution, the dispatch-site lint (every time_kernel
name in ops/ and parallel/ must be registered in KERNEL_COSTS),
HBM/padded-waste gauges, JIT executable-cache counters, the
MonitoringService writing .monitoring-es-* TSDB indices queryable via
date_histogram (single node AND a 3-node replicated cluster), retention
pruning, the prebuilt ML self-watch job, _cat/tasks + detailed task
columns, per-index dynamic slowlog thresholds, and bench.py's atomic
record file.
"""

import asyncio
import glob
import json
import os
import re
import time

import numpy as np
import pytest

from elasticsearch_tpu.monitoring import costmodel
from elasticsearch_tpu.monitoring.costmodel import (
    KERNEL_COSTS,
    device_peaks,
    kernel_cost,
    knn_scan_cost,
    knn_tiered_cost,
    matmul_cost,
    topk_scan_cost,
)


# ---------------------------------------------------------------------------
# cost model vs hand-computed values
# ---------------------------------------------------------------------------

def test_matmul_cost_hand_computed():
    # the C1 dense tier: [512, 896] @ [896, 1M], split-bf16 = 2 passes
    m, k, n = 512, 896, 1_000_000
    c = matmul_cost(m, k, n, passes=2)
    assert c["flops"] == 2.0 * m * k * n * 2
    assert c["bytes"] == 2 * (m * k * 2 + k * n * 2) + m * n * 4
    # single f32 pass: same flops per pass, double operand bytes
    c32 = matmul_cost(m, k, n, passes=1, a_bytes=4, b_bytes=4)
    assert c32["flops"] == 2.0 * m * k * n
    assert c32["bytes"] == (m * k * 4 + k * n * 4) + m * n * 4


def test_topk_scan_cost_hand_computed():
    q, n = 512, 1_000_000
    c = topk_scan_cost(q, n)
    assert c["flops"] == 2.0 * q * n  # compare + select per element
    assert c["bytes"] == q * n * 4    # one streamed read of the scores


def test_knn_tiered_cost_hand_computed():
    # the C4 shape: 1024 queries x 384 dims x 1M docs, KB=128 rescore
    b, d, n, kb = 1024, 384, 1_000_000, 128
    c = knn_tiered_cost(b, d, n, kb=kb)
    sel_flops = 2.0 * b * d * n * 2            # 2 bf16 passes
    resc_flops = 2.0 * b * kb * d              # [b, kb, d] einsum
    scan_flops = 2.0 * b * n                   # running selection
    assert c["flops"] == sel_flops + resc_flops + scan_flops
    sel_bytes = 2 * (b * d * 2 + d * n * 2)    # hi+lo tier reads, bf16
    resc_bytes = b * kb * d * 4 + b * kb * 8   # f32 gather + (score, id)
    assert c["bytes"] == sel_bytes + resc_bytes


def test_bf16_vs_f32_corpus_traffic():
    """The tiering trade on record: 2 bf16 passes move exactly the bytes
    of 1 f32 pass over the corpus, but run at double the FLOP count —
    i.e. the win must come from the MXU's bf16 rate, not from traffic."""
    b, d, n = 64, 128, 100_000
    tiered = knn_tiered_cost(b, d, n, kb=1)  # kb=1: rescore ~negligible
    f32 = knn_scan_cost(b, d, n)
    bf16_corpus = 2 * (d * n * 2)  # two bf16 copies
    f32_corpus = d * n * 4
    assert bf16_corpus == f32_corpus
    assert tiered["flops"] > f32["flops"]  # 2 selection passes vs 1


def test_device_peaks_env_override(monkeypatch):
    monkeypatch.setenv("ES_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("ES_TPU_PEAK_BW", "1e10")
    f, b, _kind = device_peaks()
    assert f == 1e12 and b == 1e10
    monkeypatch.delenv("ES_TPU_PEAK_FLOPS")
    monkeypatch.delenv("ES_TPU_PEAK_BW")
    f2, b2, kind = device_peaks()
    assert f2 > 0 and b2 > 0 and kind  # cached CPU/TPU defaults


# ---------------------------------------------------------------------------
# tier-1 lint: every device dispatch site has a cost-model entry
# ---------------------------------------------------------------------------

_TIME_KERNEL_RE = re.compile(r'time_kernel\(\s*\n?\s*"([^"]+)"')
# deferred dispatch states (PR 11) carry their kernel name as a dict
# literal ('"kernel": "<name>"') and time_kernel receives it dynamically
# at fetch time — the lint must see those names too, or an unregistered
# fused-pjit kernel could ship unaccounted
_KERNEL_FIELD_RE = re.compile(r'"kernel":\s*\n?\s*"([^"]+)"')
# write-path build stages (PR 13) dispatch through
# monitoring/refresh_profile.build_stage("<kernel>", ...) — a time_kernel
# wrapper that also charges the active RefreshProfile collector. The
# literal is the kernel name, so the same bijection holds: an
# unregistered build stage fails tier-1.
_BUILD_STAGE_RE = re.compile(r'build_stage\(\s*\n?\s*"([^"]+)"')

_DISPATCH_DIRS = ("ops", "parallel", "query", "ann", "engine", "index",
                  # PR 16: the batched analysis pipeline dispatches
                  # build.analyze from analysis/batched.py
                  "analysis",
                  # PR 17: tenant superpacks dispatch
                  # superpack.tenant_gather from tenancy/superpack.py
                  "tenancy",
                  # PR 20: the ESQL exchange dispatches
                  # (esql/exchange.py, esql/topn.py)
                  "esql")
_DISPATCH_REGEXES = (_TIME_KERNEL_RE, _KERNEL_FIELD_RE, _BUILD_STAGE_RE)


def _dispatch_site_names():
    root = os.path.join(os.path.dirname(__file__), "..",
                        "elasticsearch_tpu")
    names = {}
    for sub in _DISPATCH_DIRS:
        for path in glob.glob(os.path.join(root, sub, "*.py")):
            src = open(path, encoding="utf-8").read()
            for rx in _DISPATCH_REGEXES:
                for m in rx.finditer(src):
                    names.setdefault(m.group(1), []).append(
                        os.path.relpath(path, root))
    return names


def test_every_dispatch_site_has_a_cost_model_entry():
    """A new Pallas/XLA kernel cannot ship unaccounted: every literal
    time_kernel("<name>") in ops/ and parallel/ must have a KERNEL_COSTS
    entry (None is allowed only as an explicit wrapper declaration)."""
    sites = _dispatch_site_names()
    assert sites, "dispatch-site scan found nothing — regex rotted?"
    missing = {n: files for n, files in sites.items()
               if n not in KERNEL_COSTS}
    assert not missing, (
        f"device dispatch sites without a cost-model entry: {missing} — "
        "add them to monitoring/costmodel.KERNEL_COSTS (a None entry is "
        "an explicit 'wrapper, inner kernels carry the cost' declaration)")
    # the known kernel inventory must actually be present in the source —
    # a deleted dispatch site should prompt removing its entry too
    for expected in ("fused.pallas_scan", "batched.disjunction",
                     "sharded.fused_pipeline", "sharded.spmd_topk",
                     "vector.knn_tiered", "vector.knn_scan",
                     "compiled_plan", "ann.centroid_probe",
                     "ann.gather_scan", "ann.rescore", "ann.tail_scan",
                     "sparse.impact_gather", "sparse.impact_sum",
                     "sharded.impact_disjunction", "sparse.tail_scan",
                     # the pjit GSPMD path (PR 10): the one-program
                     # all-gather merge + the standalone device merge
                     "sharded.allgather_topk", "sharded.global_merge",
                     # PR 11: the fused arm on the one-program route and
                     # the serving wave's single combined fetch
                     "sharded.fused_allgather_topk",
                     "serving.wave_program",
                     # PR 13: the write-path build stages (index/, ann/,
                     # parallel/, engine/ via build_stage literals)
                     "build.kmeans", "build.impact_quantize",
                     "build.csr_assemble", "build.norms",
                     "build.ann_tiles", "build.device_put", "build.merge",
                     # PR 16: the batch-vectorized analyze dispatch
                     "build.analyze",
                     # PR 17: the tenant superpack gather dispatch
                     "superpack.tenant_gather",
                     # PR 20: the ESQL exchange dispatches
                     "esql.stats_exchange", "esql.topn_exchange"):
        assert expected in sites, f"dispatch site [{expected}] vanished"


def test_cost_fns_resolve_on_representative_fields():
    reps = {
        "fused.pallas_scan": {"queries": 512, "v": 896,
                              "num_docs": 1 << 20, "k": 10},
        "batched.disjunction": {"queries": 64, "num_docs": 20_000,
                                "rows": 256},
        "compiled_plan": {"queries": 1, "num_docs": 20_000},
        "sharded.spmd_topk": {"requests": 3, "queries": 3,
                              "num_docs": 8 * 20_000},
        "vector.knn_tiered": {"queries": 128, "dims": 64,
                              "num_docs": 50_000, "kb": 128},
        "vector.knn_scan": {"queries": 4, "dims": 64, "num_docs": 50_000},
        "ann.centroid_probe": {"queries": 128, "dims": 64, "nlist": 256},
        "ann.gather_scan": {"queries": 128, "dims": 64, "nprobe": 8,
                            "tile": 512, "kb": 64, "scan_tier": "int8"},
        "ann.rescore": {"queries": 128, "dims": 64, "kb": 64},
        "ann.tail_scan": {"queries": 128, "dims": 64, "num_docs": 2_000},
        "sparse.impact_gather": {"queries": 64, "rows": 64 * 4 * 8,
                                 "code_bytes": 2},
        "sparse.impact_sum": {"queries": 64, "num_docs": 20_000,
                              "cands": 4096},
        "sharded.impact_disjunction": {"queries": 64, "rows": 3 * 64 * 32,
                                       "num_docs": 3 * 20_000,
                                       "code_bytes": 2},
        "sparse.tail_scan": {"queries": 1, "num_docs": 2_000},
        # PR 16: analyze cost is bytes-based (text has no flop shape)
        "build.analyze": {"nbytes": 1 << 20},
        # PR 17: tenant-gather over a size class's padded doc width
        "superpack.tenant_gather": {"queries": 32, "num_docs": 1024,
                                    "rows": 32 * 2 * 8},
        # PR 20: the ESQL exchanges (shapes as dispatched by
        # esql/exchange.py and esql/topn.py)
        "esql.stats_exchange": {"shards": 8, "rows": 4096, "groups": 32,
                                "dbl_cols": 1, "long_cols": 1},
        "esql.topn_exchange": {"shards": 8, "rows": 4096, "keys": 2,
                               "n": 10},
    }
    for name, fields in reps.items():
        c = kernel_cost(name, fields)
        assert c and c["flops"] > 0 and c["bytes"] > 0, (name, c)
    # missing shape fields degrade to None, never raise
    assert kernel_cost("fused.pallas_scan", {"queries": 4}) is None
    assert kernel_cost("fused.msearch", {"queries": 4}) is None  # wrapper


def test_every_cost_entry_declares_an_xla_check_status():
    """PR 12 lint: every KERNEL_COSTS entry must declare its XLA
    cross-check policy — "checked" (a check_dispatch site is wired at
    its compiled-plan cache) or "exempt" WITH a recorded reason. A new
    kernel cannot ship silently un-cross-checked."""
    from elasticsearch_tpu.monitoring.xla_introspect import (
        XLA_CHECKS, xla_check_status)

    undeclared = [n for n in KERNEL_COSTS if n not in XLA_CHECKS]
    assert not undeclared, (
        f"KERNEL_COSTS entries without an xla_check status: {undeclared} — "
        "declare them in monitoring/xla_introspect.XLA_CHECKS as checked "
        "or exempt-with-reason")
    for name, spec in XLA_CHECKS.items():
        assert spec.get("status") in ("checked", "exempt"), (name, spec)
        if spec["status"] == "exempt":
            assert spec.get("reason"), (
                f"[{name}] is exempt without a reason — silent exemptions "
                "fail tier-1")
    # stale declarations should be pruned with their cost entries
    stale = [n for n in XLA_CHECKS if n not in KERNEL_COSTS]
    assert not stale, f"XLA_CHECKS entries without a cost entry: {stale}"
    # the acceptance anchors stay checked with documented tolerance bands
    for anchor in ("vector.knn_scan", "sharded.global_merge"):
        spec = xla_check_status(anchor)
        assert spec["status"] == "checked" and spec.get("tol"), anchor
    assert xla_check_status("sharded.allgather_topk")["status"] == "checked"


def test_xla_cross_check_dense_matmul_parity():
    """Acceptance: on the CPU backend the cross-check runs for the dense
    matmul kernel through its real dispatch site (the vector.knn_scan
    escalation arm) and the analytic/XLA flops ratio sits inside the
    tolerance documented in XLA_CHECKS (the analytic model is
    matmul-dominant, so the band is tight)."""
    import jax.numpy as jnp

    from elasticsearch_tpu.monitoring import xla_introspect as xi
    from elasticsearch_tpu.ops.vector import TieredKnnScanner

    # near-tie corpus: every vector within 1e-6 of the query direction,
    # so the split-bf16 selection margin test MUST flag the query and
    # the exact f32 scan (the capture site) always runs
    rng = np.random.default_rng(7)
    base = rng.normal(size=8).astype(np.float32)
    vecs = base[None, :] + 1e-6 * rng.normal(size=(300, 8)).astype(
        np.float32)
    sq = np.sum(vecs * vecs, axis=1)
    sc = TieredKnnScanner(jnp.asarray(vecs), jnp.asarray(sq),
                          "dot_product")
    _v, _i, _t, safe = sc.search(np.asarray([base], np.float32), k=10)
    assert not safe.all(), "corpus failed to force the escalation arm"
    obs = xi.observation("vector.knn_scan")
    assert obs is not None, "cross-check did not capture at the site"
    lo, hi = xi.XLA_CHECKS["vector.knn_scan"]["tol"]
    assert lo <= obs["drift"]["flops"] <= hi, obs
    blo, bhi = xi.XLA_CHECKS["vector.knn_scan"]["bytes_tol"]
    assert blo <= obs["drift"]["bytes"] <= bhi, obs
    # memory_analysis of the compiled executable rode along
    assert obs["memory"].get("argument_bytes", 0) > 0
    assert obs["memory"].get("output_bytes", 0) > 0
    assert obs["memory"]["peak_bytes"] >= obs["memory"]["argument_bytes"]
    # ...and the drift gauge is in the registry + the drift table
    from elasticsearch_tpu.monitoring.xla_introspect import drift_table
    from elasticsearch_tpu.telemetry import metrics

    g = metrics.snapshot()["gauges"]
    assert g.get("es.costmodel.drift.vector.knn_scan.flops") == \
        obs["drift"]["flops"]
    row = drift_table()["vector.knn_scan"]
    assert row["status"] == "checked"
    assert row["flops_ratio"] == obs["drift"]["flops"]


def test_xla_cross_check_allgather_merge_parity(monkeypatch):
    """Acceptance: the cross-check runs for the allgather-topk one-program
    route and the standalone device merge on the pjit CPU mesh; the
    merge program's analytic/XLA ratio sits inside its documented band
    (the program is small enough that the 2-ops/element selection
    convention tracks XLA's sort closely — measured 0.52-0.71 flops,
    0.96-0.98 bytes on the 4/8-shard CPU meshes)."""
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.monitoring import xla_introspect as xi
    from elasticsearch_tpu.parallel.sharded import (
        StackedSearcher, global_merge_rows, make_mesh, msearch_sharded)
    from elasticsearch_tpu.parallel.stacked import build_stacked_pack

    monkeypatch.setenv("ES_TPU_SPMD", "pjit")
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(40)]
    docs = [(f"d{i}", {"body": " ".join(rng.choice(words, size=8))})
            for i in range(320)]
    m = Mappings({"properties": {"body": {"type": "text"}}})
    sp = build_stacked_pack(docs, m, num_shards=4)
    ss = StackedSearcher(sp, mesh=make_mesh(4))
    assert ss._exec == "pjit"
    queries = [[("w1", 1.0), ("w2", 1.0)], [("w3", 1.0)]] * 4
    msearch_sharded(ss, "body", queries, k=5)
    obs = xi.observation("sharded.allgather_topk")
    assert obs is not None, \
        "one-program msearch route did not reach the cross-check"
    assert obs["xla"]["flops"] > 0 and obs["analytic"]["flops"] > 0
    assert obs["drift"]["flops"] > 0
    # the standalone merge program: the tight-band anchor
    v = rng.normal(size=(4, 8, 5)).astype(np.float32)
    i = rng.integers(0, 64, size=(4, 8, 5)).astype(np.int64)
    t = np.full((4, 8), 7, np.int64)
    global_merge_rows(ss, v, i, t)
    mo = xi.observation("sharded.global_merge")
    assert mo is not None
    lo, hi = xi.XLA_CHECKS["sharded.global_merge"]["tol"]
    assert lo <= mo["drift"]["flops"] <= hi, mo
    blo, bhi = xi.XLA_CHECKS["sharded.global_merge"]["bytes_tol"]
    assert blo <= mo["drift"]["bytes"] <= bhi, mo


def test_xla_check_disabled_and_bounded(monkeypatch):
    """ES_TPU_XLA_CHECK=0 turns capture off entirely; with it on, the
    per-kernel capture budget bounds the work (after MAX captures the
    call is a dict lookup returning None)."""
    import jax

    from elasticsearch_tpu.monitoring import xla_introspect as xi

    fn = jax.jit(lambda x: x * 2.0)
    args = (np.ones((4, 4), np.float32),)
    monkeypatch.setenv("ES_TPU_XLA_CHECK", "0")
    assert xi.check_dispatch("compiled_plan", fn, args,
                             fields={"queries": 1, "num_docs": 4}) is None
    monkeypatch.delenv("ES_TPU_XLA_CHECK", raising=False)
    monkeypatch.setenv("ES_TPU_XLA_CHECK_MAX", "1")
    # exempt kernels never capture
    assert xi.check_dispatch("fused.pallas_scan", fn, args) is None
    before = xi._capture_counts.get("compiled_plan", 0)
    if before == 0:
        assert xi.check_dispatch(
            "compiled_plan", fn, args,
            fields={"queries": 1, "num_docs": 4}) is not None
    # budget reached: a NEW shape does not capture
    assert xi.check_dispatch(
        "compiled_plan", fn, (np.ones((8, 8), np.float32),),
        fields={"queries": 1, "num_docs": 8}) is None


def test_bench_xla_cost_check_section(tmp_path, monkeypatch):
    """bench._profile_arm records carry the in-record ground truth."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = bench
    spec.loader.exec_module(bench)
    from elasticsearch_tpu.monitoring import xla_introspect as xi
    from elasticsearch_tpu.telemetry import time_kernel

    if xi.observation("vector.knn_scan") is None:
        test_xla_cross_check_dense_matmul_parity()

    def run():
        with time_kernel("vector.knn_scan", queries=2, dims=8,
                         num_docs=100, k=5):
            pass

    arm = bench._profile_arm(run)
    sec = arm["xla_cost_check"]
    row = sec["kernels"]["vector.knn_scan"]
    assert row["status"] == "checked"
    assert row["flops_ratio"] > 0 and sec["checked"] >= 1
    # bench_regress renders + diffs drift sections (advisory only)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import bench_regress

    rec = {"extras": {"c1": {"profile": arm}}}
    ratios = bench_regress.drift_ratios(rec)
    assert any(p.endswith("vector.knn_scan.flops_ratio") for p in ratios)
    prev = {"extras": {"c1": {"profile": {
        "xla_cost_check": {"kernels": {"vector.knn_scan": {
            "status": "checked", "flops_ratio":
                row["flops_ratio"] * 2.0, "bytes_ratio": 1.0}}}}}}}
    moved = bench_regress.drift_growth(prev, rec, 0.2)
    assert any(p.endswith("vector.knn_scan.flops_ratio")
               for p, _o, _n, _r in moved)


# ---------------------------------------------------------------------------
# time_kernel -> utilization attribution
# ---------------------------------------------------------------------------

def test_time_kernel_attaches_mfu_and_feeds_registry():
    from elasticsearch_tpu.telemetry import (
        collect_profile_events, metrics, time_kernel)

    metrics.reset()
    fields = dict(queries=8, dims=16, num_docs=1000, kb=32)
    with collect_profile_events() as events:
        with time_kernel("vector.knn_tiered", **fields):
            time.sleep(0.002)
    (e,) = [e for e in events if e["kind"] == "kernel"]
    expected = knn_tiered_cost(8, 16, 1000, kb=32)
    assert e["flops"] == expected["flops"]
    assert e["bytes"] == expected["bytes"]
    assert 0 < e["mfu"] < 1.0
    assert 0 < e["bw_util"] < 1.0
    snap = metrics.snapshot()
    assert snap["counters"]["es.kernel.vector.knn_tiered.flops"] == \
        expected["flops"]
    assert "es.kernel.vector.knn_tiered.mfu_pct" in snap["histograms"]
    # kernel_utilization aggregates the same instruments
    from elasticsearch_tpu.monitoring.device import kernel_utilization

    util = kernel_utilization()
    k = util["kernels"]["vector.knn_tiered"]
    assert k["calls"] == 1 and k["flops"] == expected["flops"]
    assert k["mfu"] > 0


def test_unmodeled_kernel_still_times():
    from elasticsearch_tpu.telemetry import (
        collect_profile_events, time_kernel)

    with collect_profile_events() as events:
        with time_kernel("fused.msearch", requests=2):
            pass
    (e,) = events
    assert "mfu" not in e and e["ms"] >= 0  # wall time only, no fake MFU


def test_executor_cache_counters_and_compile_listener():
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.index.pack import PackBuilder
    from elasticsearch_tpu.query.executor import ShardSearcher
    from elasticsearch_tpu.telemetry import metrics

    m = Mappings({"properties": {"body": {"type": "text"}}})
    b = PackBuilder(m)
    for i in range(32):
        b.add_document({"body": [f"alpha w{i % 5}"]})
    ss = ShardSearcher(b.build(), mappings=m)
    metrics.reset()
    # _search_uncached directly: the shard request cache would serve the
    # second call host-side and never reach the executable-cache lookup
    ss._search_uncached({"match": {"body": "alpha"}}, size=3)
    ss._search_uncached({"match": {"body": "alpha"}}, size=3)
    c = metrics.snapshot()["counters"]
    assert c.get("es.jit.cache.compiled_plan.misses", 0) >= 1
    assert c.get("es.jit.cache.compiled_plan.hits", 0) >= 1
    # the jax compile listener metered the first execution's XLA compile
    from elasticsearch_tpu.monitoring.device import jit_stats

    js = jit_stats()
    assert js["compiles"] >= 1
    assert js["compile_time_in_millis"] >= 0
    assert js["executable_cache"]["hits"] >= 1


# ---------------------------------------------------------------------------
# HBM gauges + padded waste
# ---------------------------------------------------------------------------

def test_device_memory_snapshot_counts_live_arrays():
    import jax.numpy as jnp

    from elasticsearch_tpu.monitoring.device import device_memory_snapshot

    keep = jnp.ones((1024, 16), jnp.float32)  # noqa: F841 - held live
    snap = device_memory_snapshot()
    assert snap["backend"] == "cpu"
    assert snap["live_arrays"] >= 1
    assert snap["live_bytes"] >= keep.nbytes


def test_pack_padded_waste_counts_shard_imbalance():
    from elasticsearch_tpu.index.mappings import Mappings
    from elasticsearch_tpu.monitoring.device import pack_padded_waste
    from elasticsearch_tpu.parallel.stacked import build_stacked_pack_routed

    m = Mappings({"properties": {"body": {"type": "text"}}})
    # 2 shards, heavily imbalanced: shard 1 pads its docs to shard 0's
    routed = [
        [(f"a{i}", {"body": f"alpha w{i % 7}"}) for i in range(60)],
        [("b0", {"body": "alpha"})],
    ]
    sp = build_stacked_pack_routed(routed, m)
    waste = pack_padded_waste(sp)
    assert waste > 0
    balanced = build_stacked_pack_routed(
        [routed[0], routed[0]], m)
    assert pack_padded_waste(balanced) < waste + sp.live.nbytes


# ---------------------------------------------------------------------------
# MonitoringService: local engine, TSDB indices, retention
# ---------------------------------------------------------------------------

@pytest.fixture
def engine():
    from elasticsearch_tpu.engine import Engine

    eng = Engine()
    yield eng
    eng.close()


def _seed_engine(eng):
    eng.create_index("logs", mappings={
        "properties": {"body": {"type": "text"}}})
    idx = eng.indices["logs"]
    for i in range(10):
        idx.index_doc(f"d{i}", {"body": f"alpha beta w{i % 3}"})
    idx.refresh()
    idx.search(query={"match": {"body": "alpha"}}, size=3)


def test_monitoring_collect_writes_tsdb_and_date_histogram(engine):
    from elasticsearch_tpu.monitoring import MONITORING_PREFIX

    _seed_engine(engine)
    mon = engine.monitoring
    n = mon.collect_once()
    assert n >= 2  # node_stats + index_stats(logs)
    mon_indices = [x for x in engine.indices if x.startswith(
        MONITORING_PREFIX)]
    assert len(mon_indices) == 1
    midx = engine.indices[mon_indices[0]]
    # hidden time_series index with deterministic (_tsid, @timestamp) ids
    assert midx.settings.get("hidden") is True
    assert midx.ts_mode is not None
    # queryable through the NORMAL search surface: date_histogram + terms
    res = engine.search_multi(
        ".monitoring-es-*", query={"term": {"type": "node_stats"}},
        size=1, aggs={
            "over_time": {
                "date_histogram": {"field": "@timestamp",
                                   "fixed_interval": "10s"},
            },
            "by_node": {"terms": {"field": "node"}},
        })
    assert res["hits"]["total"]["value"] >= 1
    buckets = res["aggregations"]["over_time"]["buckets"]
    assert sum(b["doc_count"] for b in buckets) >= 1
    assert [b["key"] for b in
            res["aggregations"]["by_node"]["buckets"]] == ["node-0"]
    src = res["hits"]["hits"][0]["_source"]
    ns = src["node_stats"]
    assert ns["indices"]["docs"]["count"] == 10
    assert ns["indices"]["search"]["query_total"] >= 1
    assert "device" in ns and "hbm_live_bytes" in ns["device"]
    assert "jit" in ns
    # per-kernel utilization rode along (the seed search dispatched
    # compiled_plan through time_kernel)
    assert "compiled_plan" in ns["device"]["kernels"]
    assert ns["device"]["kernels"]["compiled_plan"]["mfu"] >= 0
    # index_stats doc for the user index; none for the monitoring index
    res2 = engine.search_multi(
        ".monitoring-es-*", query={"term": {"type": "index_stats"}},
        size=10)
    idx_names = {h["_source"]["index"] for h in res2["hits"]["hits"]}
    assert idx_names == {"logs"}
    # re-collection is additive, never errors on the existing index
    assert mon.collect_once() >= 2


def test_monitoring_retention_prunes_expired_indices(engine):
    from elasticsearch_tpu.monitoring import monitoring_index_name
    from elasticsearch_tpu.monitoring.collectors import \
        monitoring_index_body
    from elasticsearch_tpu.monitoring.service import MONITORING_PREFIX

    _seed_engine(engine)
    body = monitoring_index_body()
    stale = MONITORING_PREFIX + "2020.01.01"
    engine.create_index(stale, mappings=body["mappings"],
                        settings=dict(body["settings"]["index"]))
    assert stale in engine.indices
    mon = engine.monitoring
    mon.collect_once()
    assert stale not in engine.indices, "expired index not pruned"
    assert monitoring_index_name() in engine.indices, \
        "today's index must survive pruning"


def test_monitoring_settings_drive_the_collection_thread(engine):
    _seed_engine(engine)
    engine.settings.update({"persistent": {
        "xpack.monitoring.collection.enabled": True,
        "xpack.monitoring.collection.interval": "100ms",
    }})
    mon = engine.monitoring
    deadline = time.time() + 20.0
    while time.time() < deadline and mon.collections_total < 2:
        time.sleep(0.05)
    assert mon.collections_total >= 2, mon.stats()
    assert mon.stats()["running"] is True
    engine.settings.update({"persistent": {
        "xpack.monitoring.collection.enabled": False}})
    assert mon.stats()["running"] is False
    # bad interval rejected by the typed setting
    from elasticsearch_tpu.utils.errors import IllegalArgumentError

    with pytest.raises(IllegalArgumentError):
        engine.settings.update({"persistent": {
            "xpack.monitoring.collection.interval": "not-a-duration"}})


def test_self_watch_ml_job_setup(engine):
    from elasticsearch_tpu.monitoring import (
        SELF_WATCH_JOB_ID, setup_self_watch_job)

    _seed_engine(engine)
    engine.monitoring.collect_once()
    out = setup_self_watch_job(engine, bucket_span="1m")
    assert out["created"] is True
    jobs = engine.ml.get_jobs(SELF_WATCH_JOB_ID)
    assert jobs["count"] == 1
    dfs = engine.meta.extras["ml_datafeeds"]
    df = dfs[f"datafeed-{SELF_WATCH_JOB_ID}"]
    assert df["indices"] == [".monitoring-es-8-*"]
    # idempotent
    assert setup_self_watch_job(engine)["created"] is False
    # the datafeed's aggregation extraction runs over the real monitoring
    # docs through the normal agg path
    from elasticsearch_tpu.ml.config import DatafeedConfig, JobConfig
    from elasticsearch_tpu.ml.datafeed import pull

    job_cfg = JobConfig(
        SELF_WATCH_JOB_ID,
        engine.meta.extras["ml_jobs"][SELF_WATCH_JOB_ID]["config"])
    df_cfg = DatafeedConfig(f"datafeed-{SELF_WATCH_JOB_ID}", df)
    now = int(time.time() * 1000)
    out = pull(engine, df_cfg, job_cfg, now - 3_600_000, now + 60_000)
    assert out["bucket_starts"].shape[0] >= 1


# ---------------------------------------------------------------------------
# REST: _nodes/stats device section, prometheus gauges, _monitoring APIs,
# _cat/tasks, detailed task listing
# ---------------------------------------------------------------------------

async def _client():
    from aiohttp.test_utils import TestClient, TestServer

    from elasticsearch_tpu.rest.app import make_app

    client = TestClient(TestServer(make_app()))
    await client.start_server()
    return client


def _run(coro):
    return asyncio.run(coro)


def test_rest_device_stats_prometheus_and_collect():
    async def go():
        client = await _client()
        try:
            await client.put("/mlogs", json={
                "mappings": {"properties": {"x": {"type": "text"}}}})
            await client.put("/mlogs/_doc/1?refresh=true", json={"x": "hi"})
            await client.post("/mlogs/_search",
                              json={"query": {"match": {"x": "hi"}}})
            stats = await (await client.get("/_nodes/stats")).json()
            node = stats["nodes"]["node-0"]
            dev = node["device"]
            assert dev["memory"]["backend"] == "cpu"
            assert dev["memory"]["live_bytes"] >= 0
            assert "pack_padded_waste_bytes" in dev["memory"]
            assert "compiled_plan" in dev["utilization"]["kernels"]
            ku = dev["utilization"]["kernels"]["compiled_plan"]
            assert ku["calls"] >= 1 and ku["flops"] > 0
            assert dev["jit"]["compiles"] >= 0
            assert node["monitoring"]["enabled"] is False
            # PR 12: the compiled-program cross-check table rides
            # device.utilization — the search above captured the
            # compiled plan (or an earlier test in this process did)
            drift = dev["utilization"]["costmodel_drift"]
            assert drift["compiled_plan"]["status"] == "checked"
            assert drift["compiled_plan"]["flops_ratio"] > 0
            assert drift["fused.pallas_scan"]["status"] == "exempt"
            assert "reason" in drift["fused.pallas_scan"]
            # ...and the serving section carries the cumulative
            # host-transition counters (satellite: beyond /_serving/stats)
            assert "host_transitions_total" in node["serving"]
            # prometheus: device gauges + per-kernel MFU histograms
            text = await (await client.get("/_prometheus/metrics")).text()
            assert "es_device_hbm_live_bytes" in text
            assert "es_device_pack_padded_waste_bytes" in text
            assert "es_kernel_compiled_plan_mfu_pct" in text
            assert "es_kernel_compiled_plan_bw_pct" in text
            # PR 12 labeled families on the scrape
            assert 'es_costmodel_drift_flops{kernel="compiled_plan"}' \
                in text
            assert 'es_serving_host_transitions_total{kind="dispatch"}' \
                in text
            assert 'es_serving_host_transitions_total{kind="fetch"}' \
                in text
            # one synchronous collection tick through REST
            r = await client.post("/_monitoring/_collect")
            assert r.status == 200
            out = await r.json()
            assert out["documents"] >= 2
            # the docs are searchable through the normal surface
            res = await (await client.post(
                "/.monitoring-es-*/_search",
                json={"size": 0, "aggs": {"types": {
                    "terms": {"field": "type"}}}})).json()
            keys = {b["key"] for b in
                    res["aggregations"]["types"]["buckets"]}
            assert "node_stats" in keys and "index_stats" in keys
            mon = await (await client.get("/_monitoring")).json()
            assert mon["collections_total"] >= 1
            assert mon["indices"], mon
        finally:
            await client.close()

    _run(go())


def test_rest_cat_tasks_and_detailed_listing():
    async def go():
        client = await _client()
        try:
            engine = client.server.app["engine"]
            t = engine.tasks.register(
                "indices:data/read/search", description="a test search")
            try:
                r = await client.get("/_cat/tasks?format=json")
                rows = await r.json()
                row = [x for x in rows
                       if x["action"] == "indices:data/read/search"][0]
                assert row["task_id"] == t.task_id
                assert row["node"] == "node-0"
                assert row["description"] == "a test search"
                assert re.fullmatch(
                    r"[\d.]+(nanos|micros|ms|s|m)", row["running_time"])
                # text mode with v + h column selection (the shared _cat
                # conventions)
                text = await (await client.get(
                    "/_cat/tasks?v=true&h=action,running_time")).text()
                lines = text.strip().splitlines()
                assert lines[0].split() == ["action", "running_time"]
                assert any("indices:data/read/search" in ln
                           for ln in lines[1:])
                # /_tasks: description + human running_time only under
                # ?detailed=true (reference ListTasks semantics)
                plain = await (await client.get("/_tasks")).json()
                tasks = plain["nodes"]["node-0"]["tasks"]
                assert all("description" not in d for d in tasks.values())
                det = await (await client.get(
                    "/_tasks?detailed=true")).json()
                dt = det["nodes"]["node-0"]["tasks"][t.task_id]
                assert dt["description"] == "a test search"
                assert dt["running_time_in_nanos"] >= 0
                assert "running_time" in dt
            finally:
                engine.tasks.unregister(t)
        finally:
            await client.close()

    _run(go())


def test_slowlog_thresholds_per_index_dynamic():
    async def go():
        client = await _client()
        try:
            from elasticsearch_tpu import telemetry

            for name in ("slowa", "slowb"):
                await client.put(f"/{name}", json={
                    "mappings": {"properties": {"x": {"type": "text"}}}})
                await client.put(f"/{name}/_doc/1?refresh=true",
                                 json={"x": "hello"})
            # nested settings body form -> dotted dynamic setting, on ONE
            # index only
            r = await client.put("/slowa/_settings", json={
                "index": {"search": {"slowlog": {"threshold": {"query": {
                    "warn": "0ms"}}}}}})
            assert r.status == 200
            st = await (await client.get("/slowa/_settings")).json()
            assert st["slowa"]["settings"]["index"][
                "search.slowlog.threshold.query.warn"] == "0ms"
            telemetry.recent_slowlogs.clear()
            for name in ("slowa", "slowb"):
                await client.post(
                    f"/{name}/_search",
                    json={"query": {"match": {"x": "hello"}}})
            logged = {e["index"] for e in telemetry.recent_slowlogs}
            assert "slowa" in logged, "per-index warn threshold ignored"
            assert "slowb" not in logged, \
                "threshold leaked across indices (global, not per-index)"
            # level escalation: info on slowb via the dotted form
            r = await client.put("/slowb/_settings", json={
                "search.slowlog.threshold.query.info": "0ms"})
            assert r.status == 200
            telemetry.recent_slowlogs.clear()
            await client.post("/slowb/_search",
                              json={"query": {"match": {"x": "hello"}}})
            entry = [e for e in telemetry.recent_slowlogs
                     if e["index"] == "slowb"][-1]
            assert entry["level"] == "info"
            # a garbage duration is rejected by the typed setting
            r = await client.put("/slowb/_settings", json={
                "search.slowlog.threshold.query.warn": "fast"})
            assert r.status == 400
        finally:
            await client.close()

    _run(go())


# ---------------------------------------------------------------------------
# bench.py atomic record
# ---------------------------------------------------------------------------

def test_bench_record_written_atomically(tmp_path, monkeypatch):
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = bench
    spec.loader.exec_module(bench)
    record = tmp_path / "rec.json"
    monkeypatch.setenv("ES_BENCH_RECORD", str(record))
    bench._write_record({"match_bm25": {"qps": 12.5, "vs_baseline": 2.0}},
                        partial=True)
    body = json.loads(record.read_text())
    assert body["partial"] is True
    assert body["extras"]["match_bm25"]["qps"] == 12.5
    assert not (tmp_path / "rec.json.tmp").exists(), \
        "temp file must be renamed away"
    # second write replaces atomically (no append, no partial content)
    bench._write_record({"match_bm25": {"qps": 13.0}}, partial=False)
    body2 = json.loads(record.read_text())
    assert "partial" not in body2
    assert body2["extras"]["match_bm25"]["qps"] == 13.0


# ---------------------------------------------------------------------------
# 3-node replicated cluster: collection enabled -> every node's docs
# queryable (date_histogram) from any node; acceptance-criteria path
# ---------------------------------------------------------------------------

def _http(method, port, path, body=None, timeout=60.0):
    import urllib.error
    import urllib.request

    data = None
    headers = {}
    if body is not None:
        data = (body if isinstance(body, str)
                else json.dumps(body)).encode()
        headers["Content-Type"] = "application/json"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, headers=headers,
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_monitoring_cluster_e2e_3node():
    from elasticsearch_tpu.cluster.http import HttpGateway, wait_for_http
    from elasticsearch_tpu.cluster.server import NodeServer

    ids = ["m1", "m2", "m3"]
    servers = {nid: NodeServer(nid, ids, {}, port=0) for nid in ids}
    for nid, s in servers.items():
        for other, o in servers.items():
            if other != nid:
                s.network.add_peer(other, "127.0.0.1", o.port)
    gateways = {}
    try:
        for nid, s in servers.items():
            s.start()
            gateways[nid] = HttpGateway(s, surface="full").start()
        port = gateways["m1"].port
        wait_for_http(port, lambda h: h.get("master_node")
                      and h.get("number_of_nodes") == 3)
        # some traffic so node_stats has something to say
        st, r = _http("PUT", port, "/mlogs", {
            "mappings": {"properties": {"x": {"type": "text"}}}})
        assert st == 200, r
        st, r = _http("PUT", port, "/mlogs/_doc/1?refresh=true",
                      {"x": "hello"}, timeout=90.0)
        assert st in (200, 201), r
        # enable collection cluster-wide (replicated settings op): every
        # node's MonitoringService starts and exports THROUGH its gateway
        st, r = _http("PUT", port, "/_cluster/settings", {
            "persistent": {
                "xpack.monitoring.collection.enabled": True,
                "xpack.monitoring.collection.interval": "500ms",
            }}, timeout=90.0)
        assert st == 200, r

        # ...so every replica ends up holding every node's history
        search_body = {
            "size": 0,
            "query": {"term": {"type": "node_stats"}},
            "aggs": {
                "by_node": {"terms": {"field": "node"}},
                "over_time": {"date_histogram": {
                    "field": "@timestamp", "fixed_interval": "1s"}},
            },
        }
        deadline = time.time() + 120.0
        nodes_seen: set = set()
        res = None
        # query a DIFFERENT node than the one that took the settings op:
        # the history must be cluster-visible, not node-local
        qport = gateways["m2"].port
        while time.time() < deadline:
            st, res = _http("POST", qport, "/.monitoring-es-*/_search",
                            search_body, timeout=90.0)
            if st == 200:
                # before the first export the wildcard matches nothing
                # (no aggregations section) — keep polling
                buckets = (res.get("aggregations") or {}).get(
                    "by_node", {}).get("buckets", [])
                nodes_seen = {b["key"] for b in buckets}
                if nodes_seen == set(ids):
                    break
            time.sleep(0.5)
        assert nodes_seen == set(ids), (nodes_seen, res)
        hist = res["aggregations"]["over_time"]["buckets"]
        assert sum(b["doc_count"] for b in hist) >= 3
        # stop collection before teardown (replicated disable)
        _http("PUT", port, "/_cluster/settings", {
            "persistent": {"xpack.monitoring.collection.enabled": False}},
            timeout=90.0)
    finally:
        for g in gateways.values():
            g.close()
        for s in servers.values():
            s.close()
