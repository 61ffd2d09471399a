"""XLA ground truth for the analytic cost model (PR 12).

Every roofline figure the node reports (MFU / bandwidth / ICI
utilization, the SLO kernel floors, the BENCH records) divides measured
wall time into the PR-5 *analytic* FLOPs/bytes — hand-derived formulas
in monitoring/costmodel.py. A drifted formula would silently mis-grade
every perf claim. XLA already knows the truth: the lowered program of
every compiled-plan cache carries `cost_analysis()` (flops, bytes
accessed) and the compiled executable `memory_analysis()` (argument /
output / temp bytes). This module cross-checks the two at the cache
sites themselves and publishes a per-kernel **drift gauge**:

    es.costmodel.drift.<kernel>.{flops,bytes} = analytic / XLA

Capture discipline (bounded by construction — a cross-check must never
become the serving-latency regression it exists to catch):

  - `check_dispatch` is called at the compiled-plan dispatch sites
    (query/executor, parallel/sharded ``_compiled*``/``_msearch_merged``,
    ops/batched, ops/vector) with the jitted fn, its dispatch args, and
    the SAME shape fields the site feeds `telemetry.time_kernel`;
  - per (kernel, abstract-shape signature) it captures at most once, and
    per kernel at most ES_TPU_XLA_CHECK_MAX (default 3) times — after
    that every call is one dict lookup;
  - the XLA numbers come from ``fn.lower(args).compile()`` — the
    OPTIMIZED executable (post-fusion), i.e. the program that actually
    runs, plus its memory_analysis. ES_TPU_XLA_CHECK=0 disables capture
    entirely (the drift table then only reports check statuses).

Drift convention (BENCH_NOTES round 16): the analytic model counts
USEFUL work (operands read once, 2 ops/element of selection); XLA counts
EXECUTED work (padding lanes, masked selects, sort comparators, scatter
plumbing). Ratios are therefore expected BELOW 1.0 on composite
programs and near 1.0 only where one dense op dominates (the f32 matmul
scan, the standalone all-gather merge). The tracked regression signal is
drift GROWTH between records (scripts/bench_regress.py, advisory), not
|1 - ratio|; the per-kernel `tol` bands below bound the kernels whose
analytic model is exact-dominant and are asserted by tier-1 on CPU.
"""

from __future__ import annotations

import os
import threading
import time

from ..telemetry import log, metrics

# ---------------------------------------------------------------------------
# check-status registry (linted: tests/test_monitoring.py requires every
# KERNEL_COSTS entry to declare a status here — "checked" or an
# exempt-with-reason. A silent exemption fails tier-1.)
# ---------------------------------------------------------------------------

# status "checked": a check_dispatch site is wired at the kernel's
# compiled-plan cache. Optional "tol": (lo, hi) band the analytic/XLA
# flops ratio must sit in — only declared where the analytic model is
# exact-dominant (asserted on CPU by tier-1; measured values in the
# comments). "bytes_tol" likewise for the bytes ratio.
# status "exempt": no XLA cross-check, with the reason on record.
_PALLAS = ("Pallas custom call — opaque to XLA HLO cost analysis "
           "(reports zero flops for the kernel body)")
XLA_CHECKS: dict[str, dict] = {
    "compiled_plan": {"status": "checked"},
    "batched.disjunction": {"status": "checked"},
    "batched.escalation": {
        "status": "checked",
        "note": "same executable family as batched.disjunction "
                "(the rerun dispatches through the same chunk cache)"},
    "sharded.spmd_topk": {"status": "checked"},
    "sharded.exact_disjunction": {"status": "checked"},
    "sharded.impact_disjunction": {"status": "checked"},
    # measured on the 4-shard CPU mesh: flops ratio 0.52-0.71, bytes
    # 0.96-0.98 — the merge program is small enough that the analytic
    # 2-ops/element selection convention tracks XLA's sort closely
    "sharded.global_merge": {"status": "checked",
                             "tol": (0.2, 2.0), "bytes_tol": (0.5, 2.0)},
    "sharded.allgather_topk": {"status": "checked"},
    "sparse.impact_gather": {"status": "checked"},
    "sparse.impact_sum": {"status": "checked"},
    # measured: flops ratio 0.98 (one f32 dot dominates; XLA adds only
    # the top-k sort comparators) — the dense-matmul parity anchor
    "vector.knn_scan": {"status": "checked",
                        "tol": (0.5, 1.5), "bytes_tol": (0.05, 2.0)},
    "vector.knn_tiered": {
        "status": "exempt",
        "reason": "routes through the split-bf16 Pallas selection on "
                  "TPU; the XLA fallback arm is cross-checked via "
                  "vector.knn_scan"},
    "fused.pallas_scan": {"status": "exempt", "reason": _PALLAS},
    "fused.msearch": {"status": "exempt",
                      "reason": "wrapper span (inner kernels carry the "
                                "accounting and the checks)"},
    "sharded.fused_pipeline": {"status": "exempt", "reason": _PALLAS},
    "sharded.fused_allgather_topk": {
        "status": "exempt",
        "reason": _PALLAS + "; the merge half of the program is "
                  "cross-checked via sharded.global_merge"},
    "serving.wave_program": {
        "status": "exempt",
        "reason": "wave-level combined fetch spanning many per-lane "
                  "programs — each lane's own kernel is cross-checked"},
    # PR 17: the tenant-gather body is batched.disjunction over
    # lane-indexed gathers; same sort/cumsum machinery, same cost shape
    "superpack.tenant_gather": {"status": "checked"},
    "sparse.tail_scan": {
        "status": "exempt",
        "reason": "tail-tier scan dispatched inside the engine's tiered "
                  "merge, no caller-visible executable cache; shares the "
                  "sharded.spmd_topk model"},
    "ann.centroid_probe": {
        "status": "exempt",
        "reason": "probe matmul jitted inside ann/kernels without a "
                  "caller-visible executable cache; dense-matmul parity "
                  "is anchored by vector.knn_scan"},
    "ann.gather_scan": {"status": "exempt", "reason": _PALLAS},
    "ann.rescore": {
        "status": "exempt",
        "reason": "rescore einsum jitted inside ann/kernels; covered by "
                  "the vector.knn_scan matmul anchor"},
    "ann.tail_scan": {
        "status": "exempt",
        "reason": "exact f32 tail scan through scan_topk; same program "
                  "family as vector.knn_scan"},
    # write-path build stages (PR 13 substrate; PR 15 device port). The
    # ported stages are exempt-with-reason on a STRONGER ground than a
    # cost cross-check: each device kernel is asserted BYTE-IDENTICAL
    # to its host twin by tests/test_device_build.py, so the analytic
    # flops/bytes model describes both sides of the basis split.
    "build.kmeans": {
        "status": "exempt",
        "reason": "PR 15: one jitted Lloyd while_loop "
                  "(device_build.kmeans_device); assignment parity with "
                  "the eager loop asserted by tests; dense-matmul cost "
                  "parity anchored by vector.knn_scan"},
    "build.impact_quantize": {
        "status": "exempt",
        "reason": "one elementwise device jit "
                  "(device_build.impact_codes_device) asserted BIT-EQUAL "
                  "to the host twin by tests/test_impact.py — stronger "
                  "than a cost cross-check"},
    "build.csr_assemble": {
        "status": "exempt",
        "reason": "PR 15: jitted segment-scatter kernel "
                  "(device_build.csr_blocked_scatter_device) asserted "
                  "byte-equal to the host numpy scatter by "
                  "tests/test_device_build.py"},
    "build.norms": {
        "status": "exempt",
        "reason": "host smallfloat quantization loop (no compiled "
                  "executable)"},
    "build.ann_tiles": {
        "status": "exempt",
        "reason": "PR 15: jitted lax-sort/segment + int8 quantize "
                  "kernel (device_build.ann_tiles_device) asserted "
                  "byte-equal to the host tile loop by "
                  "tests/test_device_build.py"},
    "build.device_put": {
        "status": "exempt",
        "reason": "pure host→device transfer — no program to analyze; "
                  "bandwidth-only cost entry"},
    "build.merge": {
        "status": "exempt",
        "reason": "wrapper over a full rebuild; the inner build.* stages "
                  "carry the per-stage accounting"},
    "build.segment_merge": {
        "status": "exempt",
        "reason": "PR 15 wrapper over the tail-union rebuild (the LSM "
                  "fold); the inner build.* stages carry the per-stage "
                  "accounting"},
    "build.analyze": {
        "status": "exempt",
        "reason": "PR 16: batch tokenize+hash kernel "
                  "(device_build.analyze_hash_device) asserted "
                  "term/position/length-identical to the host analyzer "
                  "oracle by tests/test_batched_analysis.py — stronger "
                  "than a cost cross-check; the batched host basis has "
                  "no compiled executable to introspect"},
    # PR 20: the ESQL exchange dispatches — per-query inline jits with
    # no caller-visible executable cache to wire check_dispatch through
    "esql.stats_exchange": {
        "status": "exempt",
        "reason": "PR 20: per-query jit built from the pipe's agg shape "
                  "(no caller-visible executable cache); the one-hot "
                  "matmul partials share the dense-matmul parity anchor "
                  "(vector.knn_scan), and the exchange output is "
                  "asserted bit-identical to the host _run_stats "
                  "evaluator by tests/test_esql_exchange.py"},
    "esql.topn_exchange": {
        "status": "exempt",
        "reason": "PR 20: per-query jit over the encoded rank keys; the "
                  "lax.sort comparator convention is cross-checked via "
                  "sharded.global_merge, and the selection is asserted "
                  "bit-identical to the host sort+limit by "
                  "tests/test_esql_topn.py"},
}


def xla_check_status(name: str) -> dict:
    return XLA_CHECKS.get(name, {"status": "undeclared"})


# ---------------------------------------------------------------------------
# capture state
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_seen_sigs: set = set()            # (name, signature)
_capture_counts: dict[str, int] = {}
# kernel -> latest observation (survives metrics.reset(): the drift
# table in _nodes/stats / Prometheus / bench reads from here, not from
# the registry gauges alone)
OBSERVATIONS: dict[str, dict] = {}


def enabled() -> bool:
    return os.environ.get("ES_TPU_XLA_CHECK", "auto") != "0"


def _max_captures() -> int:
    try:
        return int(os.environ.get("ES_TPU_XLA_CHECK_MAX", "3"))
    except ValueError:
        return 3


def reset_for_tests() -> None:
    with _lock:
        _seen_sigs.clear()
        _capture_counts.clear()
        OBSERVATIONS.clear()


def _signature(args, kwargs) -> tuple:
    """Hashable abstract signature of the dispatch args — the same
    identity jit caches executables under (shapes + dtypes + treedef)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    descs = tuple(
        (getattr(x, "shape", None) is not None
         and (tuple(x.shape), str(getattr(x, "dtype", type(x).__name__))))
        or (type(x).__name__, str(x)[:32])
        for x in leaves
    )
    return (str(treedef), descs)


def _normalize_cost(ca) -> dict:
    """jax returns a dict (Lowered) or a list of per-partition dicts
    (Compiled); fold to one {flops, bytes}."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes": float(ca.get("bytes accessed", 0.0) or 0.0),
    }


def _memory_dict(mem) -> dict:
    out = {}
    for attr, key in (("argument_size_in_bytes", "argument_bytes"),
                      ("output_size_in_bytes", "output_bytes"),
                      ("temp_size_in_bytes", "temp_bytes"),
                      ("generated_code_size_in_bytes", "code_bytes"),
                      ("alias_size_in_bytes", "alias_bytes")):
        v = getattr(mem, attr, None)
        if v is not None:
            out[key] = int(v)
    if out:
        # the executable's peak working set: everything resident at once
        out["peak_bytes"] = (out.get("argument_bytes", 0)
                             + out.get("output_bytes", 0)
                             + out.get("temp_bytes", 0))
    return out


def check_dispatch(name: str, fn, args=(), kwargs=None,
                   fields: dict | None = None) -> dict | None:
    """Cross-check one compiled-plan dispatch against XLA. Called at the
    dispatch sites with the jitted `fn` and the concrete args about to
    execute; captures (lower + compile + cost/memory analysis) at most
    once per (kernel, shape signature) and `ES_TPU_XLA_CHECK_MAX` times
    per kernel, then becomes a dict lookup. Never raises — the
    cross-check must never fail a search."""
    try:
        if not enabled():
            return None
        spec = XLA_CHECKS.get(name)
        if spec is not None and spec.get("status") == "exempt":
            return None
        with _lock:
            if _capture_counts.get(name, 0) >= _max_captures():
                return None
        sig = _signature(args, kwargs)
        with _lock:
            if (name, sig) in _seen_sigs:
                return None
            _seen_sigs.add((name, sig))
            _capture_counts[name] = _capture_counts.get(name, 0) + 1
        return _capture(name, fn, args, kwargs or {}, fields or {})
    except Exception as e:  # noqa: BLE001 - accounting never fails a search
        log.debug("xla cross-check for [%s] failed: %s", name, e)
        return None


def _capture(name: str, fn, args, kwargs, fields: dict) -> dict | None:
    from .costmodel import kernel_cost

    t0 = time.perf_counter()
    lowered = fn.lower(*args, **kwargs)
    compiled = lowered.compile()
    xla = _normalize_cost(compiled.cost_analysis())
    mem = {}
    try:
        mem = _memory_dict(compiled.memory_analysis())
    except Exception:  # noqa: BLE001 - older backends: cost only
        mem = {}
    analytic = kernel_cost(name, fields) or {}
    obs = {
        "kernel": name,
        "xla": {"flops": xla["flops"], "bytes": xla["bytes"]},
        "analytic": {"flops": float(analytic.get("flops", 0.0)),
                     "bytes": float(analytic.get("bytes", 0.0))},
        "memory": mem,
        "fields": {k: v for k, v in fields.items()
                   if isinstance(v, (int, float, str, bool))},
        "capture_ms": round((time.perf_counter() - t0) * 1000, 3),
        "captured_unix": time.time(),
    }
    if analytic:
        obs["drift"] = {
            "flops": round(obs["analytic"]["flops"]
                           / max(xla["flops"], 1.0), 6),
            "bytes": round(obs["analytic"]["bytes"]
                           / max(xla["bytes"], 1.0), 6),
        }
        metrics.gauge_set(f"es.costmodel.drift.{name}.flops",
                          obs["drift"]["flops"])
        metrics.gauge_set(f"es.costmodel.drift.{name}.bytes",
                          obs["drift"]["bytes"])
    metrics.counter_inc("es.costmodel.xla_checks")
    with _lock:
        prev = OBSERVATIONS.get(name)
        obs["captures"] = (prev["captures"] + 1) if prev else 1
        OBSERVATIONS[name] = obs
    return obs


def check_traceable(name: str, traceable, args=(), static_kwargs=None,
                    fields: dict | None = None) -> dict | None:
    """check_dispatch for sites whose program is a plain traceable (the
    routing helper jits internally): wraps it in jax.jit first."""
    try:
        import functools

        import jax

        fn = jax.jit(functools.partial(traceable, **(static_kwargs or {})))
        return check_dispatch(name, fn, args, None, fields)
    except Exception as e:  # noqa: BLE001
        log.debug("xla cross-check for [%s] failed: %s", name, e)
        return None


def observation(name: str) -> dict | None:
    with _lock:
        return OBSERVATIONS.get(name)


def drift_table() -> dict:
    """The registry-wide cross-check table: one row per KERNEL_COSTS
    entry — check status, and for captured kernels the analytic/XLA
    flops+bytes ratios and the executable's memory analysis. Feeds
    `_nodes/stats` device.utilization, the monitoring TSDB node_stats
    docs, bench records (`xla_cost_check`), and usage_report."""
    from .costmodel import KERNEL_COSTS

    with _lock:
        obs = {k: dict(v) for k, v in OBSERVATIONS.items()}
    out = {}
    for kname in sorted(KERNEL_COSTS):
        spec = xla_check_status(kname)
        row = {"status": spec.get("status", "undeclared")}
        if spec.get("reason"):
            row["reason"] = spec["reason"]
        if spec.get("tol"):
            row["flops_tolerance"] = list(spec["tol"])
        o = obs.get(kname)
        if o is not None:
            row["captures"] = o["captures"]
            row["analytic_flops"] = o["analytic"]["flops"]
            row["xla_flops"] = o["xla"]["flops"]
            row["analytic_bytes"] = o["analytic"]["bytes"]
            row["xla_bytes"] = o["xla"]["bytes"]
            if "drift" in o:
                row["flops_ratio"] = o["drift"]["flops"]
                row["bytes_ratio"] = o["drift"]["bytes"]
            if o.get("memory"):
                row["memory"] = dict(o["memory"])
        out[kname] = row
    return out


def format_drift_table(table: dict | None = None) -> str:
    """Human-readable drift table (tier1_gate / usage_report output)."""
    table = drift_table() if table is None else table
    lines = [f"{'kernel':<32} {'status':<10} {'flops a/x':>10} "
             f"{'bytes a/x':>10}  note"]
    for kname, row in sorted(table.items()):
        fr = row.get("flops_ratio")
        br = row.get("bytes_ratio")
        note = row.get("reason", "")[:48]
        lines.append(
            f"{kname:<32} {row.get('status', '?'):<10} "
            f"{(f'{fr:.3f}' if fr is not None else '-'):>10} "
            f"{(f'{br:.3f}' if br is not None else '-'):>10}  {note}")
    return "\n".join(lines)
