"""Device-memory accounting, JIT compile tracking, and the per-kernel
utilization snapshot.

Reference parallel: monitor/jvm/JvmStats + monitor/os/OsStats feed the
reference's node stats; here the "JVM" is the XLA runtime, so the node
must account HBM (live array bytes, allocator high-watermark), compile
activity (counts, seconds, executable-cache hit rates — a fresh compile
key mid-serving is this engine's GC-pause analog), and the padded-lane
waste its fixed-shape compilation discipline trades for compile reuse.
"""

from __future__ import annotations

import math
import threading

from ..telemetry import metrics
from .costmodel import device_peaks

_compile_lock = threading.Lock()
_compile_installed = False


def install_compile_listener() -> None:
    """Register jax.monitoring listeners that meter every XLA backend
    compile into the registry (es.jit.compiles counter + es.jit.compile.ms
    histogram) and every program that JAX's persistent compilation cache
    served (es.jit.persistent_cache_hits: `backend_compile_duration` fires
    for those too, so compiles - persistent_cache_hits is what XLA really
    compiled). Idempotent; survives metrics.reset() (the listeners
    re-create their instruments on the next compile)."""
    global _compile_installed
    with _compile_lock:
        if _compile_installed:
            return
        try:
            import jax.monitoring as jmon

            def _on_duration(event: str, duration: float, **_kw):
                if event.endswith("backend_compile_duration"):
                    metrics.counter_inc("es.jit.compiles")
                    metrics.counter_inc("es.jit.compile_time_ms",
                                        duration * 1000.0)
                    metrics.histogram_record("es.jit.compile.ms",
                                             duration * 1000.0)

            def _on_event(event: str, **_kw):
                if event == "/jax/compilation_cache/cache_hits":
                    metrics.counter_inc("es.jit.persistent_cache_hits")

            jmon.register_event_duration_secs_listener(_on_duration)
            jmon.register_event_listener(_on_event)
            # shipped from the start: 0 is a reading (a cold cache), and a
            # missing key is a server without this listener
            metrics.counter_inc("es.jit.persistent_cache_hits", 0)
            _compile_installed = True
        except Exception:  # noqa: BLE001 - older jax: counters stay at 0
            _compile_installed = True


def note_executable_cache(site: str, hit: bool) -> None:
    """Count a framework executable-cache lookup (query/executor compiled
    plans, ops/fused scanned pipelines, the sharded fused arm). A miss
    means the NEXT execution pays trace+XLA compile — the serving-latency
    cliff every cache here exists to avoid."""
    metrics.counter_inc(
        f"es.jit.cache.{'hits' if hit else 'misses'}")
    metrics.counter_inc(
        f"es.jit.cache.{site}.{'hits' if hit else 'misses'}")


def jit_stats() -> dict:
    """Compile + executable-cache counters for _nodes/stats."""
    snap = metrics.snapshot()
    c = snap["counters"]
    h = snap["histograms"].get("es.jit.compile.ms") or {}
    return {
        "compiles": int(c.get("es.jit.compiles", 0)),
        "persistent_cache_hits": int(
            c.get("es.jit.persistent_cache_hits", 0)),
        "compile_time_in_millis": int(c.get("es.jit.compile_time_ms", 0.0)),
        "compile_ms_max": h.get("max", 0.0),
        "executable_cache": {
            "hits": int(c.get("es.jit.cache.hits", 0)),
            "misses": int(c.get("es.jit.cache.misses", 0)),
        },
    }


# ---------------------------------------------------------------------------
# HBM / host memory
# ---------------------------------------------------------------------------

def device_memory_snapshot() -> dict:
    """Live device-array bytes (exact: jax.live_arrays) plus the
    allocator's own view when the backend exposes one (TPU memory_stats:
    bytes_in_use / peak_bytes_in_use / bytes_limit; CPU returns none).
    The live/peak pair is the driver-recorded device-bound proof: HBM
    residency measured, not asserted."""
    import jax

    out: dict = {"backend": None, "device_kind": None,
                 "live_arrays": 0, "live_bytes": 0}
    try:
        d = jax.devices()[0]
        out["backend"] = d.platform
        out["device_kind"] = getattr(d, "device_kind", d.platform)
        live = 0
        count = 0
        # every local device, not only the first: a sharded pack that
        # silently landed on one chip shows here as three empty rows.
        # Shard bytes come from the sharding's shapes: touching
        # `shard.data` would create per-shard arrays that the next call
        # counts as live too
        per_dev = {ld.id: {"id": ld.id, "live_bytes": 0}
                   for ld in jax.local_devices()}
        for a in jax.live_arrays():
            try:
                live += a.nbytes
                count += 1
                shard_bytes = a.dtype.itemsize * math.prod(
                    a.sharding.shard_shape(a.shape))
                for d in a.sharding.addressable_devices:
                    per_dev[d.id]["live_bytes"] += shard_bytes
            except Exception:  # noqa: BLE001 - deleted buffer race
                continue
        for ld in jax.local_devices():
            try:
                ms = ld.memory_stats() or {}
            except Exception:  # noqa: BLE001 - backend without memory stats
                ms = {}
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                if key in ms:
                    per_dev[ld.id][key] = int(ms[key])
        out["devices"] = list(per_dev.values())
        out["live_arrays"] = count
        out["live_bytes"] = int(live)
        out["device_count"] = len(jax.devices())
        from ..parallel.spmd import spmd_mode

        # the slice execution model (PR 10): pjit = GSPMD sharded pack +
        # on-device all-gather merge; shardmap = legacy per-shard bodies
        out["spmd_mode"] = spmd_mode()
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - backend without memory stats
            stats = None
        if stats:
            for src, dst in (("bytes_in_use", "bytes_in_use"),
                             ("peak_bytes_in_use", "peak_bytes_in_use"),
                             ("bytes_limit", "bytes_limit"),
                             ("largest_free_block_bytes",
                              "largest_free_block_bytes")):
                if src in stats:
                    out[dst] = int(stats[src])
    except Exception:  # noqa: BLE001 - no backend at all
        pass
    return out


def pack_padded_waste(sp) -> int:
    """Bytes of a StackedPack occupied by PADDING (docs padded to n_max
    per shard, posting blocks padded to nb_max) — the HBM rent paid for
    uniform SPMD shapes. Shape arithmetic only: no array is scanned."""
    S = max(sp.S, 1)
    doc_slots = S * max(sp.n_max, 1)
    real_docs = sum(p.num_docs for p in sp.shards)
    doc_pad = max(doc_slots - real_docs, 0) / doc_slots
    blk_slots = S * max(sp.nb_max, 1)
    real_blocks = sum(p.num_blocks for p in sp.shards)
    blk_pad = max(blk_slots - real_blocks, 0) / blk_slots
    waste = 0.0
    for arr in (sp.post_docids, sp.post_tfs, sp.post_dls):
        waste += arr.nbytes * blk_pad
    doc_arrays = [sp.live]
    doc_arrays.extend(sp.norms.values())
    doc_arrays.extend(sp.text_present.values())
    if sp.dense_tf is not None:
        doc_arrays.append(sp.dense_tf)
    for col in sp.stacked_docvalues.values():
        doc_arrays.append(col.values)
        doc_arrays.append(col.has_value)
    for vc in sp.vectors.values():
        doc_arrays.append(vc.values)
        doc_arrays.append(vc.has_value)
    for arr in doc_arrays:
        waste += arr.nbytes * doc_pad
    return int(waste)


def padded_waste_bytes(engine) -> int:
    """Padded-lane waste across every resident searcher of the node.
    Reads the private tier handles directly — the `searcher` property
    force-merges tiers as a side effect, which a stats read must never
    trigger."""
    total = 0
    for idx in engine.indices.values():
        for s in idx.tier_searchers():
            try:
                total += pack_padded_waste(s.sp)
            except Exception:  # noqa: BLE001 - stats must not fail
                continue
    # tenant superpacks (PR 17) rent additional padded HBM: vacant lanes
    # + per-lane size-class padding, the same accounting over the shared
    # layout (the manager reuses pack_padded_waste via a lane shim)
    if engine._superpacks is not None:
        try:
            total += engine._superpacks.padded_waste_bytes()
        except Exception:  # noqa: BLE001 - stats must not fail
            pass
    return total


# ---------------------------------------------------------------------------
# the utilization snapshot (per kernel, cumulative)
# ---------------------------------------------------------------------------

def kernel_utilization() -> dict:
    """{kernel_name: {calls, wall_ms, flops, bytes, mfu, bw_util,
    mfu_p50, mfu_max}} aggregated from the registry's per-kernel
    instruments (time_kernel feeds them on every dispatch). Cumulative
    MFU = total flops / total wall seconds / peak — the number future
    perf PRs are judged against."""
    snap = metrics.snapshot()
    counters = snap["counters"]
    hists = snap["histograms"]
    peak_f, peak_b, kind = device_peaks()
    out: dict = {}
    for name, h in hists.items():
        if not (name.startswith("es.kernel.") and name.endswith(".ms")):
            continue
        kname = name[len("es.kernel."):-len(".ms")]
        flops = counters.get(f"es.kernel.{kname}.flops", 0.0)
        nbytes = counters.get(f"es.kernel.{kname}.bytes", 0.0)
        sec = max(h["sum"] / 1000.0, 1e-9)
        entry = {
            "calls": h["count"],
            "wall_ms": round(h["sum"], 3),
            "wall_ms_p50": round(h["p50"], 3),
            "flops": flops,
            "bytes": nbytes,
            "mfu": round(flops / sec / peak_f, 6),
            "bw_util": round(nbytes / sec / peak_b, 6),
        }
        mh = hists.get(f"es.kernel.{kname}.mfu_pct")
        if mh:
            entry["mfu_pct_p50"] = round(mh["p50"], 4)
            entry["mfu_pct_max"] = round(mh["max"], 4)
        out[kname] = entry
    # PR 12: per-kernel analytic-vs-XLA drift (the compiled-program
    # cross-check) rides the utilization section, so a reader of the
    # roofline numbers sees how much to trust the numerator
    from .xla_introspect import OBSERVATIONS, drift_table

    for kname, entry in out.items():
        o = OBSERVATIONS.get(kname)
        if o is not None and "drift" in o:
            entry["xla_drift"] = dict(o["drift"])
    return {"device_kind": kind, "peak_flops": peak_f,
            "peak_bytes_per_sec": peak_b, "kernels": out,
            "costmodel_drift": drift_table()}


def device_stats(engine=None) -> dict:
    """The `_nodes/stats` device section: memory + utilization + jit."""
    out = {
        "memory": device_memory_snapshot(),
        "utilization": kernel_utilization(),
        "jit": jit_stats(),
    }
    if engine is not None:
        out["memory"]["pack_padded_waste_bytes"] = padded_waste_bytes(engine)
    return out
