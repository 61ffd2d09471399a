"""Host time of one search's launch and fetch alone, on the chip:

    chiprun -- python3 scripts/fetch_micro.py                  (one chip)
    chiprun --chips 4 -- python3 scripts/fetch_micro.py --devices 4

A jitted function of the cells' output shapes (top-10 of a resident row:
`f32[k]`, `i32[k]`, `i32[k]`, `i32[]`) returning (a) `four`: the four arrays,
as `search_solo` did up to PR 31, and (b) `one`: the same 3k+1 words in one
`int32` buffer, as `param_pack.pack_outputs` lays them. With --devices 4 the
row is sharded over a mesh and the outputs are replicated, as the pjit
program's merged rows are. Each call is handed one small host array, as a
dispatch is. For each variant, over CALLS calls: host time of the call, of
`jax.device_get` right behind it (what `engine.fetch` waits for), and of
`device_get` behind a `block_until_ready` (the copies alone, the program
already done); from a capture of CAPTURED calls, the runtime's host events
that happen at least every other call (allocations, copies, the launch), a
call. One JSON line, also in
chiprun_out/fetch_micro_<devices>.json.
PERF.md section 6 (PR 32) holds the readings.
"""
import argparse
import glob
import json
import os
import shutil
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CAPTURED = 200
SHARD = 8192           # the row is four shards' worth: its index splits in two
N = 4 * SHARD
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "chiprun_out")


def programs(mesh, k):
    """-> {variant: jitted fn(row, host_words)} over one resident row."""
    replicate = ((lambda x: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P()))) if mesh is not None else (lambda x: x))

    def four(row, words):
        row = replicate(row + words[0, 0].astype(jnp.float32))
        scores, idx = jax.lax.top_k(row, k)
        return (scores, (idx // SHARD).astype(jnp.int32),
                (idx % SHARD).astype(jnp.int32),
                jnp.sum(row > 0.5, dtype=jnp.int32))

    def one(row, words):
        scores, shard, doc, total = four(row, words)
        return replicate(jnp.concatenate([
            jax.lax.bitcast_convert_type(scores, jnp.int32), shard, doc,
            total.reshape(1)]))

    return {"four": jax.jit(four), "one": jax.jit(one)}


def time_calls(fn, row, calls):
    """-> ms a call (median and mean): the call, the get behind it, and the
    get behind a finished program."""
    words = np.zeros((1, 64), np.int32)
    call, get, copy = [], [], []
    for ready_first in (False, True):
        for i in range(calls + 50):
            words[0, 0] = i & 1
            t0 = time.perf_counter()
            out = fn(row, words)
            t1 = time.perf_counter()
            if ready_first:
                jax.block_until_ready(out)
                t1 = time.perf_counter()
            jax.device_get(out)
            t2 = time.perf_counter()
            if i < 50:
                continue
            if ready_first:
                copy.append(t2 - t1)
            else:
                call.append(t1 - t0)
                get.append(t2 - t1)

    def ms(xs):
        return {"p50": round(statistics.median(xs) * 1e3, 4),
                "mean": round(statistics.fmean(xs) * 1e3, 4)}

    return {"call_ms": ms(call), "get_ms": ms(get),
            "get_after_ready_ms": ms(copy)}


def host_events(fn, row, tdir):
    """-> the runtime's recurring host events, count and time a call."""
    from jax.profiler import ProfileData

    words = np.zeros((1, 64), np.int32)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir, profiler_options=options)
    for _ in range(CAPTURED):
        jax.device_get(fn(row, words))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        tdir, "plugins/profile/*/*.xplane.pb")))[-1]
    seen: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                n, ns = seen.get(ev.name, (0, 0.0))
                seen[ev.name] = (n + 1, ns + ev.duration_ns)
    return {name: {"a_call": round(n / CAPTURED, 2),
                   "us_a_call": round(ns / 1e3 / CAPTURED, 1)}
            for name, (n, ns) in sorted(seen.items())
            if n >= CAPTURED // 2}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--calls", type=int, default=2000)
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()
    devices = jax.devices()[:args.devices]
    if len(devices) < args.devices:
        raise SystemExit(f"{args.devices} devices asked, {len(devices)} here")
    mesh = Mesh(np.array(devices), ("shards",)) if args.devices > 1 else None
    row = np.random.default_rng(7).random(N).astype(np.float32)
    row = (jax.device_put(row, NamedSharding(mesh, P("shards")))
           if mesh is not None else jax.device_put(row, devices[0]))
    os.makedirs(OUT, exist_ok=True)
    tdir = os.path.join(OUT, "fetch_micro_trace")
    result = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "devices": args.devices, "calls": args.calls, "k": args.k}
    for name, fn in programs(mesh, args.k).items():
        out = jax.device_get(fn(row, np.zeros((1, 64), np.int32)))
        result[name] = {
            "device_arrays": len(jax.tree_util.tree_leaves(out)),
            **time_calls(fn, row, args.calls),
            "host_events": host_events(fn, row, tdir)}
    shutil.rmtree(tdir, ignore_errors=True)
    for key in ("call_ms", "get_ms", "get_after_ready_ms"):
        result[f"saved_{key}_p50"] = round(
            result["four"][key]["p50"] - result["one"][key]["p50"], 4)
    with open(os.path.join(OUT, f"fetch_micro_{args.devices}.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
