"""Device time of one row's top-k selection alone, on the chip:

    chiprun -- python scripts/topk_micro.py [n,k ...]      (default 294912,10)

`ops/scoring.top_k_with_total` (two levels) against `lax.top_k` over the whole
masked row, each as the server compiles it in its two contexts: under one
`vmap` (the row is rank 2: every shard body, on one chip or a shard a chip
inside `manual_shard_region`) and unbatched (rank 1: `query/executor`). The
TPU compiler lowers `lax.top_k` to its `TopK` call at rank 2 only; at rank 1
it sorts the row whole. Time is the device's, from a capture of CALLS calls;
one JSON line a variant, also in chiprun_out/topk_micro.json. PERF.md
section 6 (PR 30) holds the readings.
"""
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.ops.scoring import top_k_with_total

CALLS = 20
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "chiprun_out")


def plain(scores, match, live, k):
    n = live.shape[0]
    ok = match[:n] & live
    v, i = jax.lax.top_k(jnp.where(ok, scores[:n], -jnp.inf), k)
    return v, i, jnp.sum(ok, dtype=jnp.int32)


def device_us(trace_dir):
    """-> (us a call, the three longest operations) of the first TPU plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    ops = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.split(" = ")[0] + " " + (
                    "sort" if " sort(" in ev.name else
                    "TopK" if "TopK" in ev.name else "")
                ops[name] = ops.get(name, 0.0) + ev.duration_ns / 1e3
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:3]
    return (round(sum(ops.values()) / CALLS, 2),
            {name.strip(): round(us / CALLS, 2) for name, us in top})


def main():
    shapes = [tuple(int(x) for x in a.split(",")) for a in sys.argv[1:]]
    rng = np.random.default_rng(7)
    rows = []
    print(jax.devices(), flush=True)
    os.makedirs(OUT, exist_ok=True)
    for n, k in shapes or [(294_912, 10)]:
        args = (jnp.asarray(np.abs(rng.normal(size=n + 1)).astype(np.float32)),
                jnp.asarray(rng.random(n + 1) > 0.5),
                jnp.asarray(rng.random(n) > 0.01))
        want = None
        for select in (plain, top_k_with_total):
            for rank in (2, 1):
                def one(s, m, l, select=select):
                    return select(s, m, l, k)
                fn = jax.jit(jax.vmap(one) if rank == 2 else one)
                xs = tuple(a[None] for a in args) if rank == 2 else args
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(*xs))
                first_call_s = time.perf_counter() - t0
                got = [np.asarray(o).reshape(-1) for o in out]
                want = want or got
                tdir = os.path.join(OUT, "topk_micro_trace")
                jax.profiler.start_trace(tdir)
                for _ in range(CALLS):
                    out = fn(*xs)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                us, top = device_us(tdir)
                rows.append({
                    "n": n, "k": k, "select": select.__name__, "rank": rank,
                    "device_us": us, "first_call_s": round(first_call_s, 2),
                    "same_answer": all(np.array_equal(a, b)
                                       for a, b in zip(got, want)),
                    "longest_ops_us": top})
                print(json.dumps(rows[-1]), flush=True)
    import shutil

    shutil.rmtree(os.path.join(OUT, "topk_micro_trace"), ignore_errors=True)
    with open(os.path.join(OUT, "topk_micro.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
