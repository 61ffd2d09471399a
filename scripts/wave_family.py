#!/usr/bin/env python3
"""Count the family of wave programs a cell's traffic can reach, on the host
alone (NumPy; no JAX, no server):

    python3 scripts/wave_family.py [--workload passage.wave.c64] [--seed N]
                                   [--waves 20000]

From the cell's configuration and mix it makes the corpus and the pool as
`benchmark/run.py` does, finds each query's dense terms, sparse terms and
posting blocks by the pack's own rule (`index/pack.default_dense_min_df`:
a term of max(64, N // 256) documents or more lies in the dense tier; a
sparse term holds ceil(df / 128) blocks), and applies the ladders of
`ops/batched.py` (`wave_q_tier`: powers of two; `wave_ts_tier`: 4, 16, 64;
`wave_b_tier`: 8, 32, 128; `wave_r_tier`: 64, 256, 1,024; `wave_td_tier`:
16, 64) to

  * every wave the mix can form: 1 to `clients` members, any of the pool
    (the bounds of the family), and
  * `--waves` waves drawn from the seed, sizes 1 to `clients` (what random
    membership reaches, and how often each member of the family).

The fused arm's program is keyed by (R, Td) (its batch is always one
512-row chunk; `bud` follows R); the exact arm's, which serves the fused
arm's flagged queries padded to `parallel/sharded.ESCALATION_MIN_TIER` (8)
at least, by (Q tier, Ts, B). Which queries the fused pass flags is the
device's to say, so the exact arm is counted over the first 1 to
`--flagged` members of each wave."""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import corpus as gen  # noqa: E402

BLOCK = 128


def pow2(n: int, floor: int = 1) -> int:
    return max(floor, 1 << max(n - 1, 0).bit_length())


def steps_of_four(n: int, floor: int) -> int:
    t = floor
    while t < n:
        t *= 4
    return t


def b_tier(nb: int) -> int:
    return steps_of_four(nb, 8)


def ts_tier(ts: int) -> int:
    return steps_of_four(ts, 4)


ESCALATION_MIN_TIER = 8


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="passage.wave.c64")
    ap.add_argument("--seed", type=int, default=3500000011)
    ap.add_argument("--waves", type=int, default=20000)
    ap.add_argument("--max-size", type=int, default=None,
                    help="largest wave drawn (default: the mix's clients)")
    ap.add_argument("--flagged", type=int, default=4,
                    help="largest count of flagged queries a wave is given")
    args = ap.parse_args()
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    n_docs, clients = int(config["documents"]), int(traffic["clients"])
    c = gen.build_corpus(args.seed, n_docs, config["corpus"])
    pool = gen.build_pool(args.seed, c, config["query"], int(traffic["pool"]))
    doc = np.repeat(np.arange(c.n_docs, dtype=np.int64), c.lens)
    pairs = np.unique(c.tok.astype(np.int64) * c.n_docs + doc)
    df = np.bincount(pairs // c.n_docs, minlength=c.vocab)
    dense_min_df = max(64, n_docs // 256)
    nb = -(-df // BLOCK)
    q_td = np.array([sum(df[t] >= dense_min_df for t in q) for q in pool])
    q_ts = np.array([sum(0 < df[t] < dense_min_df for t in q) for q in pool])
    q_rows = np.array([sum(int(nb[t]) for t in q if 0 < df[t] < dense_min_df)
                       for q in pool])
    q_b = np.array([max([int(nb[t]) for t in q if 0 < df[t] < dense_min_df]
                        or [0]) for q in pool])
    print(f"pack: {n_docs} docs, dense_min_df {dense_min_df}, "
          f"{int((df >= dense_min_df).sum())} dense terms, longest sparse "
          f"term {int(nb[df < dense_min_df].max())} blocks")
    print(f"pool: {len(pool)} queries; a query: dense terms mean "
          f"{q_td.mean():.2f} max {q_td.max()}, sparse terms mean "
          f"{q_ts.mean():.2f} max {q_ts.max()}, block rows mean "
          f"{q_rows.mean():.2f} max {q_rows.max()}")

    # the bounds: every wave of 1..clients members of the pool
    r_max = steps_of_four(int(np.sort(q_rows)[-clients:].sum()), 64)
    r_ladder = [r for r in (64 << (2 * i) for i in range(16)) if r <= r_max]
    td_ladder = sorted({steps_of_four(int(t), 16) for t in q_td})
    ts_ladder = sorted({ts_tier(int(t)) for t in q_ts if t})
    b_ladder = sorted({b_tier(int(b)) for b in q_b if b})
    q_ladder = [q for q in (1 << i for i in range(clients.bit_length() + 1))
                if ESCALATION_MIN_TIER <= q <= pow2(clients)]
    print(f"fused arm: R in {r_ladder} x Td in {td_ladder} = "
          f"{len(r_ladder) * len(td_ladder)} programs at most")
    print(f"exact arm: Q tier in {q_ladder} x Ts in {ts_ladder} x B in "
          f"{b_ladder} = {len(q_ladder) * len(ts_ladder) * len(b_ladder)} "
          f"programs at most; with at most {args.flagged} flagged a wave, "
          f"{len([q for q in q_ladder if q <= pow2(args.flagged, ESCALATION_MIN_TIER)]) * len(ts_ladder) * len(b_ladder)}")

    # what waves of random membership reach
    rng = np.random.default_rng([args.seed, 7])
    fused: dict = {}
    exact: dict = {}
    for _ in range(args.waves):
        size = int(rng.integers(1, (args.max_size or clients) + 1))
        members = rng.choice(len(pool), size=size, replace=False)
        key = (steps_of_four(int(q_rows[members].sum()), 64),
               steps_of_four(int(q_td[members].max()), 16))
        fused[key] = fused.get(key, 0) + 1
        flagged = members[:int(rng.integers(1, args.flagged + 1))]
        flagged = flagged[q_ts[flagged] > 0]
        if len(flagged):
            key = (pow2(len(flagged), ESCALATION_MIN_TIER),
                   ts_tier(int(q_ts[flagged].max())),
                   b_tier(int(q_b[flagged].max())))
            exact[key] = exact.get(key, 0) + 1
    print(f"{args.waves} waves of 1..{args.max_size or clients} random members reach "
          f"{len(fused)} fused programs (R, Td): "
          f"{dict(sorted(fused.items()))}")
    print(f"their first 1..{args.flagged} members, flagged, reach "
          f"{len(exact)} exact programs (Q tier, Ts, B): "
          f"{dict(sorted(exact.items()))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
