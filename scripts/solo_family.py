#!/usr/bin/env python3
"""Count the family of solo `match` programs a cell's pool reaches, on the
host alone (the corpus and pool by the benchmark's own generator; no device,
no server):

    python3 scripts/solo_family.py [--workload passage.solo.fresh]
                                   [--seed N] [--second-seed M]

The sibling of `scripts/wave_family.py`. From the cell's configuration and mix
it makes the corpus and the pool as `benchmark/run.py` does and finds each
query's dense terms and sparse posting-block rows by the pack's own rule
(`index/pack.default_dense_min_df`: a term of max(64, N // 256) documents or
more lies in the dense tier; a sparse term holds ceil(df / 128) blocks). Then:

  * the plan shapes of before PR 38: one program a distinct ordered tuple of
    per-term keys (`("term_dense",)` or `("term_imp", rows bucket)`, the
    bucket a power of two >= 4);
  * the match family's programs the pool reaches: `query/nodes.match_tiers`,
    the program's own ladders, of (dense terms, sparse rows) a query;
  * the ladders' product: every (dense tier, rows tier) a query of the mix's
    lengths can reach on this pack (at most the mix's longest query's count
    of distinct terms, each sparse one 1 to the longest sparse term's
    blocks), the bound on the family whatever the seed;
  * a second seed's pool: what it reaches, that none of it lies outside the
    product, and what a server warmed on the first pool would still compile
    for it.

Single-shard cells only (a mesh's tiers are its largest shard's)."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)

from benchlib import corpus as gen  # noqa: E402

BLOCK = 128


def cell_files(workload: str) -> tuple[dict, dict]:
    """-> (configuration, traffic mix) of a cell of BENCHMARK.json."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    return config, traffic


def corpus_df(config: dict, seed: int):
    """-> (corpus, df a term, dense_min_df) of one seed's corpus."""
    n_docs = int(config["documents"])
    c = gen.build_corpus(seed, n_docs, config["corpus"])
    doc = np.repeat(np.arange(c.n_docs, dtype=np.int64), c.lens)
    pairs = np.unique(c.tok.astype(np.int64) * c.n_docs + doc)
    df = np.bincount(pairs // c.n_docs, minlength=c.vocab)
    return c, df, max(64, n_docs // 256)


def shapes(pool, df, dense_min_df) -> list[tuple[int, int]]:
    """(dense terms, sparse posting-block rows) of each query."""
    nb = -(-df // BLOCK)
    return [(sum(int(df[t] >= dense_min_df) for t in q),
             sum(int(nb[t]) for t in q if 0 < df[t] < dense_min_df))
            for q in pool]


def per_term_keys(pool, df, dense_min_df) -> set:
    """The plan shapes of before PR 38 (query/nodes.py at 356d72a)."""
    nb = -(-df // BLOCK)

    def bucket(n):
        b = 4
        while b < n:
            b *= 2
        return b

    return {tuple(("term_dense",) if df[t] >= dense_min_df
                  else ("term_imp", bucket(int(nb[t]))) for t in q)
            for q in pool}


def family(sizes, tiers) -> dict:
    """{(dense tier, rows tier): queries} the pool reaches."""
    out: dict = {}
    for d, r in sizes:
        key = tiers(d, r)
        out[key] = out.get(key, 0) + 1
    return out


def product(max_terms: int, max_blocks: int, tiers) -> set:
    """Every (dense tier, rows tier) a query of at most `max_terms` distinct
    terms reaches, a sparse term of 1 to `max_blocks` blocks."""
    out = set()
    for d in range(max_terms + 1):
        for s in range(max_terms - d + 1):
            if d + s == 0:
                continue
            for r in range(s, s * max_blocks + 1):
                out.add(tiers(d, r))
    return out


def pad_share(sizes, tiers) -> float:
    """1 - rows gathered for real / rows with padding, over the pool."""
    real = sum(d + r for d, r in sizes)
    padded = sum(sum(tiers(d, r)) for d, r in sizes)
    return 1.0 - real / padded


def count(workload: str, seed: int, second_seed: int) -> dict:
    """Everything main() prints, as numbers (tests/test_match_family.py)."""
    from elasticsearch_tpu.query import nodes

    config, traffic = cell_files(workload)
    min_rows = config.get("settings", {}).get("search.solo.min_rows_tier")
    saved = nodes.MATCH_MIN_ROWS
    if min_rows is not None:
        from elasticsearch_tpu.ops.batched import BatchTermSearcher

        nodes.MATCH_MIN_ROWS = BatchTermSearcher.pow2_tier(int(min_rows))
    try:
        tiers = nodes.match_tiers
        out = {"workload": workload, "pool": int(traffic["pool"]),
               "min_rows_tier": nodes.MATCH_MIN_ROWS}
        max_terms = max(int(k) for k in config["query"]["terms_share"])
        for name, s in (("first", seed), ("second", second_seed)):
            c, df, dense_min_df = corpus_df(config, s)
            pool = gen.build_pool(s, c, config["query"], int(traffic["pool"]))
            sizes = shapes(pool, df, dense_min_df)
            max_blocks = int(-(-int(df[df < dense_min_df].max()) // BLOCK))
            out[name] = {
                "seed": s, "dense_min_df": dense_min_df,
                "dense_terms": int((df >= dense_min_df).sum()),
                "longest_sparse_blocks": max_blocks,
                "per_term_shapes": len(per_term_keys(pool, df, dense_min_df)),
                "family": family(sizes, tiers),
                "pad_share": pad_share(sizes, tiers),
                "product": product(max_terms, max_blocks, tiers),
            }
        first, second = out["first"], out["second"]
        out["second_outside_product"] = sorted(
            set(second["family"]) - second["product"])
        out["second_beyond_first"] = sorted(
            set(second["family"]) - set(first["family"]))
        return out
    finally:
        nodes.MATCH_MIN_ROWS = saved


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="passage.solo.fresh")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--second-seed", type=int, default=12)
    args = ap.parse_args()
    out = count(args.workload, args.seed, args.second_seed)
    print(f"{out['workload']}: pool {out['pool']}, rows tiers from "
          f"{out['min_rows_tier']}")
    for name in ("first", "second"):
        o = out[name]
        fam = dict(sorted(o["family"].items()))
        print(f"seed {o['seed']}: dense_min_df {o['dense_min_df']}, "
              f"{o['dense_terms']} dense terms, longest sparse term "
              f"{o['longest_sparse_blocks']} blocks")
        print(f"  per-term plan shapes (before PR 38): {o['per_term_shapes']}")
        print(f"  match family reached: {len(fam)} programs (dense tier, rows "
              f"tier): queries {fam}")
        print(f"  rows padded: {100 * o['pad_share']:.1f} % of those gathered")
        print(f"  the ladders' product for the mix's lengths: "
              f"{len(o['product'])} programs {sorted(o['product'])}")
    print(f"second seed outside the product: {out['second_outside_product']}")
    print(f"second seed beyond the first pool's programs: "
          f"{out['second_beyond_first']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
