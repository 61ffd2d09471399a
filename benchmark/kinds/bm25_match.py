"""The kind `bm25_match`: text in one field, `match` queries, BM25 top-k.

A configuration with no `kind` is of this kind. Every seam delegates to the
modules of `benchlib/` that have served it from the start: the generator
(`corpus.py`), the plain reference (`reference.py`, with `smallfloat.py` and
`routing.py`) and the comparison (`compare.py`). Keys of the configuration
it reads: `documents`, `corpus`, `query`, `search` (`field`, `size`),
`number_of_shards`, `limits`; of the traffic mix: `pool`, `check_sample`.
"""

from __future__ import annotations

import numpy as np

from benchlib import compare as cmp
from benchlib import corpus as gen
from benchlib.reference import Reference


def corpus(seed: int, config: dict) -> gen.Corpus:
    return gen.build_corpus(seed, int(config["documents"]), config["corpus"])


def pool(seed: int, corpus: gen.Corpus, config: dict,
         traffic: dict) -> list[list[int]]:
    return gen.build_pool(seed, corpus, config["query"], int(traffic["pool"]))


def body(query: list[int], config: dict) -> dict:
    return gen.search_body(query, config["search"])


def index_body(config: dict) -> dict:
    return {"settings": {"number_of_shards": int(config["number_of_shards"]),
                         "number_of_replicas": 0},
            "mappings": {"properties": {
                config["search"]["field"]: {"type": "text"}}}}


def bulk(corpus: gen.Corpus, config: dict):
    """-> payload(lo, hi): the `_bulk` body of documents lo..hi-1."""
    field = config["search"]["field"]
    words = np.array([f"t{i}" for i in range(corpus.vocab)])

    def payload(lo: int, hi: int) -> bytes:
        return gen.bulk_payload(corpus, field, lo, hi, words)

    return payload


def digest(res: dict) -> tuple:
    """What a request keeps of one answer: `took`, the hits' ids as ints,
    their `_score` and `hits.total` as answered."""
    hits = res["hits"]
    return (float(res["took"]), [int(h["_id"]) for h in hits["hits"]],
            [h["_score"] for h in hits["hits"]], hits["total"])


def judge(seed: int, corpus: gen.Corpus, pool: list[list[int]], requests,
          config: dict, traffic: dict) -> dict:
    """Every answer the window gave to a sample of its answered queries,
    drawn from the seed, against the NumPy BM25 (`compare.py` lists the
    numbers)."""
    ref = Reference(corpus.lens, corpus.tok, int(config["number_of_shards"]))
    sample = cmp.draw_sample(seed, [r.query for r in requests if r.ok], pool,
                             int(traffic["check_sample"]))
    return cmp.compare(ref, pool, requests, sample,
                       int(config["search"]["size"]), config["limits"])


def df(corpus: gen.Corpus) -> np.ndarray:
    """[vocab] documents that hold each term (`postings_roofline` reads it)."""
    doc = np.repeat(np.arange(corpus.n_docs, dtype=np.int64), corpus.lens)
    pairs = np.unique(corpus.tok.astype(np.int64) * corpus.n_docs + doc)
    return np.bincount(pairs // corpus.n_docs, minlength=corpus.vocab)
