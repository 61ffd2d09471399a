"""The generator of the kind `bm25_match` (`kinds/bm25_match.py`): corpus and
query pool from `--seed` and the parameters of a configuration file
(`corpus`, `query`) and a traffic file (`pool`). Grown from chip_smoke.py's
`build_corpus` / `sample_queries` (themselves bench.py's); the original stays
where it is.

Documents are token-id arrays; `bulk_payload` turns id `i` into the word `t<i>`.
Corpus and queries come from two independent streams of the seed, so that
the pool's size never changes the corpus.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Corpus:
    lens: np.ndarray    # [n_docs] tokens per document
    tok: np.ndarray     # [sum(lens)] term ids, document after document
    starts: np.ndarray  # [n_docs] offset of each document in `tok`
    vocab: int

    @property
    def n_docs(self) -> int:
        return len(self.lens)


def _rng(seed: int, stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, not {seed}")
    return np.random.default_rng([int(seed), stream])


def doc_lengths(rng, documents: int, mean: float, sd: float, lo: int):
    """Negative-binomial lengths (a Poisson whose rate is Gamma-distributed)
    of the given mean and standard deviation, clipped below at `lo`."""
    extra = sd * sd - mean
    if extra <= 0:
        raise ValueError(f"doc_len_sd {sd} is no wider than a Poisson's "
                         f"{mean ** 0.5:.1f} at mean {mean}")
    rate = rng.gamma(mean * mean / extra, extra / mean, size=documents)
    return rng.poisson(rate, size=documents).clip(lo, None)


def term_counts(shares: dict, pool: int) -> np.ndarray:
    """`pool` term counts in the shares given ({"3": 13, ...}, any scale), by
    largest remainder: the same multiset for every seed."""
    counts = sorted(int(k) for k in shares)
    w = np.array([float(shares[str(k)]) for k in counts])
    if not counts or counts[0] < 1 or (w <= 0).any():
        raise ValueError(f"bad query terms_share {shares!r}")
    exact = w / w.sum() * pool
    n = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - n), kind="stable")[:pool - n.sum()]:
        n[i] += 1
    return np.repeat(counts, n)


def build_corpus(seed: int, documents: int, spec: dict) -> Corpus:
    """`spec` is a configuration's `corpus` object."""
    if spec.get("generator") != "zipf_text":
        raise ValueError(f"unknown corpus generator {spec.get('generator')!r}")
    vocab = int(spec["vocab"])
    rng = _rng(seed, 0)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(spec["zipf_s"])
    p /= p.sum()
    lens = doc_lengths(rng, documents, float(spec["doc_len_mean"]),
                       float(spec["doc_len_sd"]), int(spec["doc_len_min"]))
    tok = rng.choice(vocab, size=int(lens.sum()), p=p).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
    return Corpus(lens=lens, tok=tok, starts=starts, vocab=vocab)


def build_pool(seed: int, corpus: Corpus, spec: dict, pool: int) -> list[list[int]]:
    """`spec` is a configuration's `query` object. Every seed gets the same
    multiset of word counts (`terms_share`), in another order: the seed
    chooses documents and words, not the sizes. A query is that many words
    of one real document, deduplicated in query order."""
    if spec.get("from") != "documents":
        raise ValueError(f"bad query spec {spec!r}")
    rng = _rng(seed, 1)
    n_terms = term_counts(spec["terms_share"], pool)
    rng.shuffle(n_terms)
    out = []
    for d, n in zip(rng.integers(0, corpus.n_docs, size=pool), n_terms):
        at = corpus.starts[d] + rng.integers(0, corpus.lens[d], size=int(n))
        out.append([int(t) for t in dict.fromkeys(corpus.tok[at].tolist())])
    return out


def search_body(terms: list[int], spec: dict) -> dict:
    """`spec` is a configuration's `search` object."""
    return {"query": {"match": {spec["field"]: " ".join(f"t{t}" for t in terms)}},
            "size": int(spec["size"]), "_source": False,
            "track_total_hits": True}


def bulk_payload(corpus: Corpus, field: str, lo: int, hi: int,
                 words: np.ndarray) -> bytes:
    """One `_bulk` body for documents lo..hi-1; `_id` is the document's
    index, so insertion order is id order."""
    ids = corpus.tok[corpus.starts[lo]:corpus.starts[hi - 1] + corpus.lens[hi - 1]]
    w = words[ids]
    off, lines = 0, []
    for d in range(lo, hi):
        n = corpus.lens[d]
        lines.append('{"index":{"_id":"%d"}}' % d)
        lines.append('{"%s":"%s"}' % (field, " ".join(w[off:off + n])))
        off += n
    return ("\n".join(lines) + "\n").encode()
