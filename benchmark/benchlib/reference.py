"""The plain reference: BM25 with Lucene's norm quantisation in NumPy
float64, from the generator's own arrays. Grown from chip_smoke.py's
`Reference`. It imports nothing of elasticsearch_tpu and takes nothing the
server made; `smallfloat.py` and `routing.py` beside it are copies.

`precision="bf16"` is the control of PERF.md: the same arithmetic with every
per-term contribution and every running sum rounded to bfloat16, the nearest
precision below the float32 the configuration states. It stands in the
program's place in tests and in `benchmark/control.py`; no run of the
benchmark itself computes it.
"""

from __future__ import annotations

import math

import numpy as np

from .routing import shard_for_id
from .smallfloat import quantize_lengths


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even), returned
    as float64."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


class Reference:
    def __init__(self, lens, tok, num_shards: int = 1, k1: float = 1.2,
                 b: float = 0.75, precision: str = "f64"):
        if precision not in ("f64", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.n = len(lens)
        self.tok = np.asarray(tok)
        self.doc_of_tok = np.repeat(np.arange(self.n, dtype=np.int64), lens)
        self.dl = quantize_lengths(lens).astype(np.float64)
        self.avgdl = float(np.sum(lens)) / self.n
        self.num_shards = num_shards
        self.k1, self.b = k1, b
        self.precision = precision
        self._postings: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._shard = None

    def prepare(self, terms) -> None:
        """One pass over the token stream for every term the checked
        queries use: term -> (docs ascending, tf)."""
        want = np.unique(np.asarray(sorted(terms), np.int64))
        if want.size == 0:
            return
        sel = np.isin(self.tok, want)
        key = self.tok[sel].astype(np.int64) * self.n + self.doc_of_tok[sel]
        uniq, tf = np.unique(key, return_counts=True)
        t_of, d_of = uniq // self.n, uniq % self.n
        bounds = np.searchsorted(t_of, np.append(want, want[-1] + 1))
        for i, t in enumerate(want.tolist()):
            lo, hi = bounds[i], bounds[i + 1]
            self._postings[t] = (d_of[lo:hi], tf[lo:hi].astype(np.float64))

    def df(self, term: int) -> int:
        return len(self._postings[term][0])

    def scores(self, terms: list[int]) -> np.ndarray:
        """-> [n] score of every document (0 where no term matches)."""
        low = self.precision == "bf16"
        scores = np.zeros(self.n, np.float64)
        for t in terms:
            docs, tf = self._postings[t]
            df = len(docs)
            idf = math.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            norm = self.k1 * (1.0 - self.b + self.b * self.dl[docs] / self.avgdl)
            part = idf * tf / (tf + norm)
            if low:
                scores[docs] = to_bf16(scores[docs] + to_bf16(part))
            else:
                scores[docs] += part
        return scores

    def shard_of(self, docs: np.ndarray) -> np.ndarray:
        if self.num_shards == 1:
            return np.zeros(len(docs), np.int64)
        return np.array([shard_for_id(str(d), self.num_shards)
                         for d in np.asarray(docs).tolist()], np.int64)

    def top(self, terms: list[int], k: int = 10, scores=None):
        """-> (top-k doc indices in rank order, their scores, exact total).
        Order is (score desc, shard asc, doc asc): SearchPhaseController's;
        within a shard local doc order is insertion order, which is id
        order as the loader sends them."""
        if scores is None:
            scores = self.scores(terms)
        hit = np.flatnonzero(scores > 0)
        total = int(hit.size)
        if total == 0:
            return [], [], 0
        k = min(k, total)
        kth = np.partition(scores[hit], total - k)[total - k]
        cand = hit[scores[hit] >= kth]  # the top-k plus every tie at its edge
        order = np.lexsort((cand, self.shard_of(cand), -scores[cand]))[:k]
        top = cand[order]
        return top.tolist(), scores[top].tolist(), total
