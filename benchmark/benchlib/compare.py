"""The comparison that decides `correct`: answers taken from the window's
own responses against the plain reference, each number beside its limit.

Numbers (PERF.md section 2 gives the readings each limit was set from):

  total_wrong   answers whose hits.total is not {"value": exact, "relation": "eq"}
  rank_gap      widest gap, over answers and ranks, by which the reference
                score of the served document lies below the reference score of
                the reference's document at that rank, as a share of the latter
                (0 when the lists agree or differ only in documents that tie);
                1 for a list of the wrong length, a repeated or unknown id
  score_gap     widest |served _score - reference score of that document|,
                as a share of the reference score
  order_wrong   adjacent served hits out of (score desc, shard asc, doc asc)
                order by the scores the server itself printed
  repeat_diff   answers to one query that differ from the first answer to it

Reported beside them and not judged (its limit is null): `ids_differ`, the
answers whose list of ids is not the reference's own, so that swaps among
near-ties, which `rank_gap` lets pass within its limit, cannot grow unseen.
"""

from __future__ import annotations

import numpy as np


def draw_sample(seed: int, answered: list[int], pool: list[list[int]],
                size: int) -> list[int]:
    """Up to `size` of the answered queries, drawn from the seed, with one of
    the longest (most terms) always in it."""
    answered = sorted(set(answered))
    if len(answered) <= size:
        return answered
    rng = np.random.default_rng([int(seed), 2])
    pick = set(rng.choice(answered, size=size, replace=False).tolist())
    longest = max(answered, key=lambda q: (len(pool[q]), -q))
    if longest not in pick:
        pick.pop()
        pick.add(longest)
    return sorted(pick)


def _one(ref, terms, answers, k):
    """-> (total_wrong, rank_gap, score_gap, order_wrong, repeat_diff,
    ids_differ) over the answers (Request objects) to one query."""
    sc = ref.scores(terms)
    want_ids, _want_scores, want_total = ref.top(terms, k, scores=sc)
    want_sc = sc[want_ids] if want_ids else np.zeros(0)
    total_wrong = order_wrong = repeat_diff = ids_differ = 0
    rank_gap = score_gap = 0.0
    first = answers[0]
    for a in answers:
        if a is not first and (a.ids, a.total, a.scores) != (
                first.ids, first.total, first.scores):
            repeat_diff += 1
        if a.total != {"value": want_total, "relation": "eq"}:
            total_wrong += 1
        ids = a.ids or []
        ids_differ += ids != want_ids
        bad = (len(ids) != len(want_ids) or len(set(ids)) != len(ids)
               or any(not 0 <= d < ref.n for d in ids))
        if bad:
            rank_gap = score_gap = 1.0
            continue
        if not ids:
            continue
        got_sc = sc[ids]
        rank_gap = max(rank_gap, float(np.max((want_sc - got_sc) / want_sc)))
        served = np.asarray(a.scores, np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            g = np.where(got_sc > 0, np.abs(served - got_sc) / got_sc, 1.0)
        score_gap = max(score_gap, float(np.max(g)))
        shard = ref.shard_of(np.asarray(ids))
        for i in range(len(ids) - 1):
            if served[i] < served[i + 1] or (
                    served[i] == served[i + 1]
                    and (shard[i], ids[i]) >= (shard[i + 1], ids[i + 1])):
                order_wrong += 1
    return total_wrong, rank_gap, score_gap, order_wrong, repeat_diff, ids_differ


def compare(ref, pool: list[list[int]], requests, sample: list[int], k: int,
            limits: dict) -> dict:
    """-> {"correct", "compared", "numbers": {name: {"value", "limit"}}}.
    `requests` are the window's; only those answered 200 are compared, every
    answer to each sampled query."""
    by_query: dict[int, list] = {}
    chosen = set(sample)
    for r in requests:
        if r.ok and r.query in chosen:
            by_query.setdefault(r.query, []).append(r)
    ref.prepare({t for q in by_query for t in pool[q]})
    tot = {"total_wrong": 0, "rank_gap": 0.0, "score_gap": 0.0,
           "order_wrong": 0, "repeat_diff": 0}
    compared = ids_differ = 0
    for q in sorted(by_query):
        tw, rg, sg, ow, rd, idd = _one(ref, pool[q], by_query[q], k)
        compared += len(by_query[q])
        tot["total_wrong"] += tw
        tot["order_wrong"] += ow
        tot["repeat_diff"] += rd
        tot["rank_gap"] = max(tot["rank_gap"], rg)
        tot["score_gap"] = max(tot["score_gap"], sg)
        ids_differ += idd
    numbers = {name: {"value": tot[name], "limit": limits[name]}
               for name in tot}
    correct = compared > 0 and all(
        n["value"] <= n["limit"] for n in numbers.values())
    numbers["ids_differ"] = {"value": ids_differ, "limit": None}
    return {"correct": bool(correct), "compared": compared,
            "queries": len(by_query), "numbers": numbers}
