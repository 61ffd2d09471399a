"""Shard-level query execution: pack on device + compiled plan cache.

This is the TPU analog of the reference's per-shard query phase (reference
behavior: search/query/QueryPhase.java:61-149 — build collectors, run the
searcher, emit QuerySearchResult of top-k docids/scores + total). One
`ShardSearcher` owns the device-resident pack; each distinct query *shape*
(plan structure + block-bucket sizes + k) compiles once and is cached, so
steady-state queries are a single XLA executable launch with small host->
device parameter transfers (block row lists, idf weights).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..index.pack import ShardPack
from ..ops.scoring import top_k_with_total
from ..utils.errors import IllegalArgumentError
from .dsl import parse_query
from .nodes import ExecContext, QueryNode


def pack_to_device(pack: ShardPack, device=None) -> dict:
    """Ship a host ShardPack to HBM as a flat dict-of-arrays pytree.

    The single-shard twin of `parallel/sharded.stacked_to_device`: the
    host tree is built first, then placed in one tree_map pass — leaf
    PATHS here are the same vocabulary the stacked path's partition-rule
    table (parallel/spmd.PACK_PARTITION_RULES) matches against, so a new
    component added here without a rule fails the stacked upload (and
    tests/test_spmd.py's table lint) instead of silently replicating."""
    from ..utils.jax_env import ensure_x64

    ensure_x64()
    host = _pack_host_tree(pack)
    import jax.tree_util as jtu

    put = (lambda x: jax.device_put(x, device)) if device else jnp.asarray
    return jtu.tree_map(put, host)


def _pack_host_tree(pack: ShardPack) -> dict:
    put = np.asarray
    dev = {
        "post_docids": put(pack.post_docids),
        "post_tfs": put(pack.post_tfs),
        "post_dls": put(pack.post_dls),
        # [N]-aligned doc lengths: phrase scoring normalizes its per-doc
        # phrase frequency elementwise against these
        "norms": {f: put(a) for f, a in pack.norms.items()},
        "text_has": {f: put(a) for f, a in pack.text_present.items()},
        "dv_int": {},
        "dv_float": {},
        "dv_ord": {},
        "dv_mv": {},
        "live": put(pack.live),
        "vec": {},
        "vec_has": {},
    }
    dev["dv_int_ord"] = {}
    for f, col in pack.docvalues.items():
        key = {"int": "dv_int", "float": "dv_float", "ord": "dv_ord"}[col.kind]
        vals = col.values if col.kind != "ord" else col.values.astype(np.int64)
        dev[key][f] = (put(vals), put(col.has_value))
        if col.uniq_ords is not None:
            dev["dv_int_ord"][f] = put(col.uniq_ords)
        if col.mv_pair_docs is not None:
            dev["dv_mv"][f] = (put(col.mv_pair_docs), put(col.mv_pair_ords))
    dev["vec_sq"] = {}
    dev["vec_ann"] = {}
    for f, vc in pack.vectors.items():
        dev["vec"][f] = put(vc.values)
        dev["vec_has"][f] = put(vc.has_value)
        dev["vec_sq"][f] = put((vc.values * vc.values).sum(axis=-1).astype(np.float32))
        if vc.ann is not None:
            from ..ann import ann_to_device

            dev["vec_ann"][f] = ann_to_device(vc.ann, vc.values, put)
    if pack.dense_tfn is not None:
        dev["dense_tfn"] = put(pack.dense_tfn)
    if pack.pos_keys is not None:
        dev["pos_keys"] = put(pack.pos_keys)
    if pack.impact_codes is not None:
        # impact-scored sparse tier (BM25S): quantized per-posting BM25
        # contributions — the gather+sum scoring path's only operand
        # besides post_docids
        dev["impact_codes"] = put(pack.impact_codes)
    return dev


@dataclass
class ShardResult:
    doc_ids: np.ndarray  # [<=k] int32 local docids
    scores: np.ndarray  # [<=k] float32
    total: int
    max_score: float | None
    aggregations: dict | None = None


def _copy_shard_result(res: "ShardResult") -> "ShardResult":
    """Defensive copy for cache store/serve: callers may mutate hit arrays
    or aggregation dicts, and the cached original must stay pristine."""
    import copy as _copy

    return ShardResult(
        res.doc_ids.copy(), res.scores.copy(), res.total, res.max_score,
        _copy.deepcopy(res.aggregations),
    )


def _shard_result_nbytes(res: "ShardResult") -> int:
    import json as _json

    n = int(res.doc_ids.nbytes + res.scores.nbytes) + 256
    if res.aggregations:
        try:
            n += len(_json.dumps(res.aggregations, default=str))
        except Exception:
            n += 4096
    return n


class ShardSearcher:
    def __init__(self, pack: ShardPack, device=None, mappings=None):
        self.pack = pack
        self.mappings = mappings
        self.dev = pack_to_device(pack, device)
        self.ctx = ExecContext(
            num_docs=pack.num_docs,
            avgdl={f: pack.avgdl(f) for f in pack.norms},
            has_norms=frozenset(pack.norms),
        )
        from ..index.pack import BM25_K1, BM25_B

        assert not pack.dense_dict or (self.ctx.k1, self.ctx.b) == (BM25_K1, BM25_B), (
            "dense-tier packs bake default k1/b; rebuild with dense disabled"
        )
        self._cache: dict = {}
        # shard request cache identity: a process-unique token (never
        # reused, unlike id()) + epochs that bump on any in-place mutation
        # of the device-visible pack / scoring stats (cache/request_cache)
        from ..cache import next_searcher_token

        self.cache_token = next_searcher_token()
        self._pack_epoch = 0
        self._stats_epoch = 0

    def cache_scope(self, shard: int = 0):
        """-> (token, epoch) pair keying this searcher's cache entries."""
        return ((self.cache_token, shard),
                (self._pack_epoch, self._stats_epoch))

    def bump_epoch(self, stats: bool = False):
        """Invalidate every cached result of this searcher (call after any
        in-place mutation of the pack or its scoring statistics)."""
        self._pack_epoch += 1
        if stats:
            self._stats_epoch += 1
        from ..cache import request_cache

        request_cache().invalidate_searcher(self.cache_token)

    # -- compilation -------------------------------------------------------

    def _compiled(self, node: QueryNode, struct_key: tuple, k: int, agg_nodes=None, agg_key=()):
        key = (struct_key, k, agg_key)
        fn = self._cache.get(key)
        from ..monitoring.device import note_executable_cache

        note_executable_cache("compiled_plan", fn is not None)
        if fn is None:
            ctx = self.ctx
            n = self.pack.num_docs

            def run(dev, params, agg_params):
                scores, match = node.device_eval(dev, params, ctx)
                ok = match[:n] & dev["live"]
                agg_out = {}
                if agg_nodes:
                    seg = jnp.where(ok, 0, 1).astype(jnp.int32)
                    dev_a = {**dev, "_query_scores": scores[:n]}
                    for name, anode in agg_nodes.items():
                        agg_out[name] = anode.device_eval_segmented(
                            dev_a, agg_params[name], seg, 1, ok, ctx
                        )
                return (*top_k_with_total(scores, match, dev["live"], k), agg_out)

            fn = jax.jit(run)
            self._cache[key] = fn
        return fn

    # -- entry points ------------------------------------------------------

    def batched(self):
        """Cached BatchTermSearcher over this shard's device pack — the
        `_msearch` fast path. Its dense tier rides the fused Pallas
        kernel (in-kernel split-bf16 matmul + per-tile top-t + canonical
        f32 rescore) whenever ES_TPU_FUSED / ES_TPU_FUSED_TOPK and the
        pack shape allow; per-query `search` keeps the compiled-plan
        path, whose final selection is ops/scoring.top_k_with_total."""
        bs = getattr(self, "_batched", None)
        if bs is None:
            from ..ops.batched import BatchTermSearcher

            bs = self._batched = BatchTermSearcher(self)
        return bs

    def msearch(self, fld: str, queries, k: int = 10, **kw):
        """Batched term-disjunction `_msearch` -> (scores, docids, totals,
        first_pass_exact) numpy (see BatchTermSearcher.msearch).

        Consults the shard request cache per QUERY before dispatching the
        fused pipeline: warm queries are assembled host-side, only the
        cold subset is planned and dispatched, and every cold query's
        result row is stored under (searcher token, epoch, canonical
        query key) — a repeated query stream never re-enters the device.
        """
        from ..cache import canonical_key, request_cache

        rc = request_cache()
        if not rc.enabled or not queries:
            return self.batched().msearch(fld, queries, k, **kw)
        tok, epoch = self.cache_scope()
        opts = sorted((str(a), v) for a, v in kw.items())
        qkeys = [
            canonical_key({"op": "msearch", "fld": fld, "k": int(k),
                           "opts": opts,
                           "q": [[t, float(b)] for t, b in q]})
            for q in queries
        ]
        rows: dict[int, tuple] = {}
        cold: list[int] = []
        for qi, ck in enumerate(qkeys):
            got = rc.get(tok, epoch, ck)
            if got is None:
                cold.append(qi)
            else:
                rows[qi] = got
        from ..telemetry import profile_event

        profile_event("cache", scope="msearch", shard=0,
                      hits=len(queries) - len(cold), misses=len(cold))
        if cold:
            _t0 = time.perf_counter()
            cv, ci, ct, cex = self.batched().msearch(
                fld, [queries[qi] for qi in cold], k, **kw)
            # amortize the measured wave wall over the cold rows — the
            # per-entry recompute cost the planner's admission floor sees
            _row_ms = (time.perf_counter() - _t0) * 1000 / len(cold)
            for j, qi in enumerate(cold):
                row = (cv[j].copy(), ci[j].copy(), int(ct[j]), bool(cex[j]))
                rows[qi] = row
                rc.put(tok, epoch, qkeys[qi], row,
                       row[0].nbytes + row[1].nbytes + 96,
                       recompute_ms=_row_ms)
        Q = len(queries)
        width = max(r[0].shape[0] for r in rows.values())
        scores = np.full((Q, width), -np.inf, np.float32)
        ids = np.zeros((Q, width), np.int64)
        totals = np.zeros((Q,), np.int64)
        exact = np.ones((Q,), bool)
        for qi, (rv, ri, rt, re_) in rows.items():
            scores[qi, : rv.shape[0]] = rv
            ids[qi, : ri.shape[0]] = ri
            totals[qi] = rt
            exact[qi] = re_
        return scores, ids, totals, exact

    def search(
        self,
        query: dict | QueryNode | None,
        size: int = 10,
        from_: int = 0,
        mappings=None,
        aggs: dict | None = None,
    ) -> ShardResult:
        """Compiled-plan per-query search, served from the shard request
        cache when the request is a plain DSL tree against the searcher's
        own mappings (cached results are byte-identical: execution is
        deterministic per (searcher, epoch, canonical request))."""
        from ..cache import canonical_key, request_cache

        rc = request_cache()
        ck = scope = None
        if rc.enabled and mappings is None and not isinstance(query, QueryNode):
            # analysis generation: query-time analyzers (synonym-set
            # reloads) change parsed queries without any index write
            ck = canonical_key({"op": "search", "query": query, "aggs": aggs,
                                "size": int(size), "from": int(from_),
                                "ag": getattr(self.mappings,
                                              "analysis_generation", 0)})
            scope = self.cache_scope()
            hit = rc.get(scope[0], scope[1], ck)
            if hit is not None:
                from ..telemetry import CACHE_HIT_SPAN, TRACER, profile_event

                profile_event("cache", scope="search", shard=0, hits=1,
                              misses=0)
                with TRACER.span(CACHE_HIT_SPAN):
                    return _copy_shard_result(hit)
            from ..telemetry import profile_event

            profile_event("cache", scope="search", shard=0, hits=0, misses=1)
        from ..telemetry import metrics as _metrics

        _t0 = time.perf_counter()
        res = self._search_uncached(query, size, from_, mappings, aggs)
        _elapsed_ms = (time.perf_counter() - _t0) * 1000
        _metrics.histogram_record("es.shard.search.ms", _elapsed_ms)
        if ck is not None:
            rc.put(scope[0], scope[1], ck, _copy_shard_result(res),
                   _shard_result_nbytes(res), recompute_ms=_elapsed_ms)
        return res

    def _plan_request(self, query, size, from_, mappings, aggs):
        """Parse/prepare/compile one request and DISPATCH its program (no
        fetch). -> ("result", ShardResult) for degenerate requests or
        ("dispatch", state); `_finalize_request` turns the fetched outputs
        into a ShardResult. Shared by the solo path and `search_many`, so
        coalesced waves execute byte-identical per-request programs."""
        m = mappings if mappings is not None else self.mappings
        if m is None and (aggs or not isinstance(query, QueryNode)):
            from ..utils.errors import QueryParsingError

            raise QueryParsingError("no mappings available to parse the request")
        node = query if isinstance(query, QueryNode) else parse_query(query, m)
        agg_nodes = None
        if aggs:
            from ..aggs import parse_aggs

            agg_nodes = parse_aggs(aggs, m)
        if self.pack.num_docs == 0:
            return ("result", ShardResult(
                np.array([], np.int32), np.array([], np.float32), 0, None,
                {} if aggs else None,
            ))
        params, struct_key = node.prepare(self.pack)
        agg_params, agg_key = {}, ()
        if agg_nodes:
            parts = {n: a.prepare(self.pack, m) for n, a in agg_nodes.items()}
            agg_params = {n: p for n, (p, _) in parts.items()}
            agg_key = tuple((n, k) for n, (_, k) in sorted(parts.items()))
        k = min(max(size + from_, 1), self.pack.num_docs)
        fn = self._compiled(node, struct_key, k, agg_nodes, agg_key)
        # PR 12: cross-check the analytic cost model against the lowered
        # program's own cost analysis (bounded: once per plan shape)
        from ..monitoring.xla_introspect import check_dispatch

        check_dispatch("compiled_plan", fn,
                       (self.dev, params, agg_params),
                       fields={"queries": 1, "k": k,
                               "num_docs": self.pack.num_docs})
        return ("dispatch", {
            "node": node, "struct_key": struct_key, "k": k,
            "agg_nodes": agg_nodes, "agg_key": agg_key, "params": params,
            "agg_params": agg_params, "size": size, "from_": from_,
            "outs": fn(self.dev, params, agg_params),
        })

    def _finalize_request(self, state, host) -> ShardResult:
        """host = the fetched (top_scores, top_ids, total, agg_out) of a
        dispatched request; runs the (rare) two-pass agg second program
        synchronously and builds the ShardResult."""
        top_scores, top_ids, total, agg_out = host
        node, struct_key, k = state["node"], state["struct_key"], state["k"]
        agg_nodes, agg_key = state["agg_nodes"], state["agg_key"]
        agg_params = state["agg_params"]
        params = state["params"]
        size, from_ = state["size"], state["from_"]
        aggregations = None
        if agg_nodes:
            from ..aggs import two_pass_plan

            tp = two_pass_plan(agg_nodes)
            if tp:
                # pass 2: exact sub-aggs over the candidate slots only
                for name, a in tp.items():
                    agg_params[name] = {
                        **agg_params[name],
                        "cand": a.select_candidates(agg_out[name]),
                    }
                fn2 = self._compiled(
                    node, struct_key, k, agg_nodes,
                    (agg_key, "tp2",
                     tuple(sorted((n, a._C) for n, a in tp.items()))))
                _s, _i, _t, agg_out2 = jax.device_get(
                    fn2(self.dev, params, agg_params))
                for name in tp:
                    agg_out[name] = {**agg_out[name], **agg_out2[name]}
            aggregations = {
                name: anode.finalize(agg_out[name], 1)[0]
                for name, anode in agg_nodes.items()
            }
        valid = np.isfinite(top_scores)
        max_score = float(top_scores[0]) if valid.any() else None
        end = max(size + from_, 0)
        ids = top_ids[valid][from_:end]
        scs = top_scores[valid][from_:end]
        return ShardResult(
            ids.astype(np.int32), scs.astype(np.float32), int(total), max_score, aggregations
        )

    def _search_uncached(
        self,
        query: dict | QueryNode | None,
        size: int = 10,
        from_: int = 0,
        mappings=None,
        aggs: dict | None = None,
    ) -> ShardResult:
        kind, state = self._plan_request(query, size, from_, mappings, aggs)
        if kind == "result":
            return state
        from ..telemetry import time_kernel

        k = state["k"]
        with time_kernel("compiled_plan", shard=0, queries=1,
                         tier="xla_topk",
                         num_docs=self.pack.num_docs, k=k):
            host = jax.device_get(state["outs"])
        return self._finalize_request(state, host)

    def search_many(self, requests: list[dict]) -> list[ShardResult]:
        """Wave-shaped entry point: execute several `search()`-shaped
        request dicts (query, size, from_, mappings, aggs) with every
        compiled program dispatched before ANY result is fetched — one
        device round trip per wave instead of one per request. Cache
        lookups/stores, planning, and per-request programs are the same
        code as solo `search()`, so wave results are byte-identical to
        solo execution."""
        from ..cache import canonical_key, request_cache

        rc = request_cache()
        n = len(requests)
        results: list = [None] * n
        states: list = [None] * n
        slots: list = [None] * n
        for i, r in enumerate(requests):
            query = r.get("query")
            size = r.get("size", 10)
            from_ = r.get("from_", 0)
            mappings = r.get("mappings")
            aggs = r.get("aggs")
            ck = scope = None
            if (rc.enabled and mappings is None
                    and not isinstance(query, QueryNode)):
                ck = canonical_key(
                    {"op": "search", "query": query, "aggs": aggs,
                     "size": int(size), "from": int(from_),
                     "ag": getattr(self.mappings, "analysis_generation", 0)})
                scope = self.cache_scope()
                hit = rc.get(scope[0], scope[1], ck)
                if hit is not None:
                    results[i] = _copy_shard_result(hit)
                    continue
            kind, st = self._plan_request(query, size, from_, mappings, aggs)
            if kind == "result":
                results[i] = st
            else:
                states[i] = st
                slots[i] = (ck, scope)
        live = [s for s in states if s is not None]
        if live:
            from ..telemetry import host_transition, time_kernel

            # the wave contract (PR 11): every program dispatched above,
            # ONE blocking fetch here — counted like the sharded wave
            host_transition("dispatch")
            k0 = max(s["k"] for s in live)
            with time_kernel("compiled_plan", shard=0, queries=len(live),
                             tier="xla_topk",
                             num_docs=self.pack.num_docs, k=k0):
                host = jax.device_get([s["outs"] for s in live])
            host_transition("fetch")
            host = iter(host)
            for i, s in enumerate(states):
                if s is None:
                    continue
                res = self._finalize_request(s, next(host))
                results[i] = res
                if slots[i] is not None and slots[i][0] is not None:
                    ck, scope = slots[i]
                    rc.put(scope[0], scope[1], ck, _copy_shard_result(res),
                           _shard_result_nbytes(res))
        return results

    def count(self, query: dict | QueryNode | None, mappings=None) -> int:
        return self.search(query, size=1, mappings=mappings).total

    # -- field-sorted search ----------------------------------------------

    def _compiled_sorted(self, node, struct_key, k, plan, has_after, agg_nodes, agg_key):
        key = ("sorted", struct_key, k, plan.struct_key(), has_after, agg_key)
        fn = self._cache.get(key)
        if fn is None:
            ctx = self.ctx
            n = self.pack.num_docs

            def run(dev, params, after, agg_params):
                scores, match = node.device_eval(dev, params, ctx)
                ok = match[:n] & dev["live"]
                total = jnp.sum(ok, dtype=jnp.int32)
                agg_out = {}
                if agg_nodes:
                    seg = jnp.where(ok, 0, 1).astype(jnp.int32)
                    dev_a = {**dev, "_query_scores": scores[:n]}
                    for name, anode in agg_nodes.items():
                        agg_out[name] = anode.device_eval_segmented(
                            dev_a, agg_params[name], seg, 1, ok, ctx
                        )
                keys = plan.device_keys(dev, scores, n)
                sel = ok
                if has_after:
                    # lexicographic "strictly after the cursor"
                    gt = jnp.zeros(n, bool)
                    eq = jnp.ones(n, bool)
                    for kk, aa in zip(keys, after):
                        gt = gt | (eq & (kk > aa))
                        eq = eq & (kk == aa)
                    sel = sel & gt
                invalid = (~sel).astype(jnp.int32)
                docs = jnp.arange(n, dtype=jnp.int32)
                sorted_ops = jax.lax.sort(
                    (invalid, *keys, docs), num_keys=1 + len(keys)
                )
                inv_s = sorted_ops[0][:k]
                keys_s = tuple(o[:k] for o in sorted_ops[1:-1])
                docs_s = sorted_ops[-1][:k]
                return inv_s, keys_s, docs_s, total, agg_out

            fn = jax.jit(run)
            self._cache[key] = fn
        return fn

    def search_sorted(
        self,
        query,
        sort_fields,
        size: int = 10,
        from_: int = 0,
        search_after=None,
        mappings=None,
        aggs: dict | None = None,
    ):
        """-> (hits: [(docid, sort_values)], total, aggregations)."""
        from .sort import SortPlan

        m = mappings if mappings is not None else self.mappings
        node = query if isinstance(query, QueryNode) else parse_query(query, m)
        agg_nodes = None
        if aggs:
            from ..aggs import parse_aggs

            agg_nodes = parse_aggs(aggs, m)
        if self.pack.num_docs == 0:
            return [], 0, ({} if aggs else None)
        plan = SortPlan(sort_fields, self.pack, m)
        params, struct_key = node.prepare(self.pack)
        agg_params, agg_key = {}, ()
        if agg_nodes:
            parts = {nm: a.prepare(self.pack, m) for nm, a in agg_nodes.items()}
            from ..aggs import two_pass_plan

            tp = two_pass_plan(agg_nodes)
            if tp:
                # field-sorted execution can't orchestrate two passes: fall
                # back to single-pass (the one-pass budgets apply as before)
                for a in tp.values():
                    a.force_single_pass = True
                parts = {nm: a.prepare(self.pack, m)
                         for nm, a in agg_nodes.items()}
            agg_params = {nm: p for nm, (p, _) in parts.items()}
            agg_key = tuple((nm, kk) for nm, (_, kk) in sorted(parts.items()))
        k = min(max(size + from_, 1), self.pack.num_docs)
        after = ()
        if search_after is not None:
            after = plan.after_keys(search_after, self.pack)
        fn = self._compiled_sorted(
            node, struct_key, k, plan, search_after is not None, agg_nodes, agg_key
        )
        inv, keys_s, docs, total, agg_out = jax.device_get(
            fn(self.dev, params, after, agg_params)
        )
        aggregations = None
        if agg_nodes:
            aggregations = {
                name: anode.finalize(agg_out[name], 1)[0]
                for name, anode in agg_nodes.items()
            }
        nvalid = int((inv == 0).sum())
        take = list(range(min(nvalid, k)))[from_ : size + from_]
        values = plan.hit_values(keys_s, take)
        hits = [(int(docs[i]), v) for i, v in zip(take, values)]
        return hits, int(total), aggregations
