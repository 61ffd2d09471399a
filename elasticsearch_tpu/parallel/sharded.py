"""Sharded query execution over a TPU mesh.

The scatter/gather that the reference does with async per-shard RPCs
(reference behavior: AbstractSearchAsyncAction.java:301 fan-out,
SearchPhaseController.java:232 `TopDocs.merge`, coordinator agg reduce) is
here a single SPMD program: `shard_map` over a `Mesh(("shards",))` runs the
identical per-shard scoring body on every device, and the global top-k merge
is a `lax.top_k` over the gathered [S, k] partials — XLA lowers the gather to
ICI collectives. Tie-break order (score desc, shard asc, local docid asc)
falls out of flat-index ordering, matching Lucene's merge.

On a single device (e.g. one TPU chip benching an 8-shard index) the same
body runs under `vmap` over the shard axis instead — same math, no mesh.
"""

from __future__ import annotations

from dataclasses import dataclass

import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.scoring import top_k_with_total
from ..query.dsl import parse_query
from ..utils.jax_env import shard_map
from ..utils.errors import IllegalArgumentError
from ..query.nodes import ExecContext, QueryNode, match_rows, plan_key
from .param_pack import (pack, pack_outputs, packed_counts, unpack,
                         unpack_host)
from .stacked import StackedPack

log = logging.getLogger(__name__)

import functools


def _impact_codes_device(tfs, dls, k_base, k_slope, scale_inv, *,
                         qmax, dtype):
    """Device twin of index/pack.impact_codes_host (asserted equal by
    tests/test_impact.py): derive the quantized impact code blocks from
    the resident postings — ONE elementwise pass at refresh, so dfs-stat
    drift (stats_override under tiered refresh) re-norms the impact tier
    without a host rebuild or re-transfer (the refresh_dense_tfn
    discipline applied to the sparse tier). PR 15: the kernel itself
    moved to index/device_build (shared with the build-time device
    quantization path)."""
    from ..index.device_build import impact_codes_device

    return impact_codes_device(tfs, dls, k_base, k_slope, scale_inv,
                               qmax=qmax, dtype=dtype)


def make_mesh(num_shards: int) -> Mesh | None:
    """Mesh over the first num_shards devices; None -> single-device vmap.
    Delegates to parallel/spmd.make_mesh (which adds the pjit-mode
    replica axis and the multi-process stretch wiring)."""
    from .spmd import make_mesh as _mk

    return _mk(num_shards)


def _stack_shard_params(per_shard: list):
    """Stack per-shard param pytrees; ragged 1-D int32 leaves (postings block
    rows) are padded with the reserved row 0 to the max bucket size."""
    import jax.tree_util as jtu

    leaves_list = [jtu.tree_leaves(p) for p in per_shard]
    treedef = jtu.tree_structure(per_shard[0])
    stacked = []
    for leaf_group in zip(*leaves_list):
        shapes = {np.shape(x) for x in leaf_group}
        if len(shapes) == 1:
            stacked.append(np.stack([np.asarray(x) for x in leaf_group]))
        else:
            arrs = [np.asarray(x) for x in leaf_group]
            if any(a.ndim != 1 for a in arrs):
                raise ValueError("cannot stack ragged non-1D shard params")
            width = max(a.shape[0] for a in arrs)
            out = np.zeros((len(arrs), width), arrs[0].dtype)
            for i, a in enumerate(arrs):
                out[i, : a.shape[0]] = a
            stacked.append(out)
    return jtu.tree_unflatten(treedef, stacked)


def stacked_to_device(sp: StackedPack, mesh: Mesh | None) -> dict:
    """[S, ...] arrays -> device as a SHARDED PYTREE.

    The host tree is built first (numpy leaves), then every leaf ships
    via `jax.device_put` with the NamedSharding produced by the
    partition-rule table (spmd.match_partition_rules over leaf names) —
    the GSPMD discipline SNIPPETS [1][2] apply to params pytrees. A pack
    component whose name matches no rule is a hard error at upload, not
    a silently replicated array. mesh=None keeps plain `jnp.asarray`.

    PR 13: the upload is a profiled build stage (`build.device_put`, the
    host→device transfer the item-2 device builders will mostly delete)
    and counts a kind="refresh" host transition, so background merges
    get the same transition budget the serving waves hold (≤1+1/wave)."""
    from ..monitoring.refresh_profile import build_stage
    from ..telemetry import host_transition
    from ..utils.jax_env import ensure_x64

    ensure_x64()
    host_transition("refresh")
    # the host-tree assembly (numpy staging copies) is upload prep —
    # charged to the device_put stage, not the profile residual
    with build_stage("build.device_put", nbytes=sp.nbytes()):
        host = _stacked_host_tree(sp)
        if mesh is None:
            import jax.tree_util as jtu

            return jtu.tree_map(jnp.asarray, host)
        from .spmd import shard_put

        return shard_put(host, mesh)


def _stacked_host_tree(sp: StackedPack) -> dict:
    """The pack pytree with host (numpy) leaves — the input of the
    partition-rule matching; leaf PATHS here are the rule vocabulary."""
    put = np.asarray
    dev = {
        "post_docids": put(sp.post_docids),
        "post_tfs": put(sp.post_tfs),
        "post_dls": put(sp.post_dls),
        "norms": {f: put(a) for f, a in sp.norms.items()},
        "text_has": {f: put(a) for f, a in sp.text_present.items()},
        "dv_int": {},
        "dv_float": {},
        "dv_ord": {},
        "dv_mv": {},
        "dv_int_ord": {},
        "live": put(sp.live),
        "vec": {},
        "vec_has": {},
    }
    for f, col in sp.stacked_docvalues.items():
        key = {"int": "dv_int", "float": "dv_float", "ord": "dv_ord"}[col.kind]
        vals = col.values if col.kind != "ord" else col.values.astype(np.int64)
        dev[key][f] = (put(vals), put(col.has_value))
        if col.uniq_ords is not None:
            dev["dv_int_ord"][f] = put(col.uniq_ords)
        if col.mv_pair_docs is not None:
            dev["dv_mv"][f] = (put(col.mv_pair_docs), put(col.mv_pair_ords))
    dev["vec_sq"] = {}
    dev["vec_ann"] = {}
    for f, vc in sp.vectors.items():
        dev["vec"][f] = put(vc.values)
        dev["vec_has"][f] = put(vc.has_value)
        dev["vec_sq"][f] = put((vc.values * vc.values).sum(axis=-1).astype(np.float32))
        if vc.ann is not None:
            from ..ann import ann_to_device

            dev["vec_ann"][f] = ann_to_device(vc.ann, vc.values, put)
    if getattr(sp, "dense_tf", None) is not None:
        dev["dense_tf"] = put(sp.dense_tf)
    if sp.pos_keys is not None:
        dev["pos_keys"] = put(sp.pos_keys)
    return dev


@dataclass
class StackedResult:
    doc_shards: np.ndarray  # [<=k] int32 shard of each hit
    doc_ids: np.ndarray  # [<=k] int32 local docid within the shard
    scores: np.ndarray  # [<=k] float32
    total: int
    max_score: float | None
    aggregations: dict | None = None


def _copy_stacked_result(res: StackedResult) -> StackedResult:
    """Defensive copy for cache store/serve: the engine mutates results in
    place (rescore reorders, pipeline aggs rewrite the agg tree), so the
    cached original must never be handed out by reference."""
    import copy as _copy

    return StackedResult(
        res.doc_shards.copy(), res.doc_ids.copy(), res.scores.copy(),
        res.total, res.max_score, _copy.deepcopy(res.aggregations),
    )


def _stacked_result_nbytes(res: StackedResult) -> int:
    n = int(res.doc_shards.nbytes + res.doc_ids.nbytes
            + res.scores.nbytes) + 256
    if res.aggregations:
        try:
            n += len(json.dumps(res.aggregations, default=str))
        except Exception:  # noqa: BLE001 - estimate only
            n += 4096
    return n


def _two_pass(state: dict) -> bool:
    """Whether a launched request's terms aggregations need a second pass
    (`_agg_pass2_dispatch`)."""
    if not state["agg_nodes"]:
        return False
    from ..aggs import two_pass_plan

    return bool(two_pass_plan(state["agg_nodes"]))


def _hand_off() -> None:
    """Let the completer finish the solo search whose programs were just
    launched, where one may and another search waits for the engine thread
    (`common/engine_thread.hand_off`); the hand-over is its span."""
    import time as _time

    from ..common import engine_thread
    from ..telemetry import HANDED_OFF, SOLO_HANDOFF, TRACER, metrics

    t0 = _time.perf_counter_ns()
    if engine_thread.hand_off():
        TRACER.record(SOLO_HANDOFF, t0, _time.perf_counter_ns())
        metrics.counter_inc(HANDED_OFF)


class StackedSearcher:
    """Multi-shard searcher: one mesh-resident stacked pack + compiled plans.

    Scores with global term statistics — the reference's
    dfs_query_then_fetch (TransportSearchAction DFS phase /
    search/dfs/DfsPhase.java). The default per-shard-idf query_then_fetch
    mode is intentionally not reproduced: its cross-shard score skew is an
    artifact of distributed nodes, and global stats are free here."""

    def __init__(self, stacked: StackedPack, mesh: Mesh | None = None):
        from .spmd import spmd_mode

        self.sp = stacked
        self.mesh = mesh
        # execution model, resolved at construction (ES_TPU_SPMD):
        #   vmap     — no mesh: plain vmap over the stacked axis
        #   pjit     — GSPMD: vmapped bodies over the sharded pack pytree,
        #              with_sharding_constraint on hot intermediates, the
        #              global merge on-device (ICI all-gather + lax.top_k)
        #   shardmap — legacy per-shard shard_map bodies + host merge
        self._exec = ("vmap" if mesh is None else spmd_mode())
        if mesh is not None and "replicas" in mesh.axis_names \
                and self._exec == "shardmap":
            # the shard_map specs name only "shards"; a replica mesh is a
            # pjit-mode construct
            self._exec = "pjit"
        self.dev = stacked_to_device(stacked, mesh)
        self.ctx = ExecContext(
            num_docs=stacked.n_max,
            avgdl={f: self._avgdl(f) for f in stacked.norms},
            has_norms=frozenset(stacked.norms),
            sharded=True,
        )
        from ..index.pack import BM25_K1, BM25_B

        assert not stacked.dense_dict or (self.ctx.k1, self.ctx.b) == (BM25_K1, BM25_B), (
            "dense-tier packs bake default k1/b; rebuild with dense disabled"
        )
        self._cache: dict = {}
        # a searcher that never compiles a plan shape has met none: the
        # counters read 0 from the start, not nothing (a node whose every
        # search rides a wave)
        from ..telemetry import SOLO_ROWS, metrics

        metrics.counter_inc("es.jit.cache.search_solo.misses", 0)
        metrics.counters_add([(SOLO_ROWS, (0, 0))])
        self._dense_tfn_fn = None
        # shard request cache identity: per-shard epochs so one shard's
        # in-place mutation invalidates only its own entries (plus the
        # whole-searcher merged-result entries), and a dfs-stats epoch for
        # scoring-statistics drift under tiered refresh
        from ..cache import next_searcher_token

        self.cache_token = next_searcher_token()
        self._shard_epochs = [0] * stacked.S
        self._stats_epoch = 0
        self.refresh_dense_tfn()
        self.refresh_impacts()

    # -- shard request cache ----------------------------------------------

    def shard_cache_scope(self, s: int):
        """-> (token, epoch) keying shard `s`'s per-shard cache entries."""
        return ((self.cache_token, s),
                (self._shard_epochs[s], self._stats_epoch))

    def cache_scope(self):
        """-> (token, epoch) for whole-searcher (merged) results; depends
        on every shard's epoch, so any shard bump invalidates it."""
        return ((self.cache_token, -1),
                (tuple(self._shard_epochs), self._stats_epoch))

    def bump_epoch(self, shard: int | None = None, stats: bool = False):
        """Invalidate cached results after an in-place mutation: all
        shards (refresh/delete/merge) or one shard; stats=True also marks
        a dfs-statistics change (stats_override drift)."""
        if shard is None:
            self._shard_epochs = [e + 1 for e in self._shard_epochs]
        else:
            self._shard_epochs[shard] += 1
        if stats:
            self._stats_epoch += 1
        from ..cache import request_cache

        request_cache().invalidate_searcher(self.cache_token, shard=shard)

    def refresh_dense_tfn(self):
        """(Re)compute the scored dense tier dev["dense_tfn"] from the raw
        tf rows + norms + CURRENT per-field avgdl — one elementwise device
        pass, so stat drift (tiered refresh) never rebuilds the tier on the
        host or re-transfers it."""
        if "dense_tf" not in self.dev:
            return
        import itertools

        if self._dense_tfn_fn is None:
            slices = []
            v0 = 0
            for fld, group in itertools.groupby(self.sp.dense_fields):
                c = sum(1 for _ in group)
                slices.append((fld, v0, v0 + c, fld in self.sp.norms))
                v0 += c
            self._dense_slices = slices
            k1, b = self.ctx.k1, self.ctx.b

            def dense_tfn(tf, norms, avgdls):
                parts = []
                for i, (fld, a, c, hn) in enumerate(slices):
                    tfa = tf[:, a:c, :]
                    if hn:
                        K = k1 * (1.0 - b + b * norms[fld] / avgdls[i])
                        parts.append(tfa / (tfa + K[:, None, :]))
                    else:
                        parts.append(tfa / (tfa + k1))
                return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

            self._dense_tfn_fn = jax.jit(dense_tfn)
        avgdls = jnp.asarray(
            [max(self._avgdl(fld), 1e-9) for fld, _a, _c, _hn in self._dense_slices],
            jnp.float32,
        )
        self.dev["dense_tfn"] = self._dense_tfn_fn(
            self.dev["dense_tf"], self.dev["norms"], avgdls)

    def _avgdl(self, fld):
        st = self.sp.eff_field_stats.get(fld)
        if not st or st["doc_count"] == 0:
            return 1.0
        return st["sum_dl"] / st["doc_count"]

    def refresh_impacts(self):
        """(Re)derive the impact tier's quantized code blocks on device
        from the CURRENT effective field stats (the length-norm K bakes
        avgdl; idf stays query-time host math, so dfs-df drift needs no
        rebuild at all). Called at construction and after every
        stats_override change (engine tiered refresh); until then the
        stale basis makes impact_serving() False and planning degrades to
        the exact raw-postings path."""
        sp = self.sp
        if sp.impact_meta is None:
            return
        meta = sp.impact_meta
        if (self.ctx.k1, self.ctx.b) != (meta["k1"], meta["b"]):
            # a custom-similarity context cannot serve quantized defaults
            self.dev.pop("impact_codes", None)
            return
        from ..monitoring.refresh_profile import build_stage

        fields = sp.impact_fields
        fld_avgdl = np.array(
            [max(self._avgdl(f), 1e-9) for f in fields] or [1.0], np.float64)
        fld_hn = np.array([f in sp.norms for f in fields] or [False])
        rf = sp.impact_row_field  # [S, nb_max]
        safe = np.maximum(rf, 0)
        hn = fld_hn[safe] & (rf >= 0)
        k1, b = meta["k1"], meta["b"]
        k_base = np.where(hn, k1 * (1.0 - b), k1).astype(np.float32)
        k_slope = np.where(hn, k1 * b / fld_avgdl[safe], 0.0).astype(
            np.float32)
        # the device twin of the pack.py host derivation — same kernel
        # name, basis="device", so the write-path profile shows the
        # host-vs-device split of impact quantization directly
        with build_stage("build.impact_quantize",
                         rows=int(self.sp.S) * int(self.sp.nb_max),
                         code_bytes=2 if meta["dtype"] == "uint16" else 1,
                         basis="device"):
            self.dev["impact_codes"] = _impact_codes_device(
                self.dev["post_tfs"], self.dev["post_dls"],
                jnp.asarray(k_base), jnp.asarray(k_slope),
                jnp.asarray(sp.impact_row_scale_inv),
                qmax=meta["qmax"], dtype=meta["dtype"])
        sp._impact_basis = sp.stats_override

    def update_live(self):
        """Re-ship the live-docs bitmap after host-side flips (tiered
        refresh marks superseded/deleted base docs dead in place). The
        flip changes every shard's visible result set, so the request
        cache epoch bumps here — stale entries become unreachable AND are
        dropped."""
        from ..monitoring.refresh_profile import build_stage
        from ..telemetry import host_transition

        host_transition("refresh")
        with build_stage("build.device_put", nbytes=self.sp.live.nbytes):
            if self.mesh is not None:
                self.dev["live"] = jax.device_put(
                    self.sp.live, NamedSharding(self.mesh, P("shards")))
            else:
                self.dev["live"] = jnp.asarray(self.sp.live)
        self.bump_epoch()

    def _compiled(self, node, key, k, agg_nodes, agg_key, layout):
        """The program of one plan shape. It takes the request's parameters
        packed (`param_pack.pack`) and unpacks them by `layout`, which is
        therefore part of its identity. It hands its result tree back packed
        too (`param_pack.pack_outputs`): `_fetched` rebuilds the tree."""
        from ..monitoring.device import note_executable_cache

        cache_key = (key, k, agg_key, self._exec, layout)
        fn = self._cache.get(cache_key)
        note_executable_cache("search_solo", fn is not None)
        if fn is not None:
            return fn
        ctx = self.ctx
        n = self.sp.n_max
        S = self.sp.S
        # a shard can contribute at most n_max hits; the global k may exceed it
        k_local = min(k, n)
        k_global = min(k, S * k_local)

        def shard_body(dev1, par1, agg_par1):
            # the body runs inside an embedded shard_map manual region; the
            # selection is the plain XLA the single-device path runs
            # the scopes name phases, not implementations: they are HLO
            # metadata that a capture's device operations carry
            with jax.named_scope("score"):
                scores, match = node.device_eval(dev1, par1, ctx)
            with jax.named_scope("topk"):
                ts, ti, tot = top_k_with_total(scores, match, dev1["live"],
                                               k_local)
            agg_out = {}
            if agg_nodes:
                with jax.named_scope("aggs"):
                    ok = match[:n] & dev1["live"]
                    seg = jnp.where(ok, 0, 1).astype(jnp.int32)
                    dev_a = {**dev1, "_query_scores": scores[:n]}
                    for name, anode in agg_nodes.items():
                        agg_out[name] = anode.device_eval_segmented(
                            dev_a, agg_par1[name], seg, 1, ok, ctx
                        )
            return ts, ti, tot, agg_out

        from .spmd import constrain_shards, manual_shard_region

        region = manual_shard_region(
            shard_body, self.mesh,
            in_specs=(P("shards"), P("shards"), P("shards")))

        def inner(dev, params, agg_params):
            # the constraint pins the [S, ...] outputs shard-local until
            # the merge below forces the all-gather
            return constrain_shards(region(dev, params, agg_params),
                                    self.mesh)

        def search_solo(dev, buffers):
            params, agg_params = unpack(buffers, layout)
            ts, ti, tot, agg_out = inner(dev, params, agg_params)
            # global merge: flat index order = (score desc, shard asc,
            # local rank asc) — Lucene TopDocs.merge order. In pjit mode
            # the replication constraint IS the ICI all-gather of the
            # per-shard (score, doc) rows; the merged result is
            # replicated, so the host fetch pulls k rows, not S*k.
            from .spmd import constrain

            with jax.named_scope("topk"):
                flat = ts.reshape(-1)
                flat_i = ti.reshape(-1)
                if self._exec == "pjit":
                    flat = constrain(flat, self.mesh, P())
                    flat_i = constrain(flat_i, self.mesh, P())
                g_scores, g_idx = jax.lax.top_k(flat, k_global)
                g_shard = (g_idx // k_local).astype(jnp.int32)
                g_doc = flat_i[g_idx]
            # one device array a dtype class for the fetch to wait for,
            # replicated on a mesh so that the host pulls from one device.
            # The shards' counts are summed in their own width (x64 would
            # widen the sum, and S * n_max documents are far below 2**31),
            # so a plain search's whole result is one buffer of words
            outs, fn.out_layout = pack_outputs(
                (g_scores, g_shard, g_doc, tot.sum(dtype=jnp.int32), agg_out))
            return tuple(constrain(b, self.mesh, P()) for b in outs)

        # named for what it is: one compiled program per plan shape, all of
        # one family in a capture's `XLA Modules` line
        fn = jax.jit(search_solo)
        # host arrays a call of it is handed, and the leaves packed in them
        fn.packed = packed_counts(layout)
        # how its outputs are packed: known once it has been traced
        fn.out_layout = None
        self._cache[cache_key] = fn
        return fn

    def ensure_runtime_field(self, name: str, rtype: str, script) -> None:
        """Materialize a runtime field as a docvalues column (reference
        behavior: search-request runtime_mappings, mapper/RuntimeField.java —
        script-computed per query; here computed once per unique script and
        cached on the searcher, then visible to queries/aggs/sort like any
        mapped column).

        The script is the expression language (script/expression.py); ES
        `emit(expr)` sources are accepted by unwrapping the emit call."""
        from ..index.pack import DocValuesColumn
        from ..script.expression import compile_script

        if not hasattr(self, "_runtime_fields"):
            self._runtime_fields = {}
            self._runtime_cache = {}       # (name, rtype, src) -> artifacts
            self._runtime_plan_key = {}    # name -> key compiled plans baked
        src = script.get("source") if isinstance(script, dict) else script
        params = (script.get("params") if isinstance(script, dict) else None) or {}
        # params are baked into the compiled expression as constants, so they
        # are part of the field's identity
        cache_key = (name, rtype, src, json.dumps(params, sort_keys=True))
        if self._runtime_fields.get(name) == cache_key:
            return
        if name in self.sp.global_docvalues and name not in self._runtime_fields:
            raise IllegalArgumentError(
                f"runtime field [{name}] shadows a mapped field"
            )
        if rtype not in ("long", "double", "date", "boolean"):
            raise IllegalArgumentError(
                f"runtime field type [{rtype}] is not supported (numeric only)"
            )
        # compiled plans may have baked this field's vocab size / shapes — if
        # the definition changed since they were built, drop all plans
        # (redefinition is rare; a full flush is exact where name-matching
        # heuristics over/under-flush)
        if self._runtime_plan_key.get(name, cache_key) != cache_key:
            self._cache.clear()
        self._runtime_plan_key[name] = cache_key
        cached = self._runtime_cache.get(cache_key)
        if cached is not None:
            self._install_runtime_field(name, cache_key, cached)
            return
        s = src.strip()
        if s.startswith("emit(") and s.endswith(")"):
            s = s[5:-1]
        compiled = compile_script({"source": s, "params": params})
        S = self.sp.S
        n_max = self.sp.n_max
        dtype = np.int64 if rtype in ("long", "date", "boolean") else np.float32
        vals = np.zeros((S, n_max), dtype)
        has = np.zeros((S, n_max), bool)
        for i, p in enumerate(self.sp.shards):
            n = p.num_docs
            if n == 0:
                continue
            env = {}
            h_all = np.ones(n, bool)
            for f in compiled.fields:
                col = p.docvalues.get(f)
                if col is None or col.kind == "ord":
                    env[f] = np.zeros(n, np.float32)
                    h_all &= False
                else:
                    env[f] = np.where(col.has_value, col.values, 0).astype(np.float32)
                    h_all &= col.has_value
            out = np.asarray(compiled.evaluate(env))
            out = np.broadcast_to(out, (n,))
            vals[i, :n] = out.astype(dtype)
            has[i, :n] = h_all
        kind = "int" if dtype == np.int64 else "float"
        g = DocValuesColumn(kind, vals, has)
        present = vals[has]
        if present.size:
            g.vmin = present.min().item()
            g.vmax = present.max().item()
            if kind == "int":
                uniq = np.unique(present)
                g.uniq_values = uniq
                ords = np.full((S, n_max), -1, np.int32)
                ords[has] = np.searchsorted(uniq, vals[has]).astype(np.int32)
                g.uniq_ords = ords
        # per-shard planning view (prepare() reads pack.docvalues)
        pcs = []
        for i, p in enumerate(self.sp.shards):
            pc = DocValuesColumn(kind, vals[i, : p.num_docs], has[i, : p.num_docs])
            pc.vmin, pc.vmax = g.vmin, g.vmax
            if g.uniq_values is not None:
                pc.uniq_values = g.uniq_values
                pc.uniq_ords = g.uniq_ords[i, : p.num_docs]
            pcs.append(pc)
        put = (lambda x: jax.device_put(
            x, NamedSharding(self.mesh, P("shards", *([None] * (np.ndim(x) - 1))))
        )) if self.mesh is not None else jnp.asarray
        key = {"int": "dv_int", "float": "dv_float"}[kind]
        dev_entries = {key: (put(vals), put(has))}
        if g.uniq_ords is not None:
            dev_entries["dv_int_ord"] = put(g.uniq_ords)
        artifacts = {"g": g, "pcs": pcs, "dev": dev_entries}
        if len(self._runtime_cache) >= 16:  # bound memory for one-off scripts
            self._runtime_cache.pop(next(iter(self._runtime_cache)))
        self._runtime_cache[cache_key] = artifacts
        self._install_runtime_field(name, cache_key, artifacts)

    def _install_runtime_field(self, name, cache_key, artifacts) -> None:
        self.sp.stacked_docvalues[name] = artifacts["g"]
        self.sp.global_docvalues[name] = artifacts["g"]
        for p, pc in zip(self.sp.shards, artifacts["pcs"]):
            p.docvalues[name] = pc
        for key, val in artifacts["dev"].items():
            self.dev[key][name] = val
        self._runtime_fields[name] = cache_key

    def remove_runtime_fields(self, names) -> None:
        """Uninstall request-scoped runtime fields after the request
        (reference: runtime_mappings are per-search-request; they must not
        leak into later requests on the same index). Materialized columns
        stay in _runtime_cache so a repeat of the same request reinstalls
        without recomputing."""
        for name in names:
            if not getattr(self, "_runtime_fields", {}).pop(name, None):
                continue
            self.sp.stacked_docvalues.pop(name, None)
            self.sp.global_docvalues.pop(name, None)
            for p in self.sp.shards:
                p.docvalues.pop(name, None)
            for key in ("dv_int", "dv_float", "dv_int_ord"):
                self.dev.get(key, {}).pop(name, None)

    def _compiled_collapse(self, node, key, fld, k):
        """Field collapsing: best hit per field value (reference behavior:
        search/collapse/CollapseBuilder.java + Lucene CollapsingTopDocsCollector).
        Groups = global ordinals of `fld`; docs missing the field share the
        null group. Per shard: scatter-max score per group + lowest-docid
        winner; global: max over shards per group, then top-k groups."""
        cache_key = ("collapse", key, fld, k, self._exec)
        fn = self._cache.get(cache_key)
        if fn is not None:
            return fn
        ctx = self.ctx
        n = self.sp.n_max
        S = self.sp.S

        col = self.sp.global_docvalues.get(fld)
        V = len(col.ord_terms) if (col is not None and col.kind == "ord") else (
            len(col.uniq_values) if (col is not None and col.uniq_values is not None) else 0
        )

        def shard_body(dev1, par1):
            scores, match = node.device_eval(dev1, par1, ctx)
            ok = match[:n] & dev1["live"]
            total = jnp.sum(ok, dtype=jnp.int32)
            s = scores[:n]
            if fld in dev1["dv_ord"]:
                ords, h = dev1["dv_ord"][fld]
                ords = ords.astype(jnp.int32)
            elif fld in dev1["dv_int_ord"]:
                ords, h = dev1["dv_int_ord"][fld], dev1["dv_int"][fld][1]
            else:
                ords = jnp.full(n, -1, jnp.int32)
                h = jnp.zeros(n, bool)
            grp = jnp.where(h & (ords >= 0), ords, V)  # null group = V
            docids = jnp.arange(n, dtype=jnp.int32)
            masked = jnp.where(ok, s, -jnp.inf)
            gmax = jnp.full(V + 1, -jnp.inf, jnp.float32).at[grp].max(masked)
            ismax = ok & (masked == gmax[grp]) & jnp.isfinite(masked)
            # non-winner lanes scatter INT_MAX, which never wins a min
            gdoc = jnp.full(V + 1, 2**31 - 1, jnp.int32).at[grp].min(
                jnp.where(ismax, docids, 2**31 - 1)
            )
            return gmax, gdoc, total

        from .spmd import constrain_shards, manual_shard_region

        region = manual_shard_region(
            shard_body, self.mesh, in_specs=(P("shards"), P("shards")))

        def inner(dev, params):
            return constrain_shards(region(dev, params), self.mesh)

        def search_collapse(dev, params):
            gmax, gdoc, tot = inner(dev, params)  # [S, V+1] x2, [S]
            best = jnp.max(gmax, axis=0)  # [V+1]
            # winner shard: lowest shard index among maxima (merge tie-break)
            is_best = gmax == best[None, :]
            shard_sel = jnp.min(
                jnp.where(is_best, jnp.arange(S)[:, None], S), axis=0
            )
            shard_c = jnp.clip(shard_sel, 0, S - 1)
            doc_sel = jnp.take_along_axis(gdoc, shard_c[None, :], axis=0)[0]
            kk = min(k, V + 1)
            top_s, top_g = jax.lax.top_k(jnp.where(jnp.isfinite(best), best, -jnp.inf), kk)
            return (
                top_s, shard_c[top_g], doc_sel[top_g], top_g,
                tot.sum(),
            )

        fn = jax.jit(search_collapse)
        self._cache[cache_key] = (fn, V)
        return fn, V

    def search_collapse(self, query, fld: str, size=10, from_=0) -> StackedResult:
        m = self.sp.mappings
        node = query if isinstance(query, QueryNode) else parse_query(query, m)
        S = self.sp.S
        views = [self.sp.shard_view(s) for s in range(S)]
        per_shard, keys = [], []
        for v in views:
            p, k_ = node.prepare(v)
            per_shard.append(p)
            keys.append(k_)
        params = _stack_shard_params(per_shard)
        k = max(size + from_, 1)
        got = self._compiled_collapse(node, plan_key(keys), fld, k)
        fn, V = got
        top_s, top_shard, top_doc, top_g, total = jax.device_get(fn(self.dev, params))
        col = self.sp.global_docvalues.get(fld)
        valid = np.isfinite(top_s)
        res_keys = []
        for g, ok_ in zip(top_g, valid):
            if not ok_:
                continue
            if int(g) >= V or col is None:
                res_keys.append(None)
            elif col.kind == "ord":
                res_keys.append(col.ord_terms[int(g)])
            else:
                res_keys.append(int(col.uniq_values[int(g)]))
        end = max(size + from_, 0)
        out = StackedResult(
            top_shard[valid][from_:end].astype(np.int32),
            top_doc[valid][from_:end].astype(np.int32),
            top_s[valid][from_:end].astype(np.float32),
            int(total),
            float(top_s[0]) if valid.any() else None,
        )
        out.collapse_keys = res_keys[from_:end]
        return out

    def scores_at(self, query, doc_shards: np.ndarray, doc_ids: np.ndarray):
        """Evaluate `query`'s scores at specific (shard, docid) hits — the
        rescore gather (reference behavior: QueryRescorer.java combines
        window scores)."""
        from ..query.nodes import mark_exact

        m = self.sp.mappings
        node = query if isinstance(query, QueryNode) else parse_query(query, m)
        # rescore windows combine raw scores arithmetically: exact BM25,
        # never the quantized impact tier
        mark_exact(node)
        S = self.sp.S
        views = [self.sp.shard_view(s) for s in range(S)]
        per_shard, keys = [], []
        for v in views:
            p, k_ = node.prepare(v)
            per_shard.append(p)
            keys.append(k_)
        params = _stack_shard_params(per_shard)
        cache_key = ("scores_at", plan_key(keys), len(doc_ids), self._exec)
        fn = self._cache.get(cache_key)
        if fn is None:
            ctx = self.ctx
            n = self.sp.n_max

            def shard_body(dev1, par1):
                scores, match = node.device_eval(dev1, par1, ctx)
                return scores[:n], match[:n] & dev1["live"]

            from .spmd import constrain_shards, manual_shard_region

            region = manual_shard_region(
                shard_body, self.mesh, in_specs=(P("shards"), P("shards")))

            def inner(dev, params):
                return constrain_shards(region(dev, params), self.mesh)

            def scores_at(dev, params, sh, di):
                scores, match = inner(dev, params)  # [S, n]
                s = scores[sh, di]
                ok = match[sh, di]
                return jnp.where(ok, s, 0.0), ok

            fn = jax.jit(scores_at)
            self._cache[cache_key] = fn
        s, ok = jax.device_get(
            fn(self.dev, params, jnp.asarray(doc_shards), jnp.asarray(doc_ids))
        )
        return s, ok

    def search(
        self,
        query: dict | QueryNode | None,
        size: int = 10,
        from_: int = 0,
        aggs: dict | None = None,
        mappings=None,
        hand_off: bool = False,
    ) -> StackedResult:
        """Plain-DSL requests are served from the shard request cache when
        warm (whole-searcher scope: the merged result depends on every
        shard, so any shard's epoch bump invalidates it); QueryNode
        requests and per-request mapping overrides bypass the cache.
        `hand_off`: as `search_batch`'s."""
        from ..cache import request_cache

        rc = request_cache()
        ck = scope = None
        if rc.enabled and mappings is None and not isinstance(query, QueryNode):
            ck = self._request_cache_key(query, size, from_, aggs)
            scope = self.cache_scope()
            hit = rc.get(scope[0], scope[1], ck)
            if hit is not None:
                from ..telemetry import CACHE_HIT_SPAN, TRACER, profile_event

                profile_event("cache", scope="stacked_search", hits=1,
                              misses=0)
                with TRACER.span(CACHE_HIT_SPAN):
                    return _copy_stacked_result(hit)
            from ..telemetry import profile_event

            profile_event("cache", scope="stacked_search", hits=0, misses=1)
        import time as _time

        from ..telemetry import metrics as _metrics

        _t0 = _time.perf_counter()
        m = mappings if mappings is not None else self.sp.mappings
        node, _ = self._parsed(query, m)
        res = self.search_batch(
            [dict(query=node, size=size, from_=from_, aggs=aggs, mappings=m)],
            hand_off=hand_off)[0]
        _elapsed_ms = (_time.perf_counter() - _t0) * 1000
        _metrics.histogram_record("es.shard.search.ms", _elapsed_ms)
        if ck is not None:
            rc.put(scope[0], scope[1], ck, _copy_stacked_result(res),
                   _stacked_result_nbytes(res), recompute_ms=_elapsed_ms)
        return res

    def _request_cache_key(self, query, size, from_, aggs):
        """A cached StackedResult's identity: what was asked of the device.
        `track_total_hits` is not part of it: the count is always exact,
        so every value maps to one identical result and the engine formats
        `hits.total` from the request afterwards."""
        from ..cache import canonical_key

        return canonical_key({
            "op": "stacked_search", "query": query, "aggs": aggs,
            "size": int(size), "from": int(from_),
            # query-time analyzers (synonym-set reloads) change parsed
            # queries without any index write — part of the identity
            "ag": getattr(self.sp.mappings, "analysis_generation", 0),
        })

    # -- serving waves -----------------------------------------------------

    def search_many_begin(self, requests: list[dict]) -> dict:
        """Wave-shaped entry point for the serving front end: plan and
        DISPATCH every request's program without fetching anything, so a
        completer thread can pull the device outputs (`search_many_fetch`,
        engine-state-free) while the engine thread plans the next wave.

        Each request dict: query, size, from_, aggs, mappings — the
        `search()` keyword surface. Per-request results are byte-identical
        to solo `search()` calls: the cache lookup and per-request
        compiled program are the same code, and every request's program
        is independent of its wave-mates (the wave only shares the
        dispatch+fetch round trip, exactly like `search_batch`). A request
        that raises during planning carries its exception in the state
        and re-raises at finish."""
        import time as _time

        from ..cache import request_cache

        rc = request_cache()
        n = len(requests)
        st = {"t0": _time.perf_counter(), "requests": requests,
              "results": [None] * n, "states": [None] * n,
              "errors": [None] * n, "cache_slots": [None] * n}
        from ..telemetry import profile_event

        hits = misses = 0
        for i, r in enumerate(requests):
            query = r.get("query")
            size = r.get("size", 10)
            from_ = r.get("from_", 0)
            aggs = r.get("aggs")
            mappings = r.get("mappings")
            try:
                ck = scope = None
                if (rc.enabled and mappings is None
                        and not isinstance(query, QueryNode)):
                    ck = self._request_cache_key(query, size, from_, aggs)
                    scope = self.cache_scope()
                    got = rc.get(scope[0], scope[1], ck)
                    if got is not None:
                        hits += 1
                        st["results"][i] = _copy_stacked_result(got)
                        continue
                    misses += 1
                m = mappings if mappings is not None else self.sp.mappings
                node, _ = self._parsed(query, m)
                st["states"][i] = self._agg_dispatch(
                    query=node, size=size, from_=from_, aggs=aggs,
                    mappings=m)
                st["cache_slots"][i] = (ck, scope)
            except Exception as ex:  # noqa: BLE001 - per-request envelope
                st["errors"][i] = ex
        if hits or misses:
            profile_event("cache", scope="stacked_search", hits=hits,
                          misses=misses)
        st["pending"] = [s["outs"] for s in st["states"] if s is not None]
        return st

    def search_many_fetch(self, st: dict) -> None:
        """Pull the wave's device outputs. Touches NO engine/searcher host
        state — safe to run on a completer thread while the engine thread
        plans the next wave (the double-buffer stage of the serving
        pipeline)."""
        if not st["pending"]:
            st["host"] = []
            return
        from ..common import faults

        faults.check("device.fetch", shards=self.sp.S,
                     requests=len(st["pending"]))
        st["host"] = self._fetch(st["pending"])

    def search_many_finish(self, st: dict,
                           raise_errors: bool = True) -> list:
        """Finalize a fetched wave -> per-request StackedResults in
        request order (or the recorded exception object per slot when
        raise_errors=False). Two-pass terms aggs run their second wave
        here synchronously (rare). Runs on the engine thread: cache
        stores and host merges touch shared state."""
        import time as _time

        from ..cache import request_cache

        rc = request_cache()
        out = []
        if self._unpack([s for s in st["states"] if s is not None],
                        st.get("host") or []):
            # rare two-pass terms aggs: one extra dispatch + fetch round,
            # recorded so the wave's host-transition meta stays honest
            st["extra_dispatches"] = st.get("extra_dispatches", 0) + 1
            st["extra_fetches"] = st.get("extra_fetches", 0) + 1
        from ..telemetry import metrics as _metrics

        wave_ms = (_time.perf_counter() - st["t0"]) * 1000
        for i, s in enumerate(st["states"]):
            if st["errors"][i] is not None:
                if raise_errors:
                    raise st["errors"][i]
                out.append(st["errors"][i])
                continue
            res = st["results"][i] if s is None else self._agg_finalize(s)
            if s is not None:
                # computed this wave: store like solo
                _metrics.histogram_record("es.shard.search.ms", wave_ms)
                ck, scope = st["cache_slots"][i]
                if ck is not None:
                    rc.put(scope[0], scope[1], ck,
                           _copy_stacked_result(res),
                           _stacked_result_nbytes(res))
            out.append(res)
        return out

    def search_many(self, requests: list[dict],
                    raise_errors: bool = True) -> list:
        """Cache-aware batched execution of several `search()`-shaped
        requests: one dispatch wave, one device round trip, per-request
        results byte-identical to solo execution (see search_many_begin)."""
        st = self.search_many_begin(requests)
        self.search_many_fetch(st)
        return self.search_many_finish(st, raise_errors=raise_errors)

    def search_batch(self, requests: list[dict],
                     hand_off: bool = False) -> list:
        """Execute several search/agg requests with batched device
        round-trips: every request's program is dispatched before any
        result is fetched, so the fixed dispatch+fetch latency is paid
        once per WAVE, not once per request.
        Two waves maximum: pass-1 for everything, then pass-2 for
        requests whose high-cardinality terms aggs use the two-pass
        candidate scheme. Each request dict: query (dict | QueryNode |
        None), size, from_, aggs, mappings. The cut between launch, fetch
        and finish is the serving waves' (`search_many_begin` / `_fetch` /
        `_finish`).

        `hand_off`: the caller returns what this returns and does no more
        engine work (a solo `_search`): once the programs are launched,
        the fetch and all after it may move to the completer
        (`common/engine_thread.hand_off`), unless a second pass needs the
        engine thread for its launch.

        The reference has no agg-batching analog (each search is its own
        scatter/gather); this is the same discipline `ops/batched` applies
        to the query path, extended to aggregations."""
        from ..telemetry import TRACER

        states = [self._agg_dispatch(**r) for r in requests]
        if hand_off and not any(_two_pass(s) for s in states):
            _hand_off()
        host = self._fetch([s["outs"] for s in states])
        with TRACER.span("engine.unpack"):
            self._unpack(states, host)
        return [self._agg_finalize(s) for s in states]

    def _fetch(self, outs: list) -> list:
        """`jax.device_get` of launched programs' packed outputs: the wait
        for the programs and the copies. Touches no engine or searcher
        state, so a completer thread may run it."""
        from ..telemetry import TRACER, time_kernel

        with time_kernel("sharded.spmd_topk", shards=self.sp.S,
                         requests=len(outs), queries=len(outs),
                         num_docs=self.sp.S * self.sp.n_max):
            with TRACER.span("engine.fetch"):
                return jax.device_get(outs)

    def _unpack(self, states: list[dict], host: list) -> bool:
        """Cut each state's result tree out of its fetched buffers; launch
        and fetch the second pass of the requests that need one. -> whether
        any did."""
        wave2 = []
        for s, ho in zip(states, host):
            s["host"] = self._fetched(ho, s["out_layout"])
            if self._agg_pass2_dispatch(s):
                wave2.append(s)
        if wave2:
            host2 = jax.device_get([s["outs2"] for s in wave2])
            for s, h2 in zip(wave2, host2):
                s["host2"] = self._fetched(h2, s["out_layout2"])
        return bool(wave2)

    def _parsed(self, query, m, aggs=None):
        """-> (the query's node, its aggregation nodes or None); a query
        that arrives parsed and asks for no aggregations opens no span."""
        if isinstance(query, QueryNode) and not aggs:
            return query, None
        from ..telemetry import TRACER

        with TRACER.span("engine.parse"):
            node = (query if isinstance(query, QueryNode)
                    else parse_query(query, m))
            if not aggs:
                return node, None
            from ..aggs import parse_aggs

            return node, parse_aggs(aggs, m)

    def _agg_dispatch(self, query=None, size=10, from_=0, aggs=None,
                      mappings=None):
        """Plan + launch one request's pass-1 program (no device fetch)."""
        from ..telemetry import SOLO_ROWS, TRACER, account, metrics

        m = mappings if mappings is not None else self.sp.mappings
        node, agg_nodes = self._parsed(query, m, aggs)
        with TRACER.span("engine.plan") as plan:
            S = self.sp.S
            views = [self.sp.shard_view(s) for s in range(S)]
            per_shard = []
            keys = []
            for v in views:
                p, k_ = node.prepare(v)
                per_shard.append(p)
                keys.append(k_)
            params = _stack_shard_params(per_shard)
            agg_params, agg_key = {}, ()
            if agg_nodes:
                per_shard_aggs = []
                akeys = []
                for v in views:
                    parts = {nme: a.prepare(v, m)
                             for nme, a in agg_nodes.items()}
                    per_shard_aggs.append(
                        {nme: p for nme, (p, _) in parts.items()})
                    akeys.append(tuple(
                        (nme, kk) for nme, (_, kk) in sorted(parts.items())))
                agg_params = _stack_shard_params(per_shard_aggs)
                agg_key = tuple(akeys)
            k = min(max(size + from_, 1), max(self.sp.n_max * self.sp.S, 1))
            keys = plan_key(keys)
            programs = len(self._cache)  # a miss adds one
            fn, buffers = self._packed_program(
                node, keys, k, agg_nodes, agg_key, params, agg_params)
            hit = len(self._cache) == programs
            plan.attributes["program_cache"] = "hit" if hit else "miss"
            # the rows of the family's two lists it gathers, real and with
            # padding: none where the query is no match
            real, padded, tiers = match_rows(keys[0], params) or (0, 0, None)
            if tiers is not None:
                plan.attributes.update(dense_tier=tiers[0], rows_tier=tiers[1],
                                       padded_rows=padded)
        with account():
            from ..monitoring.xla_introspect import check_dispatch

            metrics.counters_add([(SOLO_ROWS, (real, padded))])
            metrics.counter_inc("es.search.topk.xla_topk")
            check_dispatch("sharded.spmd_topk", fn, (self.dev, buffers),
                           fields={"queries": 1, "k": k,
                                   "num_docs": self.sp.S * self.sp.n_max})
        # one transfer per buffer and the launch; behind a miss also trace,
        # lower and compile
        with TRACER.span("engine.dispatch",
                         **({} if hit else {"compiled": True})):
            outs, out_layout = self._launch(fn, buffers)
        return {
            "node": node, "keys": keys, "k": k, "size": size,
            "from_": from_, "agg_nodes": agg_nodes, "agg_key": agg_key,
            "params": params, "agg_params": agg_params,
            "outs": outs, "out_layout": out_layout,
        }

    def _packed_program(self, node, key, k, agg_nodes, agg_key, params,
                        agg_params):
        """-> (the plan's program, the parameters packed as it unpacks
        them). Host preparation: no transfer happens here."""
        buffers, layout = pack((params, agg_params))
        return (self._compiled(node, key, k, agg_nodes, agg_key, layout),
                buffers)

    def _launch(self, fn, buffers):
        """Call a `_compiled` program on packed parameters, counted.
        -> (its packed outputs, still on the device; the layout that
        `_fetched` unpacks them by)."""
        from ..telemetry import metrics

        n_buffers, n_leaves = fn.packed
        metrics.counter_inc("es.search.dispatch.buffers", n_buffers)
        metrics.counter_inc("es.search.dispatch.leaves", n_leaves)
        outs = fn(self.dev, buffers)
        return outs, fn.out_layout

    def _fetched(self, buffers, layout):
        """The `(g_scores, g_shard, g_doc, total, agg_out)` that a `_compiled`
        program computed, from its fetched buffers; counted as `_launch`
        counts the way in."""
        from ..telemetry import metrics

        metrics.counter_inc("es.search.fetch.buffers", len(buffers))
        metrics.counter_inc("es.search.fetch.leaves", len(layout[1]))
        return unpack_host(buffers, layout)

    def _agg_pass2_dispatch(self, s) -> bool:
        """Launch pass 2 (two-pass terms candidates) if the request needs
        it; candidate selection uses the GLOBAL merged counts (exact —
        unlike the reference's per-shard shard_size approximation)."""
        agg_nodes = s["agg_nodes"]
        if not agg_nodes:
            return False
        from ..aggs import two_pass_plan

        tp = two_pass_plan(agg_nodes)
        if not tp:
            return False
        _s1, _s2, _s3, _t, agg_out = s["host"]
        merged = {name: anode.merge_partials(agg_out[name])
                  for name, anode in agg_nodes.items()}
        s["merged"] = merged
        s["tp"] = tp
        S = self.sp.S
        agg_params = s["agg_params"]
        for name, a in tp.items():
            cm = a.select_candidates(merged[name])
            agg_params[name] = {
                **agg_params[name],
                "cand": np.broadcast_to(cm, (S, len(cm))).copy(),
            }
        # the candidates are new leaves: the host tree is packed again
        fn2, buffers = self._packed_program(
            s["node"], s["keys"], s["k"], agg_nodes,
            (s["agg_key"], "tp2",
             tuple(sorted((n, a._C) for n, a in tp.items()))),
            s["params"], agg_params)
        s["outs2"], s["out_layout2"] = self._launch(fn2, buffers)
        return True

    def _agg_finalize(self, s) -> StackedResult:
        from ..telemetry import TRACER

        with TRACER.span("engine.collect"):
            g_scores, g_shard, g_doc, total, agg_out = s["host"]
            agg_nodes = s["agg_nodes"]
            aggregations = None
            if agg_nodes:
                merged = s.get("merged") or {
                    name: anode.merge_partials(agg_out[name])
                    for name, anode in agg_nodes.items()
                }
                if "host2" in s:
                    _s1, _s2, _s3, _t, agg_out2 = s["host2"]
                    for name, a in s["tp"].items():
                        merged[name].update(a.merge_partials(agg_out2[name]))
                aggregations = {
                    name: anode.finalize(merged[name], 1)[0]
                    for name, anode in agg_nodes.items()
                }
            size, from_ = s["size"], s["from_"]
            valid = np.isfinite(g_scores)
            max_score = float(g_scores[0]) if valid.any() else None
            end = max(size + from_, 0)
            return StackedResult(
                g_shard[valid][from_:end].astype(np.int32),
                g_doc[valid][from_:end].astype(np.int32),
                g_scores[valid][from_:end].astype(np.float32),
                int(total),
                max_score,
                aggregations,
            )

    def count(self, query=None) -> int:
        return self.search(query, size=1).total

    # -- field-sorted search ----------------------------------------------

    def _compiled_sorted(self, node, key_t, k, plan, has_after, agg_nodes, agg_key):
        cache_key = ("sorted", key_t, k, plan.struct_key(), has_after, agg_key, self._exec)
        fn = self._cache.get(cache_key)
        if fn is not None:
            return fn
        ctx = self.ctx
        n = self.sp.n_max
        k_local = min(k, max(n, 1))

        def shard_body(dev1, par1, after, agg_par1):
            scores, match = node.device_eval(dev1, par1, ctx)
            ok = match[:n] & dev1["live"]
            total = jnp.sum(ok, dtype=jnp.int32)
            agg_out = {}
            if agg_nodes:
                seg = jnp.where(ok, 0, 1).astype(jnp.int32)
                dev_a = {**dev1, "_query_scores": scores[:n]}
                for name, anode in agg_nodes.items():
                    agg_out[name] = anode.device_eval_segmented(
                        dev_a, agg_par1[name], seg, 1, ok, ctx
                    )
            keys = plan.device_keys(dev1, scores, n)
            sel = ok
            if has_after:
                gt = jnp.zeros(n, bool)
                eq = jnp.ones(n, bool)
                for kk, aa in zip(keys, after):
                    gt = gt | (eq & (kk > aa))
                    eq = eq & (kk == aa)
                sel = sel & gt
            invalid = (~sel).astype(jnp.int32)
            docs = jnp.arange(n, dtype=jnp.int32)
            sorted_ops = jax.lax.sort((invalid, *keys, docs), num_keys=1 + len(keys))
            return (
                sorted_ops[0][:k_local],
                tuple(o[:k_local] for o in sorted_ops[1:-1]),
                sorted_ops[-1][:k_local],
                total,
                agg_out,
            )

        from .spmd import constrain_shards, manual_shard_region

        region = manual_shard_region(
            shard_body, self.mesh,
            in_specs=(P("shards"), P("shards"), P(), P("shards")))

        def search_sorted(dev, params, after, agg_params):
            return constrain_shards(region(dev, params, after, agg_params),
                                    self.mesh)

        fn = jax.jit(search_sorted)
        self._cache[cache_key] = fn
        return fn

    def search_sorted(
        self,
        query,
        sort_fields,
        size: int = 10,
        from_: int = 0,
        search_after=None,
        aggs: dict | None = None,
        mappings=None,
    ):
        """-> (hits: [(shard, docid, sort_values)], total, aggregations)."""
        from ..query.sort import SortPlan

        m = mappings if mappings is not None else self.sp.mappings
        node = query if isinstance(query, QueryNode) else parse_query(query, m)
        agg_nodes = None
        if aggs:
            from ..aggs import parse_aggs

            agg_nodes = parse_aggs(aggs, m)
        S = self.sp.S
        views = [self.sp.shard_view(s) for s in range(S)]
        # one plan per shard view (global dv dictionaries -> identical keys)
        plan = SortPlan(sort_fields, views[0], m)
        per_shard, keys_t = [], []
        for v in views:
            p, k_ = node.prepare(v)
            per_shard.append(p)
            keys_t.append(k_)
        params = _stack_shard_params(per_shard)
        agg_params, agg_key = {}, ()
        if agg_nodes:
            from ..aggs import two_pass_plan

            per_shard_aggs, akeys = [], []
            for attempt in (0, 1):
                per_shard_aggs, akeys = [], []
                for v in views:
                    parts = {nm: a.prepare(v, m) for nm, a in agg_nodes.items()}
                    per_shard_aggs.append({nm: p for nm, (p, _) in parts.items()})
                    akeys.append(tuple((nm, kk) for nm, (_, kk) in sorted(parts.items())))
                tp = two_pass_plan(agg_nodes)
                if not tp:
                    break
                # field-sorted execution can't orchestrate two passes: fall
                # back to single-pass (one-pass budgets apply as before)
                for a in tp.values():
                    a.force_single_pass = True
            agg_params = _stack_shard_params(per_shard_aggs)
            agg_key = tuple(akeys)
        k = min(max(size + from_, 1), max(self.sp.n_max, 1))
        after = ()
        if search_after is not None:
            after = plan.after_keys(search_after, self.sp)
        fn = self._compiled_sorted(
            node, plan_key(keys_t), k, plan, search_after is not None, agg_nodes, agg_key
        )
        inv, keys_s, docs, totals, agg_out = jax.device_get(
            fn(self.dev, params, after, agg_params)
        )
        aggregations = None
        if agg_nodes:
            aggregations = {
                name: anode.finalize(anode.merge_partials(agg_out[name]), 1)[0]
                for name, anode in agg_nodes.items()
            }
        # host-side coordinator merge: lexsort by (keys..., shard) over the
        # S*k_local candidates, skipping invalid slots
        S_, kl = inv.shape
        flat_inv = inv.reshape(-1)
        shard_of = np.repeat(np.arange(S_, dtype=np.int32), kl)
        flat_docs = docs.reshape(-1)
        flat_keys = [np.asarray(kk).reshape(-1) for kk in keys_s]
        order = np.lexsort(tuple([shard_of] + flat_keys[::-1] + [flat_inv]))
        valid = flat_inv[order] == 0
        order = order[valid]
        take = order[from_ : size + from_]
        # per-position values in original space
        key_cols = [fk[take] for fk in flat_keys]
        values = plan.hit_values(key_cols, list(range(len(take))))
        hits = [
            (int(shard_of[i]), int(flat_docs[i]), v)
            for i, v in zip(take, values)
        ]
        return hits, int(totals.sum()), aggregations


def msearch_sharded(ss: "StackedSearcher", fld: str,
                    queries: list, k: int = 10, _return_program=False):
    """Batched multi-query term-disjunction `_msearch` over the shard mesh.

    The production C5 shape: per-shard batch plans (one BatchPlan per shard,
    stacked to [S, ...]) run the batched disjunction kernel inside shard_map,
    and the coordinator merge applies the reference's
    (score desc, shard asc, doc asc) order (reference behavior:
    action/search/TransportMultiSearchAction.java fan-out +
    SearchPhaseController.java:232 TopDocs.merge). On one chip the same body
    runs under vmap; on a mesh the gather of the [S, Q, k] partials rides
    ICI collectives.

    With the fused kernel eligible (dense tier present, k <= 16,
    ES_TPU_FUSED on TPU or forced), each shard runs the fused tiled
    pipeline (ops/fused._fused_pipeline — in-kernel dense matmul +
    per-tile top-t + canonical f32 rescore) instead of the legacy
    disjunction kernel. Under the pjit execution model (PR 11) the
    pipeline runs inside an embedded shard_map manual region of the ONE
    compiled SPMD program that also merges on-device; the shard_map
    partials + host-merge form survives only as the legacy-model /
    test-oracle route. Queries flagged by any shard re-run on the exact
    arm either way, so results never depend on the fused pass.

    The shard request cache fronts the routes at the storage granularity
    matching each execution model: pjit searchers key at WAVE scope and
    store post-merge per-query rows (so the one-program route stays
    engaged when warm); legacy models keep per-SHARD entries — each
    (query, shard) pair's pre-merge top-k row cached under
    (shard token, shard epoch, canonical query key), so a partially-warm
    msearch only re-scores queries with at least one cold shard, reuses
    warm shards' cached rows at the coordinator merge, and a single
    shard's epoch bump (in-place mutation) leaves the other shards warm.

    -> (scores [Q, k], shard [Q, k], docid [Q, k], totals [Q]) numpy.
    """
    if _return_program or not queries:
        return _msearch_sharded_exact(ss, fld, queries, k, _return_program)
    from ..cache import request_cache

    rc = request_cache()
    if rc.enabled:
        return _msearch_sharded_cached(ss, rc, fld, queries, k)
    # pjit (the resolved default, incl. single-query meshes): ONE
    # compiled SPMD program — fused Pallas arm (embedded shard_map
    # region) > impact > exact, each including the on-device all-gather
    # + top-k merge. Byte-identical rows to the partials + host-merge
    # oracle below (tests/test_spmd.py). No per-tier env fork: the arm
    # is chosen by pack shape alone, the execution model by the
    # searcher's RESOLVED mode (so a later env flip cannot split a
    # searcher across execution models).
    if getattr(ss, "_exec", "vmap") == "pjit":
        return _msearch_merged(ss, fld, queries, k)
    # legacy execution models (shard_map test oracle / off-mesh vmap):
    # per-shard partials + host coordinator merge, fused > impact >
    # exact — the SAME arm priority as the merged route
    return _merge_shard_rows(*_msearch_sharded_partials(ss, fld, queries, k))


def msearch_wave(ss: "StackedSearcher", fld: str, queries: list,
                 k: int = 10):
    """Serving-wave msearch: pad the coalesced term-disjunction batch to
    the compiled power-of-two batch tier (pad queries are empty — they
    plan to zero weights and score nothing) so steady-state traffic
    reuses a small executable family instead of compiling one program per
    wave size, then strip the pad rows off.

    -> ((scores [Q,k], shard [Q,k], doc [Q,k], totals [Q]), tier) — tier
    is the padded batch width, so tier/Q is the wave's device occupancy.
    Each real query's row is byte-identical to a solo 1-query wave: rows
    are computed independently per query and pad lanes contribute exact
    zeros (the serving parity contract, tests/test_serving.py)."""
    st = msearch_wave_begin(ss, fld, queries, k)
    msearch_wave_fetch(st)
    return msearch_wave_finish(st)


def msearch_wave_begin(ss: "StackedSearcher", fld: str, queries: list,
                       k: int = 10) -> dict:
    """Wave-deferred term lane (PR 11): pad to the compiled batch tier,
    consult the request cache, and DISPATCH the cold subset's ONE merged
    SPMD program without fetching anything — the serving wave's single
    fetch stage (`engine.search_wave_fetch`) pulls this lane together
    with every other lane in one host round-trip, so the term lane no
    longer blocks the scheduler thread inside `search_wave_begin`.

    The deferred merged route serves both the pjit mesh AND the off-mesh
    vmap model (a single-device merge is still one program with a k-row
    fetch); only the shard_map oracle resolves synchronously here — it
    is a test fixture, not a serving model."""
    Q = len(queries)
    padded, tier = _pad_to_wave_tier(queries)
    st = {"Q": Q, "tier": tier}
    if getattr(ss, "_exec", "vmap") == "shardmap":
        st["result"] = msearch_sharded(ss, fld, padded, k)
        return st
    st.update(_merged_cached_begin(ss, fld, padded, k))
    return st


# the fused arm's escalation is padded to this many queries at least: the
# exact arm reads the whole dense tier whatever its batch, so the rows are
# free, and the count of flagged queries (1, 2, 3, ... by arrival order)
# then names one program and not three or four
ESCALATION_MIN_TIER = 8


def _pad_to_wave_tier(queries: list, floor: int = 1) -> tuple[list, int]:
    """-> (the queries padded with empty ones to their batch tier, the
    tier): the one place a wave's batch width is decided, for the wave
    itself and for the fused arm's escalation alike. An empty query plans
    to zero weights and scores nothing, and every row of a batch is
    computed independently, so a real query's row does not depend on its
    companions or on the padding."""
    from ..ops.batched import BatchTermSearcher

    tier = max(BatchTermSearcher.wave_q_tier(len(queries)), floor)
    return list(queries) + [[] for _ in range(tier - len(queries))], tier


def _wave_program(cache: dict, site: str, cache_key: tuple, build):
    """The compiled program of one key of a wave-program cache, built by
    `build()` where the key is new; every look-up counted
    (`es.jit.cache.wave_program.hits` / `.misses` and `es.jit.cache.<site>.*`:
    the misses are the size of the family the traffic has reached)."""
    from ..monitoring.device import note_executable_cache

    fn = cache.get(cache_key)
    note_executable_cache("wave_program", fn is not None)
    note_executable_cache(site, fn is not None)
    if fn is None:
        log.info("wave program: new key %s %s", site, cache_key)
        fn = cache[cache_key] = jax.jit(build())
    return fn


def _wave_launch(fn, *args):
    """Call a wave program: the stage `engine.wave_launch` of the wave this
    thread is carrying, and `es.search.dispatch.buffers` / `.leaves` for the
    host arrays handed over, each one host-to-device transfer (a pack that
    is resident on the device is neither)."""
    from ..telemetry import metrics, wave_stage

    host = sum(isinstance(a, (np.ndarray, np.generic))
               for a in jax.tree_util.tree_leaves(args))
    metrics.counter_inc("es.search.dispatch.buffers", host)
    metrics.counter_inc("es.search.dispatch.leaves", host)
    with wave_stage("engine.wave_launch"):
        return fn(*args)


def msearch_wave_fetch(st: dict) -> None:
    """Pull the wave's pending merged-program outputs (no-op when the
    lane resolved in begin or the engine's combined wave fetch already
    delivered them)."""
    m = st.get("merged")
    if m is not None:
        _msearch_merged_fetch(m)


def msearch_wave_finish(st: dict):
    """-> ((scores [Q,k], shard, doc, totals [Q]), tier); stores cold
    rows into the request cache (engine thread)."""
    if "result" in st:
        v, s, d, t = st["result"]
    else:
        v, s, d, t = _merged_cached_finish(st)
    Q = st["Q"]
    return (v[:Q], s[:Q], d[:Q], t[:Q]), st["tier"]


def _merge_shard_rows(v, i, t):
    """Coordinator merge of per-shard top rows [S, Q, kk]: flat order is
    (score desc, shard asc, doc asc) — the reference's
    SearchPhaseController/TopDocs.merge order. -> (scores [Q, kk],
    shard [Q, kk], docid [Q, kk], totals [Q])."""
    v, i, t = np.asarray(v), np.asarray(i), np.asarray(t)
    S, Q, kk = v.shape
    flat_v = v.transpose(1, 0, 2).reshape(Q, -1)
    flat_i = i.transpose(1, 0, 2).reshape(Q, -1)
    flat_s = np.broadcast_to(
        np.repeat(np.arange(S), kk)[None, :], flat_v.shape
    )
    order = np.lexsort((flat_i, flat_s, -flat_v), axis=1)[:, :kk]
    return (
        np.take_along_axis(flat_v, order, axis=1),
        np.take_along_axis(flat_s, order, axis=1).astype(np.int32),
        np.take_along_axis(flat_i, order, axis=1),
        t.sum(axis=0),
    )


def _impact_sharded_usable(ss: "StackedSearcher") -> bool:
    """The sharded impact arm serves: routing on (ES_TPU_IMPACT), the
    stacked code blocks derived for the CURRENT effective stats, and
    resident on device."""
    from ..ops.scoring import impact_enabled

    return (impact_enabled() and ss.sp.impact_serving()
            and "impact_codes" in ss.dev)


def impact_arm_usable(ss: "StackedSearcher") -> bool:
    """Public arm-routing probe: would msearch route this searcher to the
    impact tier? Superpack eligibility (`tenancy/`) must exclude such
    searchers — members are scored by the exact tenant-gather kernel, and
    parity is against whatever arm per-index dispatch would pick."""
    return _impact_sharded_usable(ss)


def plan_adapter(ss: "StackedSearcher", s: int) -> "_PlanShardAdapter":
    """Public host-planning adapter for one shard of a stacked searcher:
    a BatchTermSearcher over it produces the EXACT per-index plan
    (weights from effective global stats, shard-local block rows) —
    shared by the merged-msearch arm and the superpack tenant-gather
    planner so their plans can never drift apart."""
    return _PlanShardAdapter(ss.sp, s, ss)


def _msearch_sharded_partials(ss: "StackedSearcher", fld: str,
                              queries: list, k: int):
    """Per-shard pre-merge rows (v [S, Q, kk], i [S, Q, kk], t [S, Q])
    from whichever arm serves this searcher: the fused pipeline (with
    per-shard escalation), the impact-tier gather+sum, or the legacy
    exact kernel."""
    from ..planner import execution_planner

    fs = _fused_sharded_for(ss)
    fused_ok = fs is not None and fs.usable(k)
    S, Q, n_max = ss.sp.S, len(queries), ss.sp.n_max
    cands = []
    if fused_ok:
        cands.append(("fused", "sharded.fused_pipeline",
                      {"shards": S, "queries": Q, "k": k,
                       "v": ss.sp.dense_v, "num_docs": S * fs.n_pad}))
    if _impact_sharded_usable(ss):
        cands.append(("impact", "sharded.impact_disjunction",
                      {"shards": S, "queries": Q, "k": k,
                       "num_docs": S * n_max}))
    cands.append(("exact", "sharded.exact_disjunction",
                  {"tier": "exact", "shards": S, "queries": Q, "k": k,
                   "num_docs": S * n_max}))
    arm = execution_planner().choose_arm("sharded.msearch_partials", cands)
    if arm == "fused":
        return fs.msearch_partials(fld, queries, k)
    if arm == "impact":
        out = _msearch_impact_partials(ss, fld, queries, k)
        if out is not None:
            return out
    return _msearch_exact_partials(ss, fld, queries, k)


def _merged_cached_begin(ss: "StackedSearcher", fld: str, queries: list,
                         k: int) -> dict:
    """Wave-scope cache front for the merged pjit route (PR 11
    satellite): post-merge per-query rows are the storage unit, keyed
    under the WHOLE-SEARCHER scope (`cache_scope`: every shard's epoch),
    so a warm cache serves merged rows directly and the cold subset
    rides the ONE-program route — previously an enabled cache silently
    forced every pjit msearch onto the slower partials + host-merge
    path, whose per-shard rows were the only storage unit. Dispatches
    the cold subset WITHOUT fetching; `_merged_cached_finish` assembles
    and stores. With the cache disabled this degrades to cold=everything
    and no stores."""
    from ..cache import canonical_key, request_cache

    rc = request_cache()
    st = {"ss": ss, "fld": fld, "k": k, "queries": queries,
          "rows": {}, "cold": list(range(len(queries))),
          "qkeys": None, "scope": None, "merged": None}
    if rc.enabled:
        qkeys = [
            canonical_key({"op": "msearch_merged", "fld": fld, "k": int(k),
                           "q": [[t, float(b)] for t, b in q]})
            for q in queries
        ]
        tok, ep = ss.cache_scope()
        cold = []
        for qi, ck in enumerate(qkeys):
            got = rc.get(tok, ep, ck)
            if got is None:
                cold.append(qi)
            else:
                st["rows"][qi] = got
        from ..telemetry import profile_event

        profile_event("cache", scope="msearch_merged",
                      hits=len(queries) - len(cold), misses=len(cold))
        st.update(cold=cold, qkeys=qkeys, scope=(tok, ep))
    if st["cold"]:
        st["merged"] = _msearch_merged_begin(
            ss, fld, [queries[qi] for qi in st["cold"]], k)
    return st


def _merged_cached_finish(st: dict):
    """Assemble warm + freshly merged rows -> (v [Q, kk], shard, doc,
    totals [Q]); stores cold rows under the wave-scope keys."""
    from ..cache import request_cache

    rows, cold = st["rows"], st["cold"]
    if st["merged"] is not None:
        cv, csh, ci, ct = _msearch_merged_finish(st["merged"])
        rc = request_cache()
        recompute_ms = None
        if st["qkeys"] is not None and rc.enabled and cold:
            # PR 18: admission hint — the planner's predicted wall for
            # re-running this merged wave, amortized per cold row (None
            # while the kernel EMA is cold: admit, today's behavior)
            from ..planner import execution_planner

            ss = st["ss"]
            total = execution_planner().predict_ms(
                "sharded.allgather_topk",
                {"tier": "exact", "shards": ss.sp.S, "queries": len(cold),
                 "k": st["k"], "num_docs": ss.sp.S * ss.sp.n_max})
            if total is not None:
                recompute_ms = total / len(cold)
        for j, qi in enumerate(cold):
            row = (cv[j].copy(), csh[j].copy(), ci[j].copy(), int(ct[j]))
            rows[qi] = row
            if st["qkeys"] is not None and rc.enabled:
                tok, ep = st["scope"]
                rc.put(tok, ep, st["qkeys"][qi], row,
                       row[0].nbytes + row[1].nbytes + row[2].nbytes + 96,
                       recompute_ms=recompute_ms)
    Q = len(st["queries"])
    width = max((r[0].shape[0] for r in rows.values()), default=st["k"])
    V = np.full((Q, width), -np.inf, np.float32)
    SH = np.zeros((Q, width), np.int32)
    I = np.zeros((Q, width), np.int64)
    T = np.zeros((Q,), np.int64)
    for qi, (rv, rs, ri, rt) in rows.items():
        V[qi, : rv.shape[0]] = rv
        SH[qi, : rs.shape[0]] = rs
        I[qi, : ri.shape[0]] = ri
        T[qi] = rt
    return V, SH, I, T


def _msearch_sharded_cached(ss: "StackedSearcher", rc, fld: str,
                            queries: list, k: int):
    """Cached msearch. pjit searchers key at WAVE scope and store
    post-merge rows so the one-program route stays engaged
    (_merged_cached_begin); legacy execution models keep the per-shard
    storage unit: warm (query, shard) rows come from the cache, queries
    with any cold shard re-score (one batched SPMD dispatch over the
    cold subset — the device program always runs all shards, but warm
    shards' CACHED rows stay authoritative for the merge and warm
    entries are never re-stored), then one coordinator merge."""
    if getattr(ss, "_exec", "vmap") == "pjit":
        st = _merged_cached_begin(ss, fld, queries, k)
        if st["merged"] is not None:
            from ..telemetry import host_transition

            host_transition("dispatch")
            _msearch_merged_fetch(st["merged"])
        return _merged_cached_finish(st)
    from ..cache import canonical_key

    S = ss.sp.S
    qkeys = [
        canonical_key({"op": "msearch_sharded", "fld": fld, "k": int(k),
                       "q": [[t, float(b)] for t, b in q]})
        for q in queries
    ]
    rows: dict[tuple, tuple] = {}
    cold: list[int] = []
    for qi, ck in enumerate(qkeys):
        warm = True
        for s in range(S):
            tok, ep = ss.shard_cache_scope(s)
            got = rc.get(tok, ep, ck)
            if got is None:
                warm = False
            else:
                rows[(qi, s)] = got
        if not warm:
            cold.append(qi)
    from ..telemetry import profile_event

    for s in range(S):
        hits = sum(1 for qi in range(len(queries)) if (qi, s) in rows)
        profile_event("cache", scope="msearch_sharded", shard=s,
                      hits=hits, misses=len(queries) - hits)
    if cold:
        v, i, t = _msearch_sharded_partials(
            ss, fld, [queries[qi] for qi in cold], k)
        v, i, t = np.asarray(v), np.asarray(i), np.asarray(t)
        for j, qi in enumerate(cold):
            for s in range(S):
                if (qi, s) in rows:
                    continue  # warm per-shard entry stays authoritative
                row = (v[s, j].copy(), i[s, j].copy(), int(t[s, j]))
                rows[(qi, s)] = row
                tok, ep = ss.shard_cache_scope(s)
                rc.put(tok, ep, qkeys[qi], row,
                       row[0].nbytes + row[1].nbytes + 96)
    Q = len(queries)
    width = max(r[0].shape[0] for r in rows.values())
    V = np.full((S, Q, width), -np.inf, np.float32)
    I = np.zeros((S, Q, width), np.int64)
    T = np.zeros((S, Q), np.int64)
    for (qi, s), (rv, ri, rt) in rows.items():
        V[s, qi, : rv.shape[0]] = rv
        I[s, qi, : ri.shape[0]] = ri
        T[s, qi] = rt
    return _merge_shard_rows(V, I, T)


def _msearch_stack_plans(ss: "StackedSearcher", fld: str, queries: list,
                         k: int, *, impact: bool = False) -> dict | None:
    """Shared host planning of the stacked msearch arms: one
    BatchTermSearcher plan per shard, padded in place to the common
    (Ts, B) shape (row 0 = padding). -> dict of stacked [S, ...] plan
    arrays + scoring context; None when impact=True and any shard's plan
    cannot ride the impact tier."""
    from ..ops.batched import BatchTermSearcher

    sp = ss.sp
    S = sp.S
    adapters = [_PlanShardAdapter(sp, s, ss) for s in range(S)]
    plans = [BatchTermSearcher(a).plan(fld, queries, k) for a in adapters]
    if impact and any(p.impact_w is None for p in plans):
        return None
    # the one place the stacked arms' (Ts, B) is decided: on the ladders,
    # never the exact largest of the batch, so that which queries share a
    # batch cannot mint a program (row 0 is the all-padding block and a
    # padded term weighs 0: a padded lane scores nothing)
    ts_max = BatchTermSearcher.wave_ts_tier(
        max(p.sparse_rows.shape[1] for p in plans))
    b_max = BatchTermSearcher.wave_b_tier(
        max(p.sparse_rows.shape[2] for p in plans))
    attrs = ("sparse_weights", "impact_w") if impact else ("sparse_weights",)
    for s in range(S):
        sr = plans[s].sparse_rows
        plans[s].sparse_rows = np.pad(
            sr, ((0, 0), (0, ts_max - sr.shape[1]), (0, b_max - sr.shape[2]))
        )
        for attr in attrs:
            a = getattr(plans[s], attr)
            setattr(plans[s], attr,
                    np.pad(a, ((0, 0), (0, ts_max - a.shape[1]))))
    out = {
        "W": np.stack([p.W for p in plans]),  # [S, Q, V]
        "rows": np.stack([p.sparse_rows for p in plans]),
        "ws": np.stack([p.sparse_weights for p in plans]),
        # effective (override-aware) stats with the empty-field 1.0 guard —
        # raw field_stats would diverge from the tier under tiered refresh
        "avgdl": adapters[0].pack.avgdl(fld),
        "has_norms": fld in ss.ctx.has_norms,
        "kk": min(max(k, 1), max(sp.n_max, 1)),
    }
    if impact:
        out["iws"] = np.stack([p.impact_w for p in plans])
    return out


def _msearch_impact_partials(ss: "StackedSearcher", fld: str,
                             queries: list, k: int = 10):
    """The sharded impact arm (BM25S): the same SPMD shard body as the
    exact arm, but the sparse tail is a gather+sum over the stacked
    quantized impact code blocks (batch_term_disjunction's impact_w
    mode) — no tf/dl gathers, no BM25 arithmetic, ~half the postings
    bytes per query. Returns None when any shard's plan cannot ride the
    tier (caller falls back to the exact arm)."""
    from ..ops.batched import batch_term_disjunction

    sp = ss.sp
    S = sp.S
    pl = _msearch_stack_plans(ss, fld, queries, k, impact=True)
    if pl is None:
        return None
    Q = len(queries)
    W, rows, ws, iws = pl["W"], pl["rows"], pl["ws"], pl["iws"]
    avgdl, has_norms, kk = pl["avgdl"], pl["has_norms"], pl["kk"]
    n_max = sp.n_max
    Ts, B = rows.shape[2], rows.shape[3]

    def shard_body(dev1, W1, rows1, ws1, iws1):
        dev = {
            "post_docids": dev1["post_docids"][0],
            "impact_codes": dev1["impact_codes"][0],
            "live": dev1["live"][0],
        }
        if "dense_tfn" in dev1:
            dev["dense_tfn"] = dev1["dense_tfn"][0]
        v, i, t = batch_term_disjunction(
            dev, (Ts, B, kk), W1[0], rows1[0], ws1[0],
            avgdl=avgdl, num_docs=n_max, has_norms=has_norms,
            impact_w=iws1[0],
        )
        return v[None], i[None], t[None]

    sub = {key: ss.dev[key] for key in
           ("post_docids", "impact_codes", "live")}
    if "dense_tfn" in ss.dev:
        sub["dense_tfn"] = ss.dev["dense_tfn"]
    def build():
        if ss.mesh is not None:
            def msearch_impact(dev, W_, rows_, ws_, iws_):
                specs = jax.tree_util.tree_map(lambda _: P("shards"), dev)
                return shard_map(
                    shard_body, mesh=ss.mesh,
                    in_specs=(specs,) + (P("shards"),) * 4,
                    out_specs=(P("shards"), P("shards"), P("shards")),
                )(dev, W_, rows_, ws_, iws_)
        else:
            def msearch_impact(dev, W_, rows_, ws_, iws_):
                def body(d1, w1, r1, s1, i1):
                    return shard_body(
                        jax.tree_util.tree_map(lambda x: x[None], d1),
                        w1[None], r1[None], s1[None], i1[None],
                    )
                v, i, t = jax.vmap(body)(dev, W_, rows_, ws_, iws_)
                return v[:, 0], i[:, 0], t[:, 0]
        return msearch_impact

    fn = _wave_program(ss._cache, "msearch_impact",
                       ("msearch_impact", fld, Ts, B, kk, Q), build)
    from ..telemetry import profile_event, time_kernel

    code_bytes = int(np.dtype(ss.dev["impact_codes"].dtype).itemsize)
    profile_event("tier", tier="impact", queries=Q)
    fields = dict(tier="impact", shards=S, queries=Q, k=kk,
                  num_docs=S * n_max, rows=int(np.prod(rows.shape)),
                  code_bytes=code_bytes)
    prog_args = (sub, jnp.asarray(W), jnp.asarray(rows), jnp.asarray(ws),
                 jnp.asarray(iws))
    from ..monitoring.xla_introspect import check_dispatch

    check_dispatch("sharded.impact_disjunction", fn, prog_args,
                   fields=fields)
    with time_kernel("sharded.impact_disjunction", **fields):
        v, i, t = jax.device_get(fn(*prog_args))
    return v, i, t


def _msearch_sharded_exact(ss: "StackedSearcher", fld: str,
                           queries: list, k: int = 10,
                           _return_program=False):
    """The legacy exact arm: per-shard partials + coordinator merge."""
    out = _msearch_exact_partials(ss, fld, queries, k, _return_program)
    if _return_program:
        return out
    return _merge_shard_rows(*out)


def _msearch_merged(ss: "StackedSearcher", fld: str, queries: list, k: int,
                    _return_program=False):
    """The one-program msearch route: dispatch + fetch + finish in one
    call (solo callers; the serving wave drives the stages separately
    through `msearch_wave_begin/fetch/finish`)."""
    st = _msearch_merged_begin(ss, fld, queries, k,
                               _return_program=_return_program)
    if _return_program:
        return st
    from ..telemetry import host_transition

    host_transition("dispatch")
    _msearch_merged_fetch(st)
    return _msearch_merged_finish(st)


def _msearch_merged_begin(ss: "StackedSearcher", fld: str, queries: list,
                          k: int, _return_program=False):
    """Plan + DISPATCH the pjit msearch arm (PR 10, reworked PR 11): ONE
    compiled SPMD program per plan shape — per-shard scoring bodies over
    the sharded pack pytree AND the global top-k merge (`lax.top_k` over
    the ICI all-gather of the per-shard (score, shard_doc) rows) in the
    same program. No host round-trip between shard scan and coordinator
    merge; device->host traffic is k rows per query instead of S*k.
    Arm priority matches the partials oracle: fused > impact > exact —
    the fused Pallas pipeline rides an embedded shard_map manual region
    inside the SAME compiled program (PR 11: the `ES_TPU_SPMD` arm
    matrix for the fused tier is gone).

    -> a state dict for `_msearch_merged_fetch` / `_msearch_merged_finish`
    (or the (fn, args, kk) program triple under _return_program)."""
    arm = "exact"
    if not _return_program:
        # PR 18: the one-program route's arms (same eligibility gates)
        # arbitrated by the execution planner; cold = today's static
        # priority, warm = argmin of the predicted walls
        from ..planner import execution_planner

        fs = _fused_sharded_for(ss)
        fused_ok = fs is not None and fs.usable(k)
        impact_ok = _impact_sharded_usable(ss)
        S, Q, n_max = ss.sp.S, len(queries), ss.sp.n_max
        cands = []
        if fused_ok:
            cands.append(("fused", "sharded.fused_allgather_topk",
                          {"shards": S, "queries": Q, "k": k,
                           "v": ss.sp.dense_v,
                           "num_docs": S * fs.n_pad}))
        if impact_ok:
            code_b = (int(np.dtype(ss.dev["impact_codes"].dtype).itemsize)
                      if "impact_codes" in ss.dev else 2)
            cands.append(("impact", "sharded.allgather_topk",
                          {"tier": "impact", "shards": S, "queries": Q,
                           "k": k, "num_docs": S * n_max,
                           "code_bytes": code_b}))
        cands.append(("exact", "sharded.allgather_topk",
                      {"tier": "exact", "shards": S, "queries": Q,
                       "k": k, "num_docs": S * n_max}))
        # the static order, whatever the planner's EMAs hold: each arm of
        # this route is a family of compiled programs, and the first
        # escalation of a fused wave (which hands `sharded.allgather_topk`
        # its first efficiency reading) used to flip every later wave to the
        # impact arm and its 29 programs (PERF.md section 6, PR 35)
        arm = execution_planner().choose_arm(
            "sharded.msearch_merged", cands, model=False)
        if arm == "fused":
            return fs.msearch_merged_begin(fld, queries, k)
    elif _impact_sharded_usable(ss):
        arm = "impact"
    if arm == "impact":
        out = _msearch_merged_arm_begin(ss, fld, queries, k, impact=True,
                                        _return_program=_return_program)
        if out is not None:
            return out
    return _msearch_merged_arm_begin(ss, fld, queries, k, impact=False,
                                     _return_program=_return_program)


def _msearch_merged_fetch(st: dict) -> None:
    """Pull the merged program's outputs — the lane's ONE blocking
    device round-trip. Skips cleanly when the engine's combined wave
    fetch already delivered `st["host"]`."""
    if st.get("host") is not None or st.get("pending") is None:
        return
    from ..telemetry import host_transition, time_kernel

    with time_kernel(st["kernel"], **st["fields"]):
        st["host"] = jax.device_get(st["pending"])
    host_transition("fetch")


def _msearch_merged_finish(st: dict):
    """-> (scores [Q, kk], shard [Q, kk] i32, doc [Q, kk], totals [Q])."""
    _msearch_merged_fetch(st)  # no-op when the wave fetch already ran
    return st["finish"](st)


def _merged_rows_finish(st: dict):
    mv, msh, mi, mt = st["host"]
    return (np.asarray(mv), np.asarray(msh).astype(np.int32),
            np.asarray(mi), np.asarray(mt))


def _msearch_merged_arm_begin(ss: "StackedSearcher", fld: str,
                              queries: list, k: int, *, impact: bool,
                              _return_program=False):
    from ..ops.batched import batch_term_disjunction

    sp = ss.sp
    S = sp.S
    pl = _msearch_stack_plans(ss, fld, queries, k, impact=impact)
    if pl is None:
        return None
    Q = len(queries)
    avgdl, has_norms, kk = pl["avgdl"], pl["has_norms"], pl["kk"]
    n_max = sp.n_max
    Ts, B = pl["rows"].shape[2], pl["rows"].shape[3]
    dev_keys = (("post_docids", "impact_codes", "live") if impact
                else ("post_docids", "post_tfs", "post_dls", "live"))
    sub = {key: ss.dev[key] for key in dev_keys}
    if "dense_tfn" in ss.dev:
        sub["dense_tfn"] = ss.dev["dense_tfn"]
    cache_key = ("msearch_merged", impact, fld, Ts, B, kk, Q)

    def build():
        from .spmd import (
            constrain, constrain_shards, merge_topk_rows, replica_axis,
        )

        mesh = ss.mesh
        ra = replica_axis(mesh)

        def shard_one(dev1, W1, rows1, ws1, iws1):
            return batch_term_disjunction(
                dev1, (Ts, B, kk), W1, rows1, ws1,
                avgdl=avgdl, num_docs=n_max, has_norms=has_norms,
                impact_w=(iws1 if impact else None),
            )

        def msearch_merged_exact(dev, W_, rows_, ws_, iws_):
            if ra is not None:
                # replica groups: the query axis splits over the mesh's
                # second axis, so each replica group scans the (shard-
                # local, replicated) pack for its own slice of the wave
                W_, rows_, ws_, iws_ = (
                    constrain(x, mesh, P("shards", ra))
                    for x in (W_, rows_, ws_, iws_))
            args = (dev, W_, rows_, ws_, iws_)
            if mesh is None:
                # one device: the shards one after another, so that every
                # selection inside stays rank 2. Under `vmap` they are
                # rank 3, which the TPU compiler sorts whole, and past
                # 2,048 lanes that costs ~10 s of compile each (PERF.md
                # section 6, PR 35); a mesh needs the `vmap` to shard
                per_shard = [
                    shard_one(*jax.tree_util.tree_map(lambda x: x[s_], args))
                    for s_ in range(S)]
                outs = tuple(jnp.stack(o) for o in zip(*per_shard))
            else:
                outs = jax.vmap(shard_one)(*args)
            v, i, t = constrain_shards(outs, mesh)
            with jax.named_scope("topk"):
                return merge_topk_rows(v, i, t, mesh=mesh)

        return msearch_merged_exact

    fn = _wave_program(ss._cache, "msearch_merged", cache_key, build)
    iws = pl.get("iws")
    if iws is None:
        iws = np.zeros_like(pl["ws"])
    if _return_program:
        # measurement hook (scripts/c5_mesh_probe.py): the ONE compiled
        # program + its device inputs, so the in-program merge cost can
        # be timed against the shard-local partials program
        return fn, (sub, jnp.asarray(pl["W"]), jnp.asarray(pl["rows"]),
                    jnp.asarray(pl["ws"]), jnp.asarray(iws)), kk
    from ..telemetry import profile_event

    tier = "impact" if impact else "exact"
    profile_event("tier", tier=tier, queries=Q)
    fields = dict(tier=tier, shards=S, queries=Q, k=kk,
                  num_docs=S * n_max, rows=int(np.prod(pl["rows"].shape)))
    if impact:
        fields["code_bytes"] = int(
            np.dtype(ss.dev["impact_codes"].dtype).itemsize)
    prog_args = (sub, pl["W"], pl["rows"], pl["ws"], iws)
    from ..monitoring.xla_introspect import check_dispatch

    # PR 12: the one-program scan+merge vs its own compiled cost analysis
    check_dispatch("sharded.allgather_topk", fn, prog_args, fields=fields)
    outs = _wave_launch(fn, *prog_args)
    return {"pending": outs, "host": None,
            "kernel": "sharded.allgather_topk", "fields": fields,
            "finish": _merged_rows_finish}


def global_merge_rows(ss: "StackedSearcher", v, i, t):
    """Standalone on-device coordinator merge of per-shard top rows —
    the `sharded.global_merge` program. Production arms fold the merge
    into their own compiled program (`_msearch_merged`); this entry
    point serves rows produced OUTSIDE one mergeable program (the mesh
    probe's merge-fraction measurement, tests) and returns the merged
    (scores [Q, kk], shard, doc, totals [Q]) as numpy."""
    from ..telemetry import time_kernel

    v = jnp.asarray(v)
    i = jnp.asarray(i)
    t = jnp.asarray(t)
    S, Q, kk = v.shape
    cache_key = ("global_merge", S, Q, kk)
    fn = ss._cache.get(cache_key)
    if fn is None:
        from .spmd import merge_topk_rows

        def global_merge(v_, i_, t_):
            return merge_topk_rows(v_, i_, t_, mesh=ss.mesh)

        fn = ss._cache[cache_key] = jax.jit(global_merge)
    from ..monitoring.xla_introspect import check_dispatch

    check_dispatch("sharded.global_merge", fn, (v, i, t),
                   fields={"shards": S, "queries": Q, "k": kk})
    with time_kernel("sharded.global_merge", shards=S, queries=Q, k=kk):
        mv, msh, mi, mt = jax.device_get(fn(v, i, t))
    return (np.asarray(mv), np.asarray(msh).astype(np.int32),
            np.asarray(mi), np.asarray(mt))


def _msearch_exact_partials(ss: "StackedSearcher", fld: str,
                            queries: list, k: int = 10,
                            _return_program=False):
    """Batched disjunction kernel per shard (also the escalation target of
    the fused arm's flagged queries) -> pre-merge per-shard rows
    (v [S, Q, kk], i [S, Q, kk], t [S, Q]) numpy."""
    from ..ops.batched import batch_term_disjunction

    sp = ss.sp
    S = sp.S
    pl = _msearch_stack_plans(ss, fld, queries, k)
    Q = len(queries)
    W, rows, ws = pl["W"], pl["rows"], pl["ws"]
    avgdl, has_norms, kk = pl["avgdl"], pl["has_norms"], pl["kk"]
    n_max = sp.n_max
    Ts, B = rows.shape[2], rows.shape[3]

    def shard_body(dev1, W1, rows1, ws1):
        dev = {
            "post_docids": dev1["post_docids"][0],
            "post_tfs": dev1["post_tfs"][0],
            "post_dls": dev1["post_dls"][0],
            "live": dev1["live"][0],
        }
        if "dense_tfn" in dev1:
            dev["dense_tfn"] = dev1["dense_tfn"][0]
        v, i, t = batch_term_disjunction(
            dev, (Ts, B, kk), W1[0], rows1[0], ws1[0],
            avgdl=avgdl, num_docs=n_max, has_norms=has_norms,
        )
        return v[None], i[None], t[None]

    sub = {key: ss.dev[key] for key in
           ("post_docids", "post_tfs", "post_dls", "live")}
    if "dense_tfn" in ss.dev:
        sub["dense_tfn"] = ss.dev["dense_tfn"]
    def build():
        if ss.mesh is not None:
            def msearch_exact(dev, W_, rows_, ws_):
                specs = jax.tree_util.tree_map(lambda _: P("shards"), dev)
                return shard_map(
                    shard_body, mesh=ss.mesh,
                    in_specs=(specs, P("shards"), P("shards"), P("shards")),
                    out_specs=(P("shards"), P("shards"), P("shards")),
                )(dev, W_, rows_, ws_)
        else:
            def msearch_exact(dev, W_, rows_, ws_):
                def body(d1, w1, r1, s1):
                    return shard_body(
                        jax.tree_util.tree_map(lambda x: x[None], d1),
                        w1[None], r1[None], s1[None],
                    )
                v, i, t = jax.vmap(body)(dev, W_, rows_, ws_)
                return v[:, 0], i[:, 0], t[:, 0]
        return msearch_exact

    fn = _wave_program(ss._cache, "msearch_sharded",
                       ("msearch_sharded", fld, Ts, B, kk, Q), build)
    if _return_program:
        # measurement hook (scripts/c5_mesh_probe.py): the compiled
        # program + its device inputs, so collective-merge overhead can be
        # timed against the shard-local portion on a virtual mesh
        return fn, (sub, jnp.asarray(W), jnp.asarray(rows),
                    jnp.asarray(ws)), kk
    from ..telemetry import time_kernel

    fields = dict(tier="exact", shards=S, queries=Q, k=kk,
                  num_docs=S * n_max, rows=int(np.prod(rows.shape)))
    prog_args = (sub, jnp.asarray(W), jnp.asarray(rows), jnp.asarray(ws))
    from ..monitoring.xla_introspect import check_dispatch

    check_dispatch("sharded.exact_disjunction", fn, prog_args,
                   fields=fields)
    with time_kernel("sharded.exact_disjunction", **fields):
        v, i, t = jax.device_get(fn(*prog_args))
    return v, i, t


class _PlanShardAdapter:
    """Minimal BatchTermSearcher host adapter for one shard of a stacked
    pack (planning only — execution happens in msearch_sharded's SPMD
    body, not through this object)."""

    def __init__(self, sp: StackedPack, s: int, ss: "StackedSearcher"):
        self.pack = sp.shard_view(s)
        self.ctx = ss.ctx
        self.dev = {}


def _fused_sharded_for(ss: "StackedSearcher"):
    """Cached fused-msearch arm for a StackedSearcher, or None when the
    pack shape can never qualify (no dense tier / no pallas)."""
    from ..ops import fused as F

    if F.fused_enabled() == "0":
        return None
    if getattr(ss.sp, "dense_tf", None) is None or "dense_tfn" not in ss.dev:
        return None
    fs = getattr(ss, "_fused_msearch", None)
    if fs is None:
        fs = ss._fused_msearch = _FusedShardedMsearch(ss)
    return fs


class _FusedShardedMsearch:
    """C5 `_msearch` through the fused kernel, one pipeline per shard.

    The same `ops/fused._fused_pipeline` program that serves single-shard
    C1 runs as the per-shard body here: the in-kernel dense matmul +
    per-tile top-t + one-hot sparse scatter + canonical f32 rescore
    (lax.scan over QC-query chunks). Two routes share that body:

      * `msearch_merged_begin` (PR 11, the production pjit route) — the
        body runs inside an embedded shard_map manual region of ONE
        compiled SPMD program that also performs the on-device
        all-gather top-k merge; the host fetches k merged rows + one
        escalation bool per query.
      * `msearch` / `msearch_partials` (the shard_map oracle) — [S, Q, k]
        partials fetched and merged by the host coordinator in
        (score desc, shard asc, doc asc) order; kept as the parity
        fixture and the per-shard-cache execution arm of the legacy
        execution models.

    Queries flagged by ANY shard (window overflow, tile saturation,
    margin test) re-run on the exact arm, so results never depend on
    the fused pass — the same escalation contract as FusedTermSearcher."""

    def __init__(self, ss: "StackedSearcher"):
        from ..ops import fused as F

        self.ss = ss
        sp = ss.sp
        self.S = sp.S
        V = sp.dense_v
        # geometry snapshot (one per searcher — see FusedTermSearcher)
        self._qsub = F._cfg_qsub()
        self._tile_n = F._cfg_tile()
        self._t_env = int(os.environ.get("ES_TPU_FUSED_T", 0))
        self._vp2 = -(-2 * V // 128) * 128
        if (F.fused_topk_enabled() and V
                and os.environ.get("ES_TPU_FUSED_TILE") is None):
            self._tile_n = min(
                self._tile_n, F.auto_tile_matmul(self._vp2, self._qsub))
        self.n_max = sp.n_max
        self.n_pad = -(-max(sp.n_max, 1) // self._tile_n) * self._tile_n
        # the sharded arm runs stacked-tier-only (one resident layout per
        # chip); a stack too large for its chip disqualifies the arm
        self._use_stack = (
            os.environ.get("ES_TPU_FUSED_STACK", "1") != "0"
            and self._vp2 * self.n_pad * 2 <= 6 * 1024**3
        )
        self._inkernel = F.fused_topk_enabled() and self._use_stack
        self._fa = None
        self._fa_live_of = None
        self._fa_tier_of = None
        self._cache: dict = {}

    def usable(self, k: int) -> bool:
        from ..ops import fused as F

        mode = F.fused_enabled()
        if not (0 < k <= 16) or not self._use_stack:
            return False
        if self.n_max > F.MAX_DOCS_FUSED or self.n_max < 1:
            return False
        if mode == "force":
            return True
        return (jax.default_backend() == "tpu"
                and self.n_max >= 4 * F.FINE_N)

    def _arrays(self):
        from ..ops import fused as F

        dev = self.ss.dev
        if self._fa is None or self._fa_tier_of is not dev["dense_tfn"]:
            padw = self.n_pad - self.n_max
            rpad = self._vp2 - 2 * self.ss.sp.dense_v

            @jax.jit
            def split(t):  # [S, V, n_max] scored tfn -> [S, vp2, n_pad]
                tp = jnp.pad(t, ((0, 0), (0, 0), (0, padw)))
                hif = F._mask_hi(tp)
                hi = hif.astype(jnp.bfloat16)
                lo = (tp - hif).astype(jnp.bfloat16)
                st = jnp.concatenate([hi, lo], axis=1)
                return jnp.pad(st, ((0, 0), (0, rpad), (0, 0)))

            self._fa = {
                "tier32": dev["dense_tfn"],
                "post_docids": dev["post_docids"],
                "post_tfs": dev["post_tfs"],
                "post_dls": dev["post_dls"],
                "tier16_stack": split(dev["dense_tfn"]),
            }
            self._fa_tier_of = dev["dense_tfn"]
            self._fa_live_of = None  # force the live rebuild below
        if self._fa_live_of is not dev["live"]:
            padw = self.n_pad - self.n_max
            self._fa["live"] = jnp.pad(
                dev["live"].astype(jnp.float32), ((0, 0), (0, padw))
            )[:, None, :]
            self._fa_live_of = dev["live"]
        return self._fa

    def _geom(self, R):
        """Shared kernel geometry of one fused batch: (bud, tile_n,
        qsub, t) — window budget from the plan's block rows R, which
        `plan_fused` pads to a power of two from 64, so that (R, Td)
        alone name a program (see FusedTermSearcher._compiled_scan)."""
        from ..index.pack import BLOCK
        from ..ops import fused as F

        tile_n, qsub = self._tile_n, self._qsub
        njc = self.n_pad // tile_n
        t = self._t_env if self._t_env > 0 else F.tile_t_for(njc)
        mean_win = max(1, R * BLOCK // ((F.QC // qsub) * njc))
        bude = min(
            64 * 1024, max(2048, 1 << (2 * mean_win - 1).bit_length())
        )
        return bude // 128, tile_n, qsub, t

    def _compiled(self, fld, C, R, Td, k, interpret):
        from ..ops import fused as F

        bud, tile_n, qsub, t = self._geom(R)
        key = (fld, C, R, Td, k, interpret, bud, tile_n, qsub, t,
               self._inkernel, self.ss.mesh is None)
        fn = self._cache.get(key)
        from ..monitoring.device import note_executable_cache

        note_executable_cache("sharded_fused", fn is not None)
        if fn is not None:
            return fn
        kw = dict(
            k=k, n=self.n_max, n_pad=self.n_pad,
            has_norms=fld in self.ss.ctx.has_norms,
            k1=1.2, b=0.75,
            bud=bud, t=t, tile_n=tile_n, qsub=qsub,
            interpret=interpret, inkernel=self._inkernel,
        )

        def shard_scan(fa1, avgdl, rows, row_q, row_w, dr, dw):
            def body(carry, xs):
                return carry, F._fused_pipeline(fa1, avgdl, *xs, **kw)

            _, outs = jax.lax.scan(body, 0, (rows, row_q, row_w, dr, dw))
            return outs

        from .spmd import manual_shard_region

        region = manual_shard_region(
            shard_scan, self.ss.mesh,
            in_specs=(P("shards"), P()) + (P("shards"),) * 5)

        def fused_pipeline(fa, avgdl, rows, row_q, row_w, dr, dw):
            return region(fa, avgdl, rows, row_q, row_w, dr, dw)

        fn = self._cache[key] = jax.jit(fused_pipeline)
        return fn

    def _compiled_merged(self, fld, C, R, Td, k, interpret):
        """ONE compiled SPMD program (PR 11, ROADMAP item 1): the
        per-shard fused Pallas pipeline runs inside an embedded
        shard_map manual region — custom calls cannot be GSPMD-
        partitioned, but a manual region never asks the partitioner —
        and its sharded [S, C·qc, k] rows feed the on-device all-gather
        top-k merge in the SAME program. The per-query escalation flag
        is OR'd across shards in-program too, so the host fetches
        merged k-rows + one bool per query: no more fused-tier fork off
        the one-program route, no S·k-row fetch, no host merge."""
        bud, tile_n, qsub, t = self._geom(R)
        key = ("merged", fld, C, R, Td, k, interpret, bud, tile_n, qsub,
               t, self._inkernel, self.ss.mesh is None)
        return _wave_program(
            self._cache, "sharded_fused", key,
            lambda: self._build_merged(fld, k, bud, tile_n, qsub, t,
                                       interpret))

    def _build_merged(self, fld, k, bud, tile_n, qsub, t, interpret):
        from ..ops import fused as F

        kw = dict(
            k=k, n=self.n_max, n_pad=self.n_pad,
            has_norms=fld in self.ss.ctx.has_norms,
            k1=1.2, b=0.75,
            bud=bud, t=t, tile_n=tile_n, qsub=qsub,
            interpret=interpret, inkernel=self._inkernel,
        )

        def shard_scan(fa1, avgdl, rows, row_q, row_w, dr, dw):
            def body(carry, xs):
                with jax.named_scope("score"):
                    return carry, F._fused_pipeline(fa1, avgdl, *xs, **kw)

            _, outs = jax.lax.scan(body, 0, (rows, row_q, row_w, dr, dw))
            return outs

        from .spmd import constrain_shards, manual_shard_region, \
            merge_topk_rows

        mesh = self.ss.mesh
        region = manual_shard_region(
            shard_scan, mesh,
            in_specs=(P("shards"), P()) + (P("shards"),) * 5)

        def fused_pipeline_merged(fa, avgdl, rows, row_q, row_w, dr, dw):
            v, i, tot, fl = region(fa, avgdl, rows, row_q, row_w, dr, dw)
            S_, C_, qc, kk = v.shape
            v2, i2, t2 = constrain_shards(
                (v.reshape(S_, C_ * qc, kk), i.reshape(S_, C_ * qc, kk),
                 tot.reshape(S_, C_ * qc)), mesh)
            with jax.named_scope("topk"):
                mv, msh, mi, mt = merge_topk_rows(v2, i2, t2, mesh=mesh)
            flags = jnp.any(fl.reshape(S_, C_ * qc), axis=0)
            return mv, msh, mi, mt, flags

        return fused_pipeline_merged

    def msearch(self, fld, queries, k):
        """Shard_map oracle route: per-shard partials + host merge —
        kept for the legacy execution model and parity fixtures; the
        production pjit route is `msearch_merged_begin`."""
        return _merge_shard_rows(*self.msearch_partials(fld, queries, k))

    def msearch_merged(self, fld, queries, k):
        """The one-program fused msearch, begin+fetch+finish in one call
        (tests/probes; the serving wave drives the stages separately)."""
        st = self.msearch_merged_begin(fld, queries, k)
        _msearch_merged_fetch(st)
        return st["finish"](st)

    def msearch_merged_begin(self, fld, queries, k) -> dict:
        """Plan + DISPATCH the fused one-program route (no fetch)."""
        from ..telemetry import profile_event

        idxs, pb = self._plan_batch(fld, queries, k)
        interpret = jax.default_backend() != "tpu"
        fn = self._compiled_merged(fld, pb["C"], pb["R"], pb["Td"], k,
                                   interpret)
        outs = _wave_launch(fn, self._arrays(), pb["avgdl"], pb["rows"],
                            pb["row_q"], pb["row_w"], pb["dr"], pb["dw"])
        Q = len(queries)
        profile_event("tier", tier="fused", queries=Q)
        fields = dict(tier="fused", shards=self.S, queries=Q, k=k,
                      v=self.ss.sp.dense_v, num_docs=self.S * self.n_pad)
        return {"pending": outs, "host": None,
                "kernel": "sharded.fused_allgather_topk", "fields": fields,
                "finish": self._merged_finish,
                "idxs": idxs, "queries": queries, "fld": fld, "k": k}

    def _merged_finish(self, st: dict):
        """Fetched merged outputs -> (scores [Q, k], shard, doc, totals);
        flagged queries re-run on the exact merged arm (the escalation
        contract of the oracle route, at merged-row granularity)."""
        from ..ops import fused as F

        mv, msh, mi, mt, fl = [np.asarray(x) for x in st["host"]]
        queries, k, fld = st["queries"], st["k"], st["fld"]
        idxs = st["idxs"]
        Q = len(queries)
        kk = mv.shape[-1]
        qc = F.QC
        scores = np.full((Q, kk), -np.inf, np.float32)
        shards = np.zeros((Q, kk), np.int32)
        ids = np.zeros((Q, kk), np.int64)
        totals = np.zeros((Q,), np.int64)
        flagged = np.zeros((Q,), bool)
        for ci, qidx in enumerate(idxs):
            nq = len(qidx)
            base = ci * qc
            scores[qidx] = mv[base:base + nq]
            shards[qidx] = msh[base:base + nq]
            ids[qidx] = mi[base:base + nq]
            totals[qidx] = mt[base:base + nq]
            flagged[qidx] = fl[base:base + nq]
        if flagged.any():
            from ..telemetry import host_transition, profile_event

            still = np.nonzero(flagged)[0]
            profile_event("tier", tier="exact_escalation",
                          queries=int(still.shape[0]))
            # padded to its batch tier like the wave it came from: the
            # flagged count follows arrival order, and as a program's Q
            # it would mint one for every count met
            padded, _tier = _pad_to_wave_tier(
                [queries[i_] for i_ in still], floor=ESCALATION_MIN_TIER)
            st_ex = _msearch_merged_arm_begin(
                self.ss, fld, padded, k, impact=False)
            host_transition("dispatch")
            _msearch_merged_fetch(st_ex)
            ev, esh, ei, et = (a[:still.shape[0]]
                               for a in _merged_rows_finish(st_ex))
            ke = min(ev.shape[1], kk)
            scores[still, :] = -np.inf
            scores[still, :ke] = ev[:, :ke]
            shards[still, :] = 0
            shards[still, :ke] = esh[:, :ke]
            ids[still, :] = 0
            ids[still, :ke] = ei[:, :ke]
            totals[still] = et
            st["extra_dispatches"] = st.get("extra_dispatches", 0) + 1
            st["extra_fetches"] = st.get("extra_fetches", 0) + 1
        return scores, shards, ids, totals

    def _plan_batch(self, fld, queries, k):
        """Host planning shared by the oracle and merged routes: per-
        shard per-chunk fused plans padded to one (R, Td) envelope.
        -> (chunk idxs, dict of stacked [S, C, ...] arrays + shapes)."""
        from ..ops import fused as F

        sp = self.ss.sp
        S = self.S
        Q = len(queries)
        qc = F.QC
        idxs = [np.arange(s0, min(s0 + qc, Q)) for s0 in range(0, Q, qc)]
        views = [sp.shard_view(s) for s in range(S)]
        plans = [
            [F.plan_fused(v, fld, [queries[i] for i in qidx], k, qc=qc)
             for qidx in idxs]
            for v in views
        ]  # [S][C]
        from ..ops.batched import BatchTermSearcher

        C = len(idxs)
        # on the waves' ladders, coarser than the plans' own powers of two:
        # (R, Td) name the program, and which queries share a chunk follows
        # arrival order (a padded row is the all-padding block 0 with weight
        # 0, a padded dense term weighs 0)
        R = BatchTermSearcher.wave_r_tier(
            max(p.rows.shape[0] for ps in plans for p in ps))
        Td = BatchTermSearcher.wave_td_tier(
            max(p.dense_rows.shape[1] for ps in plans for p in ps))

        def _padr(a, width):
            return np.pad(
                a, [(0, width - a.shape[0])] + [(0, 0)] * (a.ndim - 1))

        return idxs, {
            "rows": np.stack([[_padr(p.rows, R) for p in ps]
                              for ps in plans]),
            "row_q": np.stack([[_padr(p.row_q, R) for p in ps]
                               for ps in plans]),
            "row_w": np.stack([[_padr(p.row_w, R) for p in ps]
                               for ps in plans]),
            "dr": np.stack([
                [np.pad(p.dense_rows,
                        ((0, 0), (0, Td - p.dense_rows.shape[1])))
                 for p in ps] for ps in plans]),
            "dw": np.stack([
                [np.pad(p.dense_w, ((0, 0), (0, Td - p.dense_w.shape[1])))
                 for p in ps] for ps in plans]),
            "avgdl": np.float32(views[0].avgdl(fld)),
            "C": C, "R": R, "Td": Td,
        }

    def msearch_partials(self, fld, queries, k):
        """Pre-merge per-shard rows (scores [S, Q, kk], ids, totals
        [S, Q]); queries flagged by ANY shard have their per-shard rows
        replaced by the exact arm's partials, so the merge (and any cached
        per-shard entry) never depends on the fused pass."""
        ss = self.ss
        sp = ss.sp
        S = self.S
        Q = len(queries)
        idxs, pb = self._plan_batch(fld, queries, k)
        interpret = jax.default_backend() != "tpu"
        fn = self._compiled(fld, pb["C"], pb["R"], pb["Td"], k, interpret)
        from ..telemetry import profile_event, time_kernel

        profile_event("tier", tier="fused", queries=Q)
        with time_kernel("sharded.fused_pipeline", tier="fused", shards=S,
                         queries=Q, k=k, v=sp.dense_v,
                         num_docs=S * self.n_pad):
            v, i, t, fl = jax.device_get(
                fn(self._arrays(), pb["avgdl"], pb["rows"], pb["row_q"],
                   pb["row_w"], pb["dr"], pb["dw"]))
        # [S, C, qc, ...] -> per-shard [S, Q, ...]
        kk = v.shape[-1]
        scores = np.full((S, Q, kk), -np.inf, np.float32)
        ids = np.zeros((S, Q, kk), np.int64)
        totals = np.zeros((S, Q), np.int64)
        flagged = np.zeros((Q,), bool)
        for ci, qidx in enumerate(idxs):
            nq = len(qidx)
            scores[:, qidx] = v[:, ci, :nq]
            ids[:, qidx] = i[:, ci, :nq]
            totals[:, qidx] = t[:, ci, :nq]
            flagged[qidx] |= fl[:, ci, :nq].any(axis=0)
        if flagged.any():
            # escalation at per-shard granularity: the exact arm's
            # pre-merge rows REPLACE the fused rows for flagged queries,
            # so downstream consumers (merge, per-shard cache entries)
            # see only exact data for them
            still = np.nonzero(flagged)[0]
            profile_event("tier", tier="exact_escalation",
                          queries=int(still.shape[0]))
            ev, ei, et = _msearch_exact_partials(
                self.ss, fld, [queries[i_] for i_ in still], k)
            ke = ev.shape[2]
            scores[:, still, :] = -np.inf
            scores[:, still, :ke] = ev
            ids[:, still, :] = 0
            ids[:, still, :ke] = ei
            totals[:, still] = et
        return scores, ids, totals
