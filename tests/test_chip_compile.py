"""Compile the served lexical path's kernels for a described TPU v5e at
the 1M-doc C1 width, with no chip attached (on-chip-measurement guide,
section 2, rehearsal 3): what the chip's compiler refuses surfaces here
at no chip time. Nothing runs, so these say nothing about results or
times — a pass is not a chip run.

Everything built from the topology lives in fixtures/tests (only one
process may load libtpu; under xdist only the worker given this file
does), and the persistent compile cache is off around the compiles (an
AOT entry written here cannot be read back without a chip).
"""

import functools
import os
import re
import types

import numpy as np
import pytest

N_DOCS = 1_000_000   # bench.py C1 (chip_smoke.py --docs 1000000)
V_DENSE = 896        # dense-tier rows at that corpus (bench.py preflight)
N_BLOCKS = 400_000   # ~40M postings / 128 lanes, plus per-term padding
TOP_K = 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]), ("shards",))


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from elasticsearch_tpu.utils.jax_env import ensure_x64

    ensure_x64()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fused_geometry(n_docs):
    """The geometry FusedTermSearcher / _FusedShardedMsearch derive for a
    pack of n_docs with the C1 dense tier (bench.py preflight recipe)."""
    from elasticsearch_tpu.ops import fused as F

    qsub = F._cfg_qsub()
    vp2 = -(-2 * V_DENSE // 128) * 128
    tile_n = min(F._cfg_tile(), F.auto_tile_matmul(vp2, qsub))
    n_pad = -(-n_docs // tile_n) * tile_n
    return qsub, vp2, tile_n, n_pad


@pytest.mark.parametrize("bud", [16, 512])
def test_fused_tile_candidates_compiles(one_chip, no_persistent_cache, bud):
    import jax.numpy as jnp

    from elasticsearch_tpu.ops import fused as F

    qsub, vp2, tile_n, n_pad = _fused_geometry(N_DOCS)
    assert (qsub, vp2, tile_n) == (256, 1792, 4096)
    njc, njf = n_pad // tile_n, n_pad // F.FINE_N
    rows = 8 * bud
    compiled = F.fused_tile_candidates.lower(
        None,
        _sds((1, n_pad), jnp.float32, one_chip),
        _sds((rows, 128), jnp.int32, one_chip),
        _sds((rows, 128), jnp.int32, one_chip),
        _sds(((F.QC // qsub) * (njf + 1),), jnp.int32, one_chip),
        w=_sds((F.QC, vp2), jnp.bfloat16, one_chip),
        tstack=_sds((vp2, n_pad), jnp.bfloat16, one_chip),
        t=F.tile_t_for(njc), bud=bud, tile_n=tile_n, qsub=qsub,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _assert_one_replicated_buffer_of_words(compiled, words):
    """PR 32: the program hands its whole result back as ONE `int32[words]`
    (on a mesh every chip holds it), and the chip is asked for no 64-bit
    bitcast on the way."""
    import jax

    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, np.dtype(o.dtype)) for o in outs] == [
        ((words,), np.dtype(np.int32))]
    assert all(s.is_fully_replicated for s in
               jax.tree_util.tree_leaves(compiled.output_shardings))
    text = compiled.as_text()
    entry = [ln for ln in text.splitlines() if ln.startswith("ENTRY ")]
    assert len(entry) == 1 and re.search(
        rf"-> \(?s32\[{words}\]", entry[0]), entry
    assert "s64" not in text and "f64" not in text


def _assert_block_select_in(text, n):
    """The compiled program selects in two levels and calls no Mosaic
    kernel: one selection over the K chosen blocks, and no sort of a whole
    row of n (what a rank-1 `lax.top_k` over the row compiles to)."""
    from elasticsearch_tpu.ops.scoring import SELECT_BLOCK

    assert "tpu_custom_call" not in text
    assert f"{TOP_K * SELECT_BLOCK}]" in text, "no K x W candidate row"
    whole_row = re.compile(rf"= \(f32\[(1,)*{n}\].* sort\(")
    assert not [ln for ln in text.splitlines() if whole_row.search(ln)]


def test_top_k_with_total_compiles_under_vmap(one_chip, no_persistent_cache):
    """ops/scoring.top_k_with_total as the one-shard server runs it: inside
    the vmapped shard body (S=1), at the 1M-doc width."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.scoring import top_k_with_total

    def shard_body(scores, match, live):
        return top_k_with_total(scores, match, live, TOP_K)

    compiled = jax.jit(jax.vmap(shard_body)).lower(
        _sds((1, N_DOCS + 1), jnp.float32, one_chip),
        _sds((1, N_DOCS + 1), jnp.bool_, one_chip),
        _sds((1, N_DOCS), jnp.bool_, one_chip),
    ).compile()
    _assert_block_select_in(compiled.as_text(), N_DOCS)


def test_impact_gather_pallas_compiles(one_chip, no_persistent_cache):
    """The impact tier's scalar-prefetch gather, which `auto` selects on
    tpu only (ops/scoring.impact_enabled), at a 64-query x 32-row wave."""
    import jax.numpy as jnp

    from elasticsearch_tpu.index.pack import BLOCK
    from elasticsearch_tpu.ops.kernels import (
        _IMPACT_G, _impact_gather_pallas,
    )

    compiled = _impact_gather_pallas.lower(
        _sds((N_BLOCKS, BLOCK), jnp.uint16, one_chip),
        _sds((N_BLOCKS, BLOCK), jnp.int32, one_chip),
        _sds((64, 32), jnp.int32, one_chip),
        _sds((64, 32), jnp.float32, one_chip),
        g=_IMPACT_G, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _match_plan(S, td=8, tr=32):
    """A stacked plan of the match family (query/nodes.match_params):
    rows, their weights and impact scales; dense rows, weights and flags;
    threshold, boost and avgdl."""
    f32, i32 = np.float32, np.int32
    return (np.zeros((S, tr), i32), np.ones((S, tr), f32),
            np.ones((S, tr), f32), np.zeros((S, td), i32),
            np.ones((S, td), f32), np.ones((S, td), i32), np.ones((S,), i32),
            np.ones((S,), f32), np.ones((S,), f32))


@pytest.mark.parametrize("td, tr", [(0, 8), (8, 32), (16, 128)])
def test_packed_parameters_feed_the_solo_scoring_and_scan(
        td, tr, one_chip, no_persistent_cache):
    """PR 27: the solo program takes a `match`'s parameters as one
    int32[S, W] buffer; its slices (and 32-bit bitcasts) feed the dense
    rows' indices, the impact tier's gather and the two-level selection.
    PR 38: the parameters are a member of the match family's (its
    smallest, a core one and the one beyond the core), scored by
    `match_scores`."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.index.pack import BLOCK
    from elasticsearch_tpu.ops.scoring import match_scores, top_k_with_total
    from elasticsearch_tpu.parallel.param_pack import (pack, pack_outputs,
                                                       unpack)

    buffers, layout = pack(_match_plan(1, td, tr))
    width = 3 * tr + 3 * td + 3
    assert [(b.shape, b.dtype) for b in buffers] == [((1, width),
                                                      np.dtype(np.int32))]

    def shard_body(dense_tfn, codes, docids, live, params):
        dev = {"dense_tfn": dense_tfn, "impact_codes": codes,
               "post_docids": docids}
        scores, match = match_scores(dev, params, N_DOCS, 1.2, 0.75, True,
                                     impact=True)
        return top_k_with_total(scores, match, live, TOP_K)

    def solo(dense_tfn, codes, docids, live, buffers):
        ts, ti, tot = jax.vmap(shard_body)(dense_tfn, codes, docids, live,
                                           unpack(buffers, layout))
        # PR 32: the merge's rows and the total leave as one buffer
        g_scores, g_idx = jax.lax.top_k(ts.reshape(-1), TOP_K)
        return pack_outputs((g_scores, (g_idx // TOP_K).astype(jnp.int32),
                             ti.reshape(-1)[g_idx],
                             tot.sum(dtype=jnp.int32), {}))[0]

    compiled = jax.jit(solo).lower(
        _sds((1, V_DENSE, N_DOCS), jnp.float32, one_chip),
        _sds((1, N_BLOCKS, BLOCK), jnp.uint16, one_chip),
        _sds((1, N_BLOCKS, BLOCK), jnp.int32, one_chip),
        _sds((1, N_DOCS), jnp.bool_, one_chip),
        tuple(_sds(b.shape, b.dtype, one_chip) for b in buffers),
    ).compile()
    _assert_block_select_in(compiled.as_text(), N_DOCS)
    # no 64-bit bitcast is ever asked of the chip, on the way in or out
    _assert_one_replicated_buffer_of_words(compiled, 3 * TOP_K + 1)


def _whole_leaf_copies(text, nb):
    """Instructions that copy a whole [nb, 128] pack leaf (with or without
    its leading shard axis): a plain `copy` (what a relayout compiles to,
    inside the fusion that calls it as well). A `copy-start`/`copy-done`
    pair is the cross-program prefetch the one-chip program holds too."""
    leaf = re.compile(rf"= (s32|u16)\[(1,)?{nb},128\]\S* copy\(")
    return [ln.strip()[:160] for ln in text.splitlines() if leaf.search(ln)]


@pytest.mark.parametrize("td, tr", [(0, 8), (8, 32), (16, 128)])
def test_solo_search_compiles_on_four_chips_at_the_four_shard_cells_size(
        td, tr, mesh4, no_persistent_cache):
    """PR 29: the program of `passage-4chip.solo.c8`, one `match` over four
    shards of 294,912 documents, one a chip: the packed `int32[4, W]`
    parameters, the impact tier's gather and `top_k_with_total` (plain XLA,
    so what compiles here is what the chip runs) inside
    `manual_shard_region`, under the same `vmap` as on one chip (the row is
    rank 2; a rank-1 `lax.top_k` over it would be a stable sort of 294,912
    pairs); then the replication constraint that gathers the shards' rows,
    and the global top-k, packed into one replicated buffer (PR 32):
    `_compiled`'s shape, built from the same pieces, for members of the
    match family (PR 38). PR 39: a block count that is not a multiple of 8
    costs no relayout of the whole `post_docids` leaf a search."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticsearch_tpu.index.pack import BLOCK
    from elasticsearch_tpu.ops.scoring import match_scores, top_k_with_total
    from elasticsearch_tpu.parallel.param_pack import (pack, pack_outputs,
                                                       unpack)
    from elasticsearch_tpu.parallel.spmd import (constrain, constrain_shards,
                                                 manual_shard_region)

    S, n = 4, 294_912
    nb = N_BLOCKS // 3          # ~1/3 of the 1M-doc pack's blocks a shard
    assert nb % 8, "the block count whose layout the compiler tiles (1,128)"
    buffers, layout = pack(_match_plan(S, td, tr))
    width = 3 * tr + 3 * td + 3
    assert [(b.shape, b.dtype) for b in buffers] == [((S, width),
                                                      np.dtype(np.int32))]

    def shard_body(dev1, params):
        with jax.named_scope("score"):
            scores, match = match_scores(
                {"dense_tfn": dev1["dense_tfn"], "impact_codes": dev1["codes"],
                 "post_docids": dev1["docids"]}, params, n, 1.2, 0.75, True,
                impact=True)
        with jax.named_scope("topk"):
            return top_k_with_total(scores, match, dev1["live"], TOP_K)

    region = manual_shard_region(shard_body, mesh4,
                                 in_specs=(P("shards"), P("shards")))

    def search_solo(dev, buffers):
        ts, ti, tot = constrain_shards(region(dev, unpack(buffers, layout)),
                                       mesh4)
        with jax.named_scope("topk"):
            flat = constrain(ts.reshape(-1), mesh4, P())
            flat_i = constrain(ti.reshape(-1), mesh4, P())
            g_scores, g_idx = jax.lax.top_k(flat, TOP_K)
        outs, _ = pack_outputs((g_scores, (g_idx // TOP_K).astype(jnp.int32),
                                flat_i[g_idx], tot.sum(dtype=jnp.int32), {}))
        return tuple(constrain(b, mesh4, P()) for b in outs)

    sharded = NamedSharding(mesh4, P("shards"))
    dev = {"dense_tfn": _sds((S, V_DENSE, n), jnp.float32, sharded),
           "codes": _sds((S, nb, BLOCK), jnp.uint16, sharded),
           "docids": _sds((S, nb, BLOCK), jnp.int32, sharded),
           "live": _sds((S, n), jnp.bool_, sharded)}
    # the parameters arrive as one host array: no sharding of their own
    compiled = jax.jit(search_solo).lower(
        dev, tuple(jax.ShapeDtypeStruct(b.shape, b.dtype) for b in buffers),
    ).compile()
    text = compiled.as_text()
    _assert_block_select_in(text, n)
    # the shards' rows reach every chip: an all-gather, or (the compiler's
    # choice here) each chip's K rows in a zeroed [S x K] buffer, summed
    # (the ids' rows may share one all-reduce with the total, a tuple)
    gathered = " ".join(ln.split(" all-")[0] for ln in text.splitlines()
                        if re.search(r" all-(gather|reduce)\(", ln))
    for rows in ("f32", "s32"):
        assert re.search(rf"{rows}\[({S * TOP_K}|{S},(1,)?{TOP_K})\]",
                         gathered), f"the shards' {rows} rows are not gathered"
    # each chip is handed its own row of the packed parameters
    assert f"s32[1,{width}]" in text
    # and reads the rows of its leaves it gathers, not a relaid copy of them
    assert not _whole_leaf_copies(text, nb)
    # and every chip holds the one buffer the host fetches from one of them
    _assert_one_replicated_buffer_of_words(compiled, 3 * TOP_K + 1)


def test_sharded_fused_region_compiles_on_four_chips(mesh4,
                                                     no_persistent_cache):
    """The one-program fused `_msearch` of a 4-shard index: the Pallas
    pipeline inside the embedded shard_map region + the on-device
    all-gather top-k merge (parallel/sharded._compiled_merged), on a
    Mesh of the four described devices, 250k docs per shard."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticsearch_tpu.index.pack import BLOCK
    from elasticsearch_tpu.ops import fused as F
    from elasticsearch_tpu.parallel.sharded import _FusedShardedMsearch

    S = 4
    n_max = N_DOCS // S
    ss = types.SimpleNamespace(
        sp=types.SimpleNamespace(S=S, dense_v=V_DENSE, n_max=n_max),
        mesh=mesh4,
        ctx=types.SimpleNamespace(has_norms=frozenset({"body"})),
    )
    fs = _FusedShardedMsearch(ss)
    assert fs._inkernel and fs.n_pad % fs._tile_n == 0
    sharded = NamedSharding(mesh4, P("shards"))
    replicated = NamedSharding(mesh4, P())
    nb = N_BLOCKS // S
    fa = {
        "tier32": _sds((S, V_DENSE, n_max), jnp.float32, sharded),
        "post_docids": _sds((S, nb, BLOCK), jnp.int32, sharded),
        "post_tfs": _sds((S, nb, BLOCK), jnp.float32, sharded),
        "post_dls": _sds((S, nb, BLOCK), jnp.float32, sharded),
        "tier16_stack": _sds((S, fs._vp2, fs.n_pad), jnp.bfloat16, sharded),
        "live": _sds((S, 1, fs.n_pad), jnp.float32, sharded),
    }
    C, R, Td = 1, 4096, 4
    fn = fs._compiled_merged("body", C, R, Td, TOP_K, False)
    compiled = fn.lower(
        fa, _sds((), jnp.float32, replicated),
        _sds((S, C, R), jnp.int32, sharded),
        _sds((S, C, R), jnp.int32, sharded),
        _sds((S, C, R), jnp.float32, sharded),
        _sds((S, C, F.QC, Td), jnp.int32, sharded),
        _sds((S, C, F.QC, Td), jnp.float32, sharded),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_the_exact_wave_program_compiles_without_f64_or_a_whole_row_sort(
        one_chip, no_persistent_cache, monkeypatch):
    """The merged exact arm (the fused arm's escalation) at the serving
    cell's width, 294,912 passages on one chip, at the batch tier the
    escalation pads to: no f64 (the prefix sum that was 147 s of a 178 s
    compile, PR 35), and no sort over a whole row of documents (a
    `lax.top_k` of a rank-3 operand): the only sort left is the candidates'."""
    import jax.numpy as jnp

    from elasticsearch_tpu.index.pack import BLOCK
    from elasticsearch_tpu.parallel import sharded

    n, v_dense, nb = 294_912, 1152, 500_000
    ts, b, q = 4, 8, sharded.ESCALATION_MIN_TIER
    monkeypatch.setattr(sharded, "_msearch_stack_plans", lambda *a, **k: {
        "W": np.zeros((1, q, v_dense), np.float32),
        "rows": np.zeros((1, q, ts, b), np.int32),
        "ws": np.zeros((1, q, ts), np.float32),
        "avgdl": 56.0, "has_norms": True, "kk": TOP_K})
    ss = types.SimpleNamespace(
        sp=types.SimpleNamespace(S=1, n_max=n), mesh=None, _cache={},
        dev=dict.fromkeys(("post_docids", "post_tfs", "post_dls", "live",
                           "dense_tfn")))
    fn, _args, kk = sharded._msearch_merged_arm_begin(
        ss, "body", [[]] * q, TOP_K, impact=False, _return_program=True)
    dev = {"post_docids": _sds((1, nb, BLOCK), jnp.int32, one_chip),
           "post_tfs": _sds((1, nb, BLOCK), jnp.float32, one_chip),
           "post_dls": _sds((1, nb, BLOCK), jnp.float32, one_chip),
           "live": _sds((1, n), jnp.bool_, one_chip),
           "dense_tfn": _sds((1, v_dense, n), jnp.float32, one_chip)}
    text = fn.lower(
        dev, _sds((1, q, v_dense), jnp.float32, one_chip),
        _sds((1, q, ts, b), jnp.int32, one_chip),
        _sds((1, q, ts), jnp.float32, one_chip),
        _sds((1, q, ts), jnp.float32, one_chip)).compile().as_text()
    assert not re.search(r"\bf64\[", text)
    sorted_widths = {int(w) for w in re.findall(
        r"= \(?[a-z0-9]+\[\d+,(\d+)\][^=]* sort\(", text)}
    assert sorted_widths and max(sorted_widths) <= ts * b * BLOCK, sorted_widths


def test_device_build_kernels_compile_at_one_bulk_burst(
        one_chip, no_persistent_cache):
    """index/device_build's jitted builders at one 5,000-document burst
    (Poisson(40) tokens of ~6 chars): the analyze hash kernel, the CSR
    blocked scatter and the impact quantization pass — the write path
    `auto` takes on any backend that is not cpu."""
    import jax.numpy as jnp

    from elasticsearch_tpu.index import device_build as db
    from elasticsearch_tpu.index.pack import BLOCK

    docs = 5_000
    bp, lp = db._pow2_pad(docs, floor=8), db._pow2_pad(420, floor=64)
    db._analyze_hash_jit().lower(
        _sds((bp, lp), jnp.uint8, one_chip),
        _sds((bp,), jnp.int32, one_chip),
    ).compile()
    lanes = db._pow2_pad(docs * 40)
    blocks = lanes // BLOCK + 20_000  # one partial block per distinct term
    flat = functools.partial(_sds, (lanes,), sharding=one_chip)
    db._csr_scatter_jit().lower(
        flat(jnp.int32), flat(jnp.float32), flat(jnp.float32),
        flat(jnp.int32), flat(jnp.int32),
        total_blocks=blocks, block=BLOCK, n_sentinel=docs,
    ).compile()
    row = functools.partial(_sds, (blocks,), jnp.float32, one_chip)
    db._impact_codes_jit().lower(
        _sds((blocks, BLOCK), jnp.float32, one_chip),
        _sds((blocks, BLOCK), jnp.float32, one_chip),
        row(), row(), row(), qmax=65535, dtype="uint16",
    ).compile()
