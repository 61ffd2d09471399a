"""The batched ANN gather-scan: one dispatch for a whole query batch.

Query time is two stages, both device-side:

  1. centroid probe — [B, D] @ [D, C] matmul + top-nprobe per query
     (the partition routing the reference does with an HNSW entry-point
     walk; here it is one small MXU pass).
  2. gather-scan — THE dispatch this module exists for: for every
     (query, probed cluster) pair, DMA the cluster's [L, D] quantized
     tile and fold its scores into a running in-VMEM top-kb. The Pallas
     arm uses scalar-prefetched probe ids to drive the tile gather
     through BlockSpec index maps (grid (B, nprobe), p innermost, so
     the accumulator discipline of ops/kernels applies unchanged); the
     XLA arm reproduces the semantics with gathers + top_k for non-TPU
     backends, chunked over the batch to bound materialization.

Scores out of the scan are SELECTION scores (quantized tier); callers
f32-rescore the surviving candidate ids (ops/vector._rescore_knn) —
the tiered_candidates discipline of ops/kernels applied to ANN.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.kernels import MAX_FUSED_K, _mask_hi, _merge_topk, use_pallas

_I0 = np.int32(0)

# XLA-arm chunking: bound the gathered [chunk, P, L, D] materialization
_XLA_CHUNK_BYTES = 128 * 1024 * 1024

SCAN_TIERS = ("int8", "bf16")


def _transform_slots(dots, transform, auxd, auxq):
    """_apply_transform (ops/kernels) generalized to per-slot aux: every
    query probes different clusters, so auxd is [B, M] not [N]."""
    if transform == "identity":
        return dots
    if transform == "cosine":
        return (1.0 + dots * auxd * auxq) / 2.0
    if transform == "dot_product":
        return (1.0 + dots) / 2.0
    if transform == "l2_norm":
        l2 = jnp.maximum(auxd - 2.0 * dots + auxq, 0.0)
        return 1.0 / (1.0 + l2)
    if transform == "max_inner_product":
        return jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
    raise ValueError(f"unknown transform [{transform}]")


def slot_aux(sq_slots, similarity: str):
    """Per-slot transform aux from packed squared norms (zeros when the
    transform needs none)."""
    if similarity == "cosine":
        return 1.0 / jnp.maximum(jnp.sqrt(sq_slots), 1e-30)
    if similarity == "l2_norm":
        return sq_slots
    return jnp.zeros_like(sq_slots)


def query_aux(qvecs, similarity: str):
    """Per-query transform aux ([B]) matching ops/vector._aux_for."""
    qsq = jnp.sum(qvecs * qvecs, axis=-1)
    if similarity == "cosine":
        return 1.0 / jnp.maximum(jnp.sqrt(qsq), 1e-30)
    if similarity == "l2_norm":
        return qsq
    return jnp.zeros_like(qsq)


@functools.partial(jax.jit, static_argnames=("nprobe",))
def centroid_topk(centroids, qvecs, *, nprobe: int):
    """-> probe ids [B, nprobe]: the nprobe nearest partitions per query
    (argmin ||q - c||^2 == argmax q.c - ||c||^2/2 — metric-shared with
    the k-means assignment, so every similarity routes consistently)."""
    logits = qvecs @ centroids.T - 0.5 * jnp.sum(
        centroids * centroids, axis=-1)[None, :]
    _, probe = jax.lax.top_k(logits, min(nprobe, centroids.shape[0]))
    return probe.astype(jnp.int32)


# ---------------------------------------------------------------------------
# XLA arm
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("kb", "tier", "transform"))
def _ann_scan_xla_chunk(
    q, probes, order, t_a, t_b, scale, offset, auxd_slots, live_slots,
    aux_q, *, kb, tier, transform,
):
    B = q.shape[0]
    P, L = probes.shape[1], order.shape[1]
    ord_g = order[probes].reshape(B, P * L)
    if tier == "int8":
        dots = jnp.einsum(
            "bpld,bd->bpl", t_a[probes], q,
            preferred_element_type=jnp.float32,
        )
        qsum = jnp.sum(q, axis=1)
        dots = (scale[probes] * dots
                + offset[probes] * qsum[:, None, None])
    else:
        qh = _mask_hi(q).astype(jnp.bfloat16)
        dots = jnp.einsum(
            "bpld,bd->bpl", t_a[probes], qh,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bpld,bd->bpl", t_b[probes], qh,
            preferred_element_type=jnp.float32,
        )
    dots = dots.reshape(B, P * L)
    auxd = auxd_slots[probes].reshape(B, P * L)
    scores = _transform_slots(dots, transform, auxd, aux_q[:, None])
    ok = (ord_g >= 0) & live_slots[probes].reshape(B, P * L)
    scores = jnp.where(ok, scores, -jnp.inf)
    totals = jnp.sum(ok, axis=1, dtype=jnp.int32)
    v, idx = jax.lax.top_k(scores, min(kb, P * L))
    ids = jnp.take_along_axis(ord_g, idx, axis=1)
    return v, ids.astype(jnp.int32), totals


# ---------------------------------------------------------------------------
# Pallas arm
# ---------------------------------------------------------------------------

def _ann_scan_kernel(
    probes_ref, q_ref, ta_ref, tb_ref, auxd_ref, ord_ref, live_ref,
    auxq_ref,
    ov_ref, oi_ref, ot_ref,
    acc_v, acc_i, cnt,
    *, kb, tier, transform,
):
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _():
        acc_v[:] = jnp.full_like(acc_v, -jnp.inf)
        acc_i[:] = jnp.zeros_like(acc_i)
        cnt[:] = jnp.zeros_like(cnt)

    dn = (((1,), (1,)), ((), ()))
    if tier == "int8":
        # tb_ref carries the (scale, offset) pair stacked on axis 0
        dots = jax.lax.dot_general(
            q_ref[:], ta_ref[0].astype(jnp.float32), dn,
            preferred_element_type=jnp.float32,
        )
        qsum = jnp.sum(q_ref[:], axis=1, keepdims=True)
        dots = tb_ref[0, 0:1, :] * dots + tb_ref[0, 1:2, :] * qsum
    else:
        # ta/tb are the split-bf16 hi/lo tiles; q arrives bf16-masked
        dots = jax.lax.dot_general(
            q_ref[:], ta_ref[0], dn, preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            q_ref[:], tb_ref[0], dn, preferred_element_type=jnp.float32,
        )
    scores = _transform_slots(dots, transform, auxd_ref[:], auxq_ref[:])
    ids = ord_ref[:]
    ok = (ids >= 0) & (live_ref[:] > 0)
    scores = jnp.where(ok, scores, -jnp.inf)
    cnt[:] += ok.astype(jnp.float32)
    new_v, new_i = _merge_topk(scores, ids, acc_v[:], acc_i[:], kb)
    acc_v[:] = new_v
    acc_i[:] = new_i

    @pl.when(p == np_ - 1)
    def _():
        ov_ref[:] = acc_v[:]
        oi_ref[:] = acc_i[:]
        ot_ref[:] = jnp.sum(cnt[:], axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("kb", "tier", "transform", "interpret"),
)
def _ann_scan_pallas(
    q, probes, order, t_a, t_b, auxd_slots, live_slots, aux_q,
    *, kb, tier, transform, interpret,
):
    B, D = q.shape
    P = probes.shape[1]
    C, L = order.shape
    kernel = functools.partial(
        _ann_scan_kernel, kb=kb, tier=tier, transform=transform)
    tile_spec = pl.BlockSpec(
        (1, *t_a.shape[1:]), lambda b, p, pr: (pr[b, p], *(_I0,) * (t_a.ndim - 1)))
    slot_spec = pl.BlockSpec((1, L), lambda b, p, pr: (pr[b, p], _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, D), lambda b, p, pr: (b, _I0)),
            tile_spec,
            pl.BlockSpec(
                (1, *t_b.shape[1:]),
                lambda b, p, pr: (pr[b, p], *(_I0,) * (t_b.ndim - 1))),
            slot_spec,
            slot_spec,
            slot_spec,
            pl.BlockSpec((1, 1), lambda b, p, pr: (b, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kb), lambda b, p, pr: (b, _I0)),
            pl.BlockSpec((1, kb), lambda b, p, pr: (b, _I0)),
            pl.BlockSpec((1, 1), lambda b, p, pr: (b, _I0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, kb), jnp.float32),
            pltpu.VMEM((1, kb), jnp.int32),
            pltpu.VMEM((1, L), jnp.float32),
        ],
    )
    out_v, out_i, out_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, kb), jnp.float32),
            jax.ShapeDtypeStruct((B, kb), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(probes, q, t_a, t_b, auxd_slots, order,
      live_slots.astype(jnp.float32), aux_q[:, None])
    return out_v, out_i, out_t[:, 0]


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def ann_gather_scan(
    qvecs,        # [B, D] f32
    probes,       # [B, P] i32 (centroid_topk output)
    ann_dev: dict,  # ann_to_device output
    live_slots,   # [C, L] bool — live[order] with pad slots False
    kb: int,
    *,
    tier: str = "int8",
    similarity: str = "cosine",
    interpret: bool | None = None,
):
    """-> (sel_v [B, kb] selection scores, sel_i [B, kb] docids,
    totals [B] candidate counts). One batched dispatch over the probed
    cluster tiles; Pallas on TPU, XLA gathers elsewhere."""
    if tier not in SCAN_TIERS:
        raise ValueError(f"unknown ANN scan tier [{tier}]")
    qvecs = jnp.asarray(qvecs, jnp.float32)
    B, D = qvecs.shape
    P = probes.shape[1]
    order = ann_dev["order"]
    C, L = order.shape
    kb = max(1, min(kb, P * L))
    auxd_slots = slot_aux(ann_dev["sq"], similarity)
    aux_q = query_aux(qvecs, similarity)
    tile_bytes = B * P * L * (D if tier == "int8" else 4 * D)
    pallas_ok = kb <= MAX_FUSED_K
    if interpret is None:
        if not use_pallas(score_bytes=tile_bytes) or not pallas_ok:
            return _ann_scan_chunked(
                qvecs, probes, ann_dev, auxd_slots, live_slots, aux_q,
                kb=kb, tier=tier, similarity=similarity)
        interpret = jax.default_backend() != "tpu"
    if not pallas_ok:
        return _ann_scan_chunked(
            qvecs, probes, ann_dev, auxd_slots, live_slots, aux_q,
            kb=kb, tier=tier, similarity=similarity)
    if tier == "int8":
        q_in = qvecs
        t_a = ann_dev["codes"]
        # (scale, offset) stacked to one [C, 2, L] operand so the kernel
        # gathers a single metadata tile per probe
        t_b = jnp.stack([ann_dev["scale"], ann_dev["offset"]], axis=1)
    else:
        q_in = _mask_hi(qvecs).astype(jnp.bfloat16)
        t_a, t_b = ann_dev["hi"], ann_dev["lo"]
    return _ann_scan_pallas(
        q_in, probes, order, t_a, t_b, auxd_slots,
        live_slots, aux_q,
        kb=kb, tier=tier, transform=similarity,
        interpret=bool(interpret),
    )


def _ann_scan_chunked(qvecs, probes, ann_dev, auxd_slots, live_slots,
                      aux_q, *, kb, tier, similarity):
    """XLA arm, chunked over the batch so the [chunk, P, L, D] gather
    stays bounded. Chunk geometry is padded to one size so every chunk
    reuses one compiled executable."""
    B, D = qvecs.shape
    P, L = probes.shape[1], ann_dev["order"].shape[1]
    per_q = P * L * D * (1 if tier == "int8" else 4)
    chunk = max(1, min(B, _XLA_CHUNK_BYTES // max(per_q, 1)))
    if tier == "int8":
        t_a, t_b = ann_dev["codes"], None
        scale, offset = ann_dev["scale"], ann_dev["offset"]
    else:
        t_a, t_b = ann_dev["hi"], ann_dev["lo"]
        scale = offset = jnp.zeros((1, 1), jnp.float32)
    if t_b is None:
        t_b = t_a  # unused by the int8 path; keeps the jit signature fixed
    outs = []
    for s in range(0, B, chunk):
        qc = qvecs[s:s + chunk]
        pc = probes[s:s + chunk]
        ac = aux_q[s:s + chunk]
        pad = chunk - qc.shape[0]
        if pad:
            qc = jnp.pad(qc, ((0, pad), (0, 0)))
            pc = jnp.pad(pc, ((0, pad), (0, 0)))
            ac = jnp.pad(ac, (0, pad))
        outs.append(_ann_scan_xla_chunk(
            qc, pc, ann_dev["order"], t_a, t_b, scale, offset,
            auxd_slots, live_slots, ac,
            kb=kb, tier=tier, transform=similarity))
    v = jnp.concatenate([o[0] for o in outs])[:B]
    i = jnp.concatenate([o[1] for o in outs])[:B]
    t = jnp.concatenate([o[2] for o in outs])[:B]
    return v, i, t


# ---------------------------------------------------------------------------
# traced per-query form (query/nodes.py runs inside a compiled plan)
# ---------------------------------------------------------------------------

def ann_candidates_traced(
    ann_dev: dict, qvec, live, kcand: int,
    *, nprobe: int, tier: str, similarity: str,
):
    """Pure-jnp single-query probe + quantized scan + candidate
    selection, callable inside jit/vmap/shard_map (the KnnNode path —
    the per-shard compiled plan is the dispatch, so no pallas_call
    here). -> (cand_ids [kcand] i32, sel_scores [kcand], totals i32)."""
    cents = ann_dev["centroids"]
    C = cents.shape[0]
    L = ann_dev["order"].shape[1]
    logits = cents @ qvec - 0.5 * jnp.sum(cents * cents, axis=-1)
    _, probes = jax.lax.top_k(logits, min(nprobe, C))
    order = ann_dev["order"][probes]          # [P, L]
    if tier == "int8":
        dots = jnp.einsum(
            "pld,d->pl", ann_dev["codes"][probes], qvec,
            preferred_element_type=jnp.float32)
        dots = (ann_dev["scale"][probes] * dots
                + ann_dev["offset"][probes] * jnp.sum(qvec))
    else:
        qh = _mask_hi(qvec).astype(jnp.bfloat16)
        dots = jnp.einsum(
            "pld,d->pl", ann_dev["hi"][probes], qh,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "pld,d->pl", ann_dev["lo"][probes], qh,
            preferred_element_type=jnp.float32,
        )
    flat_ids = order.reshape(-1)
    auxd = slot_aux(ann_dev["sq"][probes], similarity).reshape(-1)
    auxq = query_aux(qvec[None, :], similarity)[0]
    scores = _transform_slots(
        dots.reshape(1, -1), similarity, auxd[None, :], auxq)[0]
    ok = (flat_ids >= 0) & live[jnp.maximum(flat_ids, 0)]
    scores = jnp.where(ok, scores, -jnp.inf)
    kcand = max(1, min(kcand, flat_ids.shape[0]))
    sel_v, sel_pos = jax.lax.top_k(scores, kcand)
    cand = jnp.take(flat_ids, sel_pos)
    return cand.astype(jnp.int32), sel_v, jnp.sum(ok, dtype=jnp.int32)
