"""GSPMD sharding of the stacked pack: one compiled program per slice.

PR 10 replaces the explicit-stacking + per-shard `shard_map` execution
model with the sharding discipline every GSPMD training/inference stack
applies to its weights (SNIPPETS.md [1][2] — GDA/pjit sharded
compilation, regex partition rules over a params pytree): the device
pack IS a pytree, a `match_partition_rules`-style table maps every leaf
name to a `PartitionSpec`, arrays go up via `jax.device_put` with a
`NamedSharding`, and the search programs become ordinary `jit`-compiled
SPMD functions — `jax.vmap` over the shard axis of the sharded inputs,
`with_sharding_constraint` on the hot intermediates, and the global
top-k merge as `lax.top_k` over an ICI all-gather of the per-shard
(score, shard_doc) rows. XLA's SPMD partitioner lowers the gather to
ICI collectives; per-query device->host traffic drops from S*k rows to
k because only the merged (replicated) result is fetched.

Execution-mode contract (`ES_TPU_SPMD`):

  * ``pjit`` / ``auto`` (default) — GSPMD: sharded pack pytree, shard
    bodies embedded as `manual_shard_region` (shard_map-in-jit) regions
    of the ONE compiled program, on-device all-gather merge. PR 11:
    the manual region is how the fused Pallas arm rides this program —
    XLA's SPMD partitioner cannot split a custom call, but a manual
    region needs no partitioning decisions at all, so the Pallas
    kernels run per mesh device INSIDE the same compiled SPMD program
    that merges on-device. No separate code shape, no `force_xla` pin.
  * ``shardmap`` — the legacy PR-1..9 model: per-shard `shard_map`
    bodies + HOST coordinator merge. Demoted to a test oracle (parity
    fixtures, the C5 probe's shard-local timing arm); production
    routing never selects it unless the env forces it.

Replica groups: when `ES_TPU_REPLICAS=R` (R > 1) and the host exposes
S*R devices, the mesh gains a second ``replicas`` axis. Pack leaves are
sharded over ``shards`` only — i.e. replicated across ``replicas`` —
and the merged query axis is constrained over ``replicas``, so R
replica groups serve concurrent reads of the same resident pack.

Multi-process stretch (`ES_TPU_DIST_COORD`): `maybe_init_distributed`
wires `jax.distributed.initialize` behind env flags so the same mesh
code can span TCP cluster nodes; experimental, off by default.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# execution mode
# ---------------------------------------------------------------------------

def spmd_mode() -> str:
    """Resolved SPMD execution mode: "pjit" | "shardmap".

    ES_TPU_SPMD=auto|pjit|shardmap; auto (the default) resolves to pjit
    — the GSPMD path is the production model, shard_map the fallback."""
    v = os.environ.get("ES_TPU_SPMD", "auto").strip().lower()
    if v == "shardmap":
        return "shardmap"
    return "pjit"


# ---------------------------------------------------------------------------
# partition rules over the pack pytree
# ---------------------------------------------------------------------------

# leaf-path regex -> PartitionSpec. Paths are '/'-joined pytree key paths
# of the device pack dict built by `parallel/sharded.stacked_to_device`
# (e.g. "post_docids", "norms/body", "dv_int/bytes/0",
# "vec_ann/vec/codes"). Every stacked leaf carries the shard axis
# leading, so its spec shards dim 0 over "shards" and (implicitly)
# replicates the rest — including across a "replicas" mesh axis when one
# exists. The table is deliberately EXHAUSTIVE and non-overlapping: a
# leaf matching zero rules or more than one rule is a hard error
# (tests/test_spmd.py), so a new pack component cannot silently ship
# replicated (HBM x S) or mis-sharded.
PACK_PARTITION_RULES: list[tuple[str, P]] = [
    (r"^(post_docids|post_tfs|post_dls)$", P("shards")),
    (r"^impact_codes$", P("shards")),
    (r"^pos_keys$", P("shards")),
    (r"^live$", P("shards")),
    (r"^dense_tf$", P("shards")),
    (r"^dense_tfn$", P("shards")),
    (r"^norms/", P("shards")),
    (r"^text_has/", P("shards")),
    (r"^dv_int/", P("shards")),
    (r"^dv_float/", P("shards")),
    (r"^dv_ord/", P("shards")),
    (r"^dv_mv/", P("shards")),
    (r"^dv_int_ord/", P("shards")),
    (r"^vec/", P("shards")),
    (r"^vec_has/", P("shards")),
    (r"^vec_sq/", P("shards")),
    (r"^vec_ann/", P("shards")),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jtu.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jtu.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jtu.GetAttrKey):
            parts.append(str(p.name))
        else:  # pragma: no cover - future key kinds degrade to repr
            parts.append(str(p))
    return "/".join(parts)


def leaf_paths(tree) -> list[tuple[str, object]]:
    """-> [(path_str, leaf)] for every leaf of the pack pytree."""
    flat, _ = jtu.tree_flatten_with_path(tree)
    return [(_path_str(path), leaf) for path, leaf in flat]


def match_partition_rules(tree, rules=None):
    """-> pytree of PartitionSpec, one per leaf of `tree`.

    The fmengine/GSPMD `match_partition_rules` discipline applied to the
    pack: scalars (and 1-element arrays) replicate as PS(); every other
    leaf must match EXACTLY ONE rule — zero matches means an unsharded
    new component (it would replicate S-fold in HBM), two means an
    ambiguous table; both are hard errors, never silent fallbacks."""
    rules = PACK_PARTITION_RULES if rules is None else rules
    flat, treedef = jtu.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in flat:
        name = _path_str(path)
        shape = np.shape(leaf)
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            specs.append(P())
            continue
        hits = [spec for rx, spec in rules if re.search(rx, name)]
        if len(hits) == 0:
            raise ValueError(
                f"no partition rule matches pack leaf [{name}] "
                f"(shape {shape}) — add it to PACK_PARTITION_RULES")
        if len(hits) > 1:
            raise ValueError(
                f"pack leaf [{name}] matched {len(hits)} partition rules "
                "— the table must be non-overlapping")
        specs.append(hits[0])
    return jtu.tree_unflatten(treedef, specs)


def shard_put(tree, mesh: Mesh):
    """Ship a host pack pytree to the mesh: `jax.device_put` with the
    rule-matched NamedSharding per leaf. This is the GSPMD replacement
    for the positional `P("shards", None, ...)` construction — the
    sharding of every leaf is decided by its NAME, the same way a
    training stack shards its params pytree."""
    specs = match_partition_rules(tree)
    return jtu.tree_map(
        lambda x, s: jax.device_put(np.asarray(x), NamedSharding(mesh, s)),
        tree, specs)


# ---------------------------------------------------------------------------
# sharding constraints (the hot-intermediate annotations)
# ---------------------------------------------------------------------------

def constrain(x, mesh: Mesh | None, spec: P):
    """with_sharding_constraint, a no-op off-mesh (so traced bodies are
    shared between the single-device and pjit paths)."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_shards(tree, mesh: Mesh | None):
    """Constrain every leaf of a per-shard output pytree to stay sharded
    over the mesh's shard axis (dim 0) — the annotation that keeps the
    vmapped shard bodies partitioned instead of gathered."""
    if mesh is None:
        return tree
    s = NamedSharding(mesh, P("shards"))
    return jtu.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, s), tree)


def replica_axis(mesh: Mesh | None) -> str | None:
    """The mesh's replica axis name when replica groups are configured."""
    if mesh is not None and "replicas" in mesh.axis_names:
        return "replicas"
    return None


def manual_shard_region(shard_body, mesh: Mesh | None, *, in_specs):
    """Run a per-shard body as ONE region of the caller's jit program.

    On a mesh the body executes inside an embedded `shard_map` — manual
    partitioning, the only execution form in which Pallas custom calls
    run per mesh device inside a single compiled SPMD program (GSPMD
    cannot partition a custom call; a manual region never asks it to).
    The surrounding program stays GSPMD, so the on-device all-gather
    top-k merge composes directly with the region's sharded outputs —
    this is the PR-11 closure of the fused-arm fork (ROADMAP item 1).

    The body runs under `vmap` over the leading shard axis, on and off
    the mesh: off it over all S stacked shards, on it (inside the
    `shard_map`) over each device's local slice of size 1. `in_specs`
    entries are `P("shards")` for [S, ...]-stacked pytree args (mapped
    on axis 0, so the body sees one shard's slice) or `P()` for
    replicated args passed through whole (not mapped). Outputs keep the
    leading shard axis (out_specs P("shards")).

    Why `vmap` and not `x[0]` on the mesh: the compiler lays a local
    [1, m, 128] leaf with m mod 8 != 0 out as T(1,128), and a squeeze
    of it asks for T(8,128) — a relayout of the whole leaf every call."""
    shards_spec = P("shards")
    axes = tuple(0 if s == shards_spec else None for s in in_specs)
    region = jax.vmap(shard_body, in_axes=axes)
    if mesh is None:
        return region
    from ..utils.jax_env import shard_map

    return shard_map(region, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=shards_spec)


# ---------------------------------------------------------------------------
# the on-device global top-k merge
# ---------------------------------------------------------------------------

def merge_topk_rows(v, i, t, *, mesh: Mesh | None = None):
    """Global coordinator merge, traced: per-shard top rows
    (v [S, Q, kk] f32, i [S, Q, kk] ids, t [S, Q] totals) ->
    (scores [Q, kk], shard [Q, kk] i32, doc [Q, kk], totals [Q]).

    Order is (score desc, shard asc, doc asc) — the reference's
    SearchPhaseController / Lucene TopDocs.merge order, byte-identical
    to the host `_merge_shard_rows` lexsort: `lax.top_k` breaks score
    ties by lowest flat index, the shard-major flat layout makes flat
    index order = (shard asc, rank asc), and each shard's row is already
    (score desc, doc asc) internally, so rank asc == doc asc on ties.

    Under a mesh the input rows are constrained to replicated before the
    top-k — THIS is the ICI all-gather (S*Q*kk (score, id) rows cross
    the interconnect once; the merged k rows are replicated, so the host
    fetch pulls k rows per query instead of S*k). With replica groups
    the query axis stays split over "replicas" so each group merges only
    its own slice of the wave."""
    S, Q, kk = v.shape
    flat_v = jnp.swapaxes(v, 0, 1).reshape(Q, S * kk)
    flat_i = jnp.swapaxes(i, 0, 1).reshape(Q, S * kk)
    ra = replica_axis(mesh)
    flat_v = constrain(flat_v, mesh, P(ra, None))
    flat_i = constrain(flat_i, mesh, P(ra, None))
    mv, sel = jax.lax.top_k(flat_v, kk)
    shard = (sel // kk).astype(jnp.int32)
    mi = jnp.take_along_axis(flat_i, sel, axis=1)
    return mv, shard, mi, t.sum(axis=0)


def allgather_rows_bytes(s: int, q: int, kk: int,
                         id_bytes: int = 8) -> float:
    """The collective-traffic model of the merge: every shard's [Q, kk]
    (score f32, id i64) rows are all-gathered across the S mesh devices
    — per-device ICI traffic is (S-1)/S of the total row bytes out and
    the same in; the model reports the TOTAL gathered row volume
    S*Q*kk*(4+id_bytes), the quantity the all-gather moves across the
    interconnect once (BENCH_NOTES round 14)."""
    return float(s * q * kk * (4 + id_bytes))


# ---------------------------------------------------------------------------
# mesh construction + the multi-process stretch
# ---------------------------------------------------------------------------

_dist_initialized = False


def maybe_init_distributed() -> bool:
    """Experimental multi-process mesh across TCP cluster nodes: when
    ES_TPU_DIST_COORD is set, `jax.distributed.initialize` joins this
    process to the slice-wide device mesh (coordinator address +
    ES_TPU_DIST_NPROCS / ES_TPU_DIST_RANK) so `jax.devices()` spans
    every node and the same pjit programs compile slice-wide. Off by
    default; a failed initialize raises — a node asked to join a mesh
    must not carry on alone."""
    global _dist_initialized
    coord = os.environ.get("ES_TPU_DIST_COORD")
    if not coord or _dist_initialized:
        return _dist_initialized
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ.get("ES_TPU_DIST_NPROCS", "1")),
        process_id=int(os.environ.get("ES_TPU_DIST_RANK", "0")),
    )
    _dist_initialized = True
    return _dist_initialized


def make_mesh(num_shards: int) -> Mesh | None:
    """Mesh over the first num_shards devices; None -> single-device vmap.

    In pjit mode, ES_TPU_REPLICAS=R (with S*R devices available) builds
    a 2-D (S, R) mesh with axes ("shards", "replicas"): the pack shards
    over the first axis and replicates over the second, so R replica
    groups serve concurrent reads. The shard_map fallback always gets
    the 1-D mesh (its in/out specs name only "shards")."""
    maybe_init_distributed()
    devices = jax.devices()
    if num_shards <= 1 or len(devices) < num_shards:
        return None
    if spmd_mode() == "pjit":
        want = int(os.environ.get("ES_TPU_REPLICAS", "1") or 1)
        r = max(1, min(want, len(devices) // num_shards))
        if r > 1:
            arr = np.array(devices[: num_shards * r]).reshape(num_shards, r)
            return Mesh(arr, ("shards", "replicas"))
    return Mesh(np.array(devices[:num_shards]), ("shards",))
