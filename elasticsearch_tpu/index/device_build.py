"""Device-side index construction kernels (ROADMAP item 2, PR 15).

Every query-time structure is precomputed at refresh (impact codes, IVF
tiles, bf16 split pairs), but through PR 14 the builds themselves ran as
host loops: BENCH_r11's `build_profile` baseline shows the ANN build
spending ~97% of its wall in host kmeans and the text build dominated by
CSR assembly after tokenization. This module ports the arithmetic core
of each build stage to jitted device kernels, dispatched through the
SAME `build.*` KERNEL_COSTS entries PR 13 registered — so host-vs-device
attribution, the XLA cost cross-check, and the RefreshProfile stage
split apply to the port from day one (the `basis` field on each
dispatch records which side ran).

Kernels (GPUSparse's parallel inverted-index construction, shaped for
XLA rather than CUDA warps):

  - `kmeans_device`   — the Lloyd loop as ONE compiled program
    (matmul + argmin assignment waves, scatter-add centroid update)
    under `lax.while_loop`, with an on-device convergence criterion:
    iteration stops when the max squared centroid shift drops to
    `tol` (default 0.0 — a zero shift is a fixed point, so early exit
    is output-identical to the fixed 8-iteration host loop while
    skipping dead work).
  - `csr_blocked_scatter_device` — the blocked-postings assembly as a
    segment-scatter kernel: flat CSR lanes scatter into their
    [total_blocks, BLOCK] destinations and the per-block max-tf /
    min-len metadata derives via scatter-max/min (order-independent,
    exactly the host reduceat).
  - `ann_tiles_device` — IVF tile packing as a `jax.lax`-sort/segment
    kernel: stable argsort by cluster, per-cluster rank via the size
    prefix sum, one gather of the sorted vectors, per-vector int8
    scalar quantization (ann/quantize math verbatim), and scatters
    into the padded [C, L] tiles.
  - `impact_codes_device` — the impact quantization elementwise pass
    (shared with parallel/sharded.refresh_impacts, which proved the
    shape in PR 13).

Byte parity: each kernel performs the identical f32/int arithmetic as
its host twin, so device-built packs are asserted BYTE-IDENTICAL to
host-built packs by tests/test_device_build.py — the port changes where
the work runs, never what it produces.

Gating: `ES_TPU_DEVICE_BUILD` (default on) enables the device path;
stages engage per dispatch only above `ES_TPU_DEVICE_BUILD_MIN`
elements (default 32768) so tiny test corpora skip jit compile
overhead — CPU smokes may be host-bound either way; TPU is the
criterion (BENCH_NOTES convention)."""

from __future__ import annotations

import functools
import os

import numpy as np

__all__ = [
    "device_build_enabled",
    "device_build_min",
    "use_device_build",
    "use_device_csr_scatter",
    "kmeans_device",
    "csr_blocked_scatter_device",
    "ann_tiles_device",
    "impact_codes_device",
    "analyze_hash_device",
]

# quantization constants mirrored from ann/quantize.py (the host twin)
_QMAX = 127.0
_QLEVELS = 254.0


def device_build_enabled() -> bool:
    """ES_TPU_DEVICE_BUILD: "0" pins every build stage to the host path
    (the PR-13 baseline); anything else (default) enables the device
    kernels."""
    return os.environ.get("ES_TPU_DEVICE_BUILD", "1") != "0"


def device_build_min() -> int:
    """Per-dispatch element floor below which a stage stays on the host
    (jit compile + transfer overhead beats tiny corpora; the bench
    corpora and production refreshes clear it)."""
    try:
        return int(os.environ.get("ES_TPU_DEVICE_BUILD_MIN", "32768"))
    except ValueError:
        return 32768


def use_device_build(elements: int) -> bool:
    """The per-stage gate: enabled AND the dispatch is big enough."""
    return device_build_enabled() and elements >= device_build_min()


def use_device_csr_scatter(postings: int) -> bool:
    """The gate of the blocked-CSR scatter alone. Its input is the
    accumulator's flat CSR, host arrays, its output goes back into the
    pack's host arrays, and `total_blocks` is a static shape of the
    program: on a v5e every shard of every refresh compiled it anew for
    12-13 s, and compiled it ran no faster than the host's scatter (2.16 s
    against 1.68 s for one 294,912-passage shard, 0.64 s of it on the
    device; PERF.md, PR 29). So by default the host assembles the blocks;
    the kernel engages where `ES_TPU_DEVICE_BUILD_MIN` sets a floor by
    hand (the parity tests, an A/B), and is what a pack that stays on
    the device would start from."""
    return ("ES_TPU_DEVICE_BUILD_MIN" in os.environ
            and use_device_build(postings))


# ---------------------------------------------------------------------------
# kmeans: the Lloyd loop as one compiled program
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _kmeans_jit():
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnames=("iters",))
    def run(vecs, init_centroids, iters, tol):
        def assign_of(c):
            # argmin ||v-c||^2 == argmax v.c - ||c||^2/2 — the matmul +
            # argmin assignment wave (identical to the host-loop math)
            logits = (vecs @ c.T
                      - 0.5 * jnp.sum(c * c, axis=1)[None, :])
            return jnp.argmax(logits, axis=1)

        C = init_centroids.shape[0]

        def body(state):
            i, c, _shift = state
            assign = assign_of(c)
            sums = jnp.zeros_like(c).at[assign].add(vecs)
            counts = jnp.zeros((C,), jnp.float32).at[assign].add(1.0)
            new_c = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts[:, None], 1.0), c)
            shift = jnp.max(jnp.sum((new_c - c) ** 2, axis=1))
            return i + 1, new_c, shift

        def cond(state):
            i, _c, shift = state
            return (i < iters) & (shift > tol)

        iters_run, cents, _shift = lax.while_loop(
            cond, body, (jnp.int32(0), init_centroids,
                         jnp.float32(np.inf)))
        return cents, assign_of(cents), iters_run

    return run


def kmeans_device(vectors, nlist: int, iters: int = 8,
                  tol: float | None = None):
    """Lloyd k-means for the IVF partition index as ONE jitted program.

    -> (centroids [C, D] f32, assign [N] int32, iters_run int).

    tol is the on-device convergence criterion: the loop exits when the
    max squared centroid shift <= tol. The default (ES_TPU_KMEANS_TOL,
    0.0) only exits at an exact fixed point — further iterations would
    be no-ops — so results are identical to the fixed-iteration host
    loop; a looser tol trades iterations for centroid precision
    (documented in DIVERGENCES)."""
    import jax.numpy as jnp

    if tol is None:
        tol = float(os.environ.get("ES_TPU_KMEANS_TOL", "0.0"))
    vecs = jnp.asarray(vectors, jnp.float32)
    N, _D = vecs.shape
    C = max(1, min(nlist, N))
    # deterministic strided init over the corpus (unchanged from the
    # host-driven loop this kernel replaces)
    init_idx = (jnp.arange(C) * (N // C)).astype(jnp.int32)
    cents, assign, iters_run = _kmeans_jit()(
        vecs, vecs[init_idx], iters, jnp.float32(tol))
    return (np.asarray(cents), np.asarray(assign, np.int32),
            int(iters_run))


# ---------------------------------------------------------------------------
# blocked-CSR assembly: segment scatter + scatter-max/min block metadata
# ---------------------------------------------------------------------------

def _pow2_pad(n: int, floor: int = 1024) -> int:
    """Flat lanes pad to the next power of two so the jit cache sees a
    bounded family of shapes instead of one executable per corpus."""
    p = floor
    while p < n:
        p <<= 1
    return p


@functools.lru_cache(maxsize=1)
def _csr_scatter_jit():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("total_blocks", "block",
                                                 "n_sentinel"))
    def run(flat_docs, flat_tfs, flat_dls, dest_row, dest_col,
            total_blocks, block, n_sentinel):
        # one extra dump row swallows the pow2 padding lanes
        docids = jnp.full((total_blocks + 1, block), n_sentinel,
                          jnp.int32).at[dest_row, dest_col].set(flat_docs)
        tfs = jnp.zeros((total_blocks + 1, block),
                        jnp.float32).at[dest_row, dest_col].set(flat_tfs)
        dls = jnp.ones((total_blocks + 1, block),
                       jnp.float32).at[dest_row, dest_col].set(flat_dls)
        bmax = jnp.zeros((total_blocks + 1,),
                         jnp.float32).at[dest_row].max(flat_tfs)
        bmin = jnp.full((total_blocks + 1,), jnp.inf,
                        jnp.float32).at[dest_row].min(flat_dls)
        return (docids[:total_blocks], tfs[:total_blocks],
                dls[:total_blocks], bmax[:total_blocks],
                bmin[:total_blocks])

    return run


def _put(arrays, device):
    """Host arrays committed to `device`, so that the jitted stage they
    feed runs there; None leaves them to the default device."""
    if device is None:
        return arrays
    import jax

    return jax.device_put(arrays, device)


def csr_blocked_scatter_device(flat_docs, flat_tfs, flat_dls,
                               dest_row, dest_col, total_blocks: int,
                               block: int, n_sentinel: int, device=None):
    """Blocked-postings assembly on device: flat CSR lanes scatter into
    [total_blocks, BLOCK] and block max-tf / min-len derive via
    scatter-max/min (order-independent — exactly the host reduceat).

    -> (post_docids, post_tfs, post_dls, block_max_tf, block_min_len)
    as numpy; min-len stays +inf for empty blocks (caller normalizes,
    same as the host path)."""
    np_ = _pow2_pad(len(flat_docs))
    pad = np_ - len(flat_docs)
    fd = np.concatenate([np.asarray(flat_docs, np.int32),
                         np.zeros(pad, np.int32)])
    ft = np.concatenate([np.asarray(flat_tfs, np.float32),
                         np.zeros(pad, np.float32)])
    fl = np.concatenate([np.asarray(flat_dls, np.float32),
                         np.ones(pad, np.float32)])
    dr = np.concatenate([np.asarray(dest_row, np.int32),
                         np.full(pad, total_blocks, np.int32)])
    dc = np.concatenate([np.asarray(dest_col, np.int32),
                         np.zeros(pad, np.int32)])
    out = _csr_scatter_jit()(*_put((fd, ft, fl, dr, dc), device),
                             int(total_blocks), int(block),
                             int(n_sentinel))
    # np.array (not asarray): writable host copies — callers normalize
    # block_min_len in place and the pack arrays outlive the jit buffers
    return tuple(np.array(a) for a in out)


# ---------------------------------------------------------------------------
# ANN tile packing: lax-sort/segment + on-device int8 quantization
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _ann_tiles_jit():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("C", "L"))
    def run(vectors, docids, assign, qlevels, C, L):
        M = assign.shape[0]
        # stable sort by cluster = the segment layout (lax.sort under
        # jnp.argsort); per-cluster rank from the size prefix sum
        order_local = jnp.argsort(assign, stable=True)
        a_sorted = assign[order_local]
        ids_sorted = docids[order_local]
        sizes = jnp.zeros((C,), jnp.int32).at[assign].add(1)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(sizes)[:-1].astype(jnp.int32)])
        rank = jnp.arange(M, dtype=jnp.int32) - offsets[a_sorted]
        order = jnp.full((C, L), -1,
                         jnp.int32).at[a_sorted, rank].set(ids_sorted)
        # per-vector int8 affine quantization (ann/quantize math)
        vecs = vectors[ids_sorted]
        vmin = vecs.min(axis=-1)
        vmax = vecs.max(axis=-1)
        offset = (vmin + vmax) / 2.0
        # qlevels rides in as a runtime operand: a baked 254.0 constant
        # lets XLA strength-reduce the divide into a reciprocal multiply,
        # which is 1 ulp off the host quantizer — byte parity demands the
        # real division
        scale = (vmax - vmin) / qlevels
        safe = jnp.where(scale > 0, scale, 1.0)
        codes = jnp.clip(
            jnp.rint((vecs - offset[:, None]) / safe[:, None]),
            -_QMAX, _QMAX).astype(jnp.int8)
        codes_t = jnp.zeros((C, L, vectors.shape[1]),
                            jnp.int8).at[a_sorted, rank].set(codes)
        scale_t = jnp.zeros((C, L),
                            jnp.float32).at[a_sorted, rank].set(scale)
        offset_t = jnp.zeros((C, L),
                             jnp.float32).at[a_sorted, rank].set(offset)
        return order, codes_t, scale_t, offset_t

    return run


def ann_tiles_device(vectors, docids, assign, C: int, L: int):
    """IVF tile packing on device -> (order [C,L] i32, codes [C,L,D]
    i8, scale [C,L] f32, offset [C,L] f32) as numpy — byte-identical to
    the host per-cluster loop (same stable sort, same quantizer)."""
    import jax.numpy as jnp

    order, codes, scale, offset = _ann_tiles_jit()(
        jnp.asarray(vectors, jnp.float32),
        jnp.asarray(docids, jnp.int32),
        jnp.asarray(assign, jnp.int32),
        jnp.float32(_QLEVELS), int(C), int(L))
    return (np.asarray(order), np.asarray(codes),
            np.asarray(scale), np.asarray(offset))


# ---------------------------------------------------------------------------
# batch text analysis: tokenize + segmented term hashing (PR 16)
# ---------------------------------------------------------------------------

# padded [values, chars] tensors above this element budget fall back to
# the batched host path — one dispatch must never provoke a transfer
# larger than the rest of the refresh combined
_ANALYZE_MAX_ELEMENTS = 1 << 26

# two independent polynomial hash lanes; term identity on device is the
# (h1, h2, token_length) triple (collision odds documented in
# DIVERGENCES "Vectorized ingest")
_HASH_MULT_1 = 1000003
_HASH_MULT_2 = 8191


@functools.lru_cache(maxsize=1)
def _analyze_hash_jit():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(chars, lengths):
        # chars [B, L] uint8 (raw ASCII bytes), lengths [B] int32
        L = chars.shape[1]
        valid = jnp.arange(L, dtype=jnp.int32)[None, :] < lengths[:, None]
        c = chars
        lower = jnp.where((c >= 65) & (c <= 90), c + 32, c)
        is_word = ((((lower >= 97) & (lower <= 122))
                    | ((c >= 48) & (c <= 57))) & valid)
        # _WORD_RE apostrophe join: 0x27 with word chars on both sides
        prev_word = jnp.pad(is_word[:, :-1], ((0, 0), (1, 0)))
        next_word = jnp.pad(is_word[:, 1:], ((0, 0), (0, 1)))
        joiner = (c == 39) & valid & prev_word & next_word
        in_tok = is_word | joiner
        prev_in = jnp.pad(in_tok[:, :-1], ((0, 0), (1, 0)))
        next_in = jnp.pad(in_tok[:, 1:], ((0, 0), (0, 1)))
        start = in_tok & ~prev_in
        end = in_tok & ~next_in
        # segmented polynomial rolling hash over the LOWERED bytes:
        # h_i = h_{i-1} * K + byte_i, reset at token starts (multiplier
        # 0), identity (1, 0) outside tokens. The affine composition
        # (m, v)∘(m', v') = (m·m', v·m' + v') is associative, so the
        # whole row reduces in one lax.associative_scan — O(log L)
        # depth instead of the host's per-char loop.
        cu = lower.astype(jnp.uint32)

        def seg_hash(mult):
            m = jnp.where(in_tok,
                          jnp.where(start, jnp.uint32(0),
                                    jnp.uint32(mult)),
                          jnp.uint32(1))
            v = jnp.where(in_tok, cu, jnp.uint32(0))

            def comb(a, b):
                return a[0] * b[0], a[1] * b[0] + b[1]

            _, h = jax.lax.associative_scan(comb, (m, v), axis=1)
            return h

        return (start, end, joiner,
                seg_hash(_HASH_MULT_1), seg_hash(_HASH_MULT_2))

    return run


def analyze_hash_device(chars, lengths):
    """Standard-analyzer tokenization + term hashing over a padded
    [values, chars] uint8 tensor as ONE jitted program.

    -> (start, end, joiner, h1, h2) as numpy arrays trimmed back to the
    input shape: boolean token start/end/apostrophe-join masks plus two
    uint32 hash lanes whose values AT the end positions are the tokens'
    polynomial hashes over their lowercased bytes. Returns None when
    the pow2-padded tensor exceeds the transfer budget (the caller
    degrades to the batched host path)."""
    chars = np.asarray(chars, np.uint8)
    lengths = np.asarray(lengths, np.int32)
    B, L = chars.shape
    Bp = _pow2_pad(B, floor=8)
    Lp = _pow2_pad(L, floor=64)
    if Bp * Lp > _ANALYZE_MAX_ELEMENTS:
        return None
    cp = np.zeros((Bp, Lp), np.uint8)
    cp[:B, :L] = chars
    lp = np.zeros((Bp,), np.int32)
    lp[:B] = lengths
    out = _analyze_hash_jit()(cp, lp)
    return tuple(np.asarray(a)[:B, :L] for a in out)


# ---------------------------------------------------------------------------
# impact quantization: the elementwise pass (PR-13 device twin, shared)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _impact_codes_jit():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("qmax", "dtype"))
    def run(tfs, dls, k_base, k_slope, scale_inv, *, qmax, dtype):
        K = k_base[..., None] + k_slope[..., None] * dls
        tfn = tfs / (tfs + K)
        q = jnp.rint(tfn * scale_inv[..., None])
        q = jnp.clip(q, 1, qmax)  # tf > 0 stays a match (code >= 1)
        q = jnp.where(tfs > 0, q, 0)
        return q.astype(jnp.uint16 if dtype == "uint16" else jnp.int8)

    return run


def impact_codes_device(tfs, dls, k_base, k_slope, scale_inv, *,
                        qmax: int, dtype: str, device=None):
    """Impact-code derivation as one elementwise device pass — the twin
    of index/pack.impact_codes_host (asserted equal by tests). Accepts
    device or host arrays; returns a device array (callers fetching to
    host wrap in np.asarray). `device`: where host arrays are put and the
    pass runs (a build's shard device); None is the default device."""
    import jax.numpy as jnp

    args = (tfs, dls, k_base, k_slope, scale_inv)
    if device is None:
        args = tuple(jnp.asarray(a) for a in args)
    return _impact_codes_jit()(*_put(args, device),
                               qmax=int(qmax), dtype=dtype)
