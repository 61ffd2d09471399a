"""One host-to-device transfer a dispatch, one device-to-host transfer a fetch.

A plan's parameters are a pytree of small host arrays, `[S, ...]` each after
`_stack_shard_params`, and a jitted program ships every leaf it reads as a
transfer of its own at a fixed cost (~0.12 ms on a v5e's host, PERF.md
section 5), a dozen a `match`. `pack` lays them side by side in one buffer
per dtype class before the call; `unpack`, at the top of the traced program,
slices the same `[S, ...]` tree back out, bit for bit.

All four-byte numeric leaves share one `int32[S, W]` buffer (a float32 rides
as its bits, so the device is only ever asked for a 32-bit bitcast); every
other dtype (int64 under x64, bool) gets one buffer of its own dtype. A leaf
that is already a `jax.Array` passes through beside the buffers. The layout
is a hashable tuple and belongs to the identity of the program that unpacks
by it.

The way back is the mirror. A program's result is a tree of small device
arrays, and `jax.device_get` waits for a copy of each. `pack_outputs`, at the
end of the traced program, lays them side by side in one flat buffer per dtype
class by the same rule; `unpack_host` cuts the fetched buffers back into the
tree by NumPy views, bit for bit. This layout is known once the program has
been traced, and stays with it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np

_WORD = np.dtype(np.int32)


def _buffer_dtype(dtype: np.dtype) -> np.dtype:
    """The dtype of the buffer a leaf of `dtype` rides in."""
    if dtype.itemsize == 4 and dtype.kind in "iuf":
        return _WORD
    return dtype


def pack(tree):
    """-> (buffers, layout). `buffers`: one host array `[S, W]` per dtype
    class, in order of first use, then the leaves that were device arrays
    already, last first. `layout`: (treedef, per leaf (buffer, offset, shape,
    dtype)); offset None marks a leaf that is its buffer, counted from the
    end. A tree without leaves gives no buffer. This runs once a search:
    nothing in the loop is slower than a reshape."""
    leaves, treedef = jtu.tree_flatten(tree)
    slots: dict = {}      # buffer dtype -> [index, [S, w] pieces, next offset]
    passed = []
    entries = []
    for a in leaves:
        if not isinstance(a, np.ndarray):
            if isinstance(a, jax.Array):
                passed.append(a)
                entries.append((-len(passed), None, None, None))
                continue
            a = np.asarray(a)
        shape, dtype = a.shape, a.dtype
        if not shape:
            raise ValueError("pack wants stacked [S, ...] leaves, got a scalar")
        width = a.size // shape[0] if shape[0] else 0
        bdt = _buffer_dtype(dtype)
        slot = slots.get(bdt)
        if slot is None:
            slot = slots[bdt] = [len(slots), [], 0]
        flat = a.reshape(shape[0], width)
        slot[1].append(flat if dtype == bdt else flat.view(bdt))
        entries.append((slot[0], slot[2], shape, dtype))
        slot[2] += width
    buffers = [p[0] if len(p) == 1 else np.concatenate(p, axis=1)
               for _, p, _ in slots.values()]
    return (*buffers, *reversed(passed)), (treedef, tuple(entries))


def packed_counts(layout) -> tuple[int, int]:
    """-> (host buffers, leaves packed into them) of a layout."""
    packed = [e for e in layout[1] if e[1] is not None]
    return len({e[0] for e in packed}), len(packed)


def unpack(buffers, layout):
    """The tree `pack` was given, rebuilt from its buffers: static slices on
    axis 1, a reshape, and a 32-bit bitcast for what rode as its bits.
    Traceable; under `jit` the leaves are device values."""
    treedef, entries = layout
    leaves = []
    for buf, off, shape, dtype in entries:
        x = buffers[buf]
        if off is not None:
            width = math.prod(shape[1:])
            x = jax.lax.slice_in_dim(x, off, off + width, axis=1)
            x = x.reshape(shape)
            if dtype != _WORD and _buffer_dtype(dtype) == _WORD:
                x = jax.lax.bitcast_convert_type(x, dtype)
        leaves.append(x)
    return jtu.tree_unflatten(treedef, leaves)


def pack_outputs(tree):
    """-> (buffers, layout), traceable. `buffers`: one flat device array per
    dtype class, in order of first use, every leaf raveled into it (a float32
    as its bits; a scalar is one element wide). `layout`: as `pack` gives it,
    the offsets and shapes those of whole leaves. A tree without leaves gives
    no buffer."""
    leaves, treedef = jtu.tree_flatten(tree)
    slots: dict = {}      # buffer dtype -> [index, flat pieces, next offset]
    entries = []
    for x in leaves:
        x = jnp.asarray(x)
        dtype = np.dtype(x.dtype)
        bdt = _buffer_dtype(dtype)
        slot = slots.setdefault(bdt, [len(slots), [], 0])
        flat = x.reshape(-1)
        slot[1].append(flat if dtype == bdt
                       else jax.lax.bitcast_convert_type(flat, bdt))
        entries.append((slot[0], slot[2], x.shape, dtype))
        slot[2] += x.size
    buffers = tuple(p[0] if len(p) == 1 else jnp.concatenate(p)
                    for _, p, _ in slots.values())
    return buffers, (treedef, tuple(entries))


def unpack_host(buffers, layout):
    """The tree `pack_outputs` was given, from its buffers once they are host
    arrays: a slice, a view and a reshape a leaf, no copy."""
    treedef, entries = layout
    leaves = []
    for buf, off, shape, dtype in entries:
        x = buffers[buf][off:off + math.prod(shape)]
        leaves.append((x if x.dtype == dtype else x.view(dtype)).reshape(shape))
    return jtu.tree_unflatten(treedef, leaves)
