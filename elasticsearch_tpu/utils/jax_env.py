"""JAX runtime configuration for the framework.

int64 DocValues (dates are epoch millis ~2^41, longs are arbitrary) need
64-bit integer device arrays, so x64 must be enabled; XLA lowers s64 on TPU
to u32 pairs. All floating-point arrays in this codebase use explicit
float32/bfloat16 dtypes, so enabling x64 does not introduce f64 compute
anywhere on the hot path.
"""

import os

import jax

_done = False
_cache_done = False

# <repo>/.jax_cache (git-ignored); fixed so a second run's keys match
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def ensure_x64():
    global _done
    if not _done:
        jax.config.update("jax_enable_x64", True)
        _done = True


def enable_compile_cache():
    """Persistent XLA compilation cache across processes.

    TPU compiles for the large-shard query programs run 20-200s, so server
    restarts and repeated bench runs must not re-pay them. The analog of
    the reference warming node query caches on restart; here the compiled
    executable itself is the cache unit.

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and no
    directory is set here; otherwise the cache lives at one fixed path
    inside the checkout (the path is part of the cache key, so it must
    not move between runs). An unwritable directory is an error: a server
    that silently recompiles everything is not the deployed system."""
    global _cache_done
    if _cache_done:
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _cache_done = True


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with the varying-manual-axes check off: the Pallas
    kernels called inside the sharded regions declare plain
    `ShapeDtypeStruct` outputs (no `vma`), which the check rejects."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
