"""Small shared helpers."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def rank_within_run(sorted_keys: jax.Array) -> jax.Array:
    """Position of each element within its run of equal keys.

    ``sorted_keys`` must be sorted; used for balanced/capacity placement
    (k-means balancing, MoE expert dispatch).
    """
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.where(
        jnp.concatenate(
            [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]]),
        idx, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, starts)
    return idx - run_start


def pretty_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PB"


def tree_size_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "dtype"))


def pallas_interpret_default() -> bool:
    """Whether Pallas kernels should run in interpret mode here.

    On a TPU backend kernels always compile: ``REPRO_PALLAS_INTERPRET``
    may be unset or "0" there, and any other value raises instead of
    quietly timing the Python interpreter on the chip. Off the TPU the
    kernels (written for Mosaic) run in interpret mode, which the
    variable set to "0" turns off.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if jax.default_backend() == "tpu":
        if env not in (None, "0"):
            raise RuntimeError(
                f"REPRO_PALLAS_INTERPRET={env!r} asks for interpret mode "
                f"on a TPU backend; Pallas kernels compile on the TPU "
                f"(unset it or set it to '0')")
        return False
    return env != "0"


#: persistent compile cache of a checkout that sets no
#: ``JAX_COMPILATION_CACHE_DIR``: a fixed path, since the path is part of
#: the cache key (git-ignored)
CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Called at the start of ``main``, never on
    import. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and this sets no other directory; otherwise the cache lives
    at :data:`CHECKOUT_COMPILE_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_COMPILE_CACHE)
    return CHECKOUT_COMPILE_CACHE
