"""Placement of JAX's persistent compilation cache.

``pio-tpu train`` and ``pio-tpu deploy`` are separate processes and
deploy warms every batch bucket on every start, so without a cache on
disk each of them compiles everything it runs. A directory that moves
between runs never hits, so it is either the one the environment names
or one fixed path inside the checkout — never a temporary name.
"""

from __future__ import annotations

import os

#: fallback location, resolved like ``utils.native.NATIVE_DIR``
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)
#: bound on the fallback directory; JAX evicts least-recently-used
#: entries past it. A directory the environment names is the
#: environment's to bound (``JAX_COMPILATION_CACHE_MAX_SIZE``).
DEFAULT_CACHE_MAX_BYTES = 4 << 30


def cache_dir() -> str:
    """Where the cache is: ``JAX_COMPILATION_CACHE_DIR``, else the
    fixed path in the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def configure_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    the directory in use. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    has already read it and no directory is set here."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update(
            "jax_compilation_cache_max_size", DEFAULT_CACHE_MAX_BYTES
        )
    # the serving warm-up buckets each compile in well under JAX's 1 s
    # default floor, which would leave every deploy recompiling them;
    # a floor near their 0.2-0.3 s would persist a program on one start
    # and not the next, so there is none and the size bound above is
    # what keeps the directory from growing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()


def cache_entry_count(directory: str) -> int:
    """Number of compiled programs in ``directory`` (0 if absent)."""
    try:
        return sum(
            1 for name in os.listdir(directory) if name.endswith("-cache")
        )
    except OSError:
        return 0
