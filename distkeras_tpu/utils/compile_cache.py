"""Persistent XLA compilation cache.

The reference pays its per-executor startup cost in Keras model
deserialization + TF graph construction (reference: distkeras/workers.py ->
Worker.prepare_model, re-run in every Spark task). The TPU-shaped analog of
that cost is XLA compilation (tens of seconds per program on a v5e), and the
TPU-shaped fix is the persistent compilation cache: compiled executables are
keyed by HLO hash on disk, so re-creating a trainer (new jit closures, same
program) or re-running a harness hits the cache instead of the compiler.

Where the cache lives is decided from outside: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it itself; nothing here overrides it), otherwise
``<checkout>/.jax_cache`` — a fixed path, because the path is part of the
cache's key and a directory that moves never hits. ``chip_smoke.py``, the
harnesses and the examples call this before their first compile; the
library's classes never set a cache themselves.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: two levels up from distkeras_tpu/utils/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache(platform: str | None = None) -> str | None:
    """Switch JAX's persistent compilation cache on. Returns the cache
    directory, or None when skipped. Safe to call repeatedly.

    ``platform``: the backend name, or None to ask JAX (which
    initializes the backend). The cache stays off for "cpu": XLA:CPU AOT
    entries embed compile-machine feature lists that warn (and can
    SIGILL) on reload, and CPU compiles of these programs take seconds."""
    import jax

    if platform is None:
        platform = jax.default_backend()
    if platform == "cpu":
        return None

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program that takes meaningful compile time; the default
    # threshold (1s+) skips the small-but-numerous ragged-window variants
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return path
