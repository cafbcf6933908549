"""Where compiled XLA programs are kept between processes.

Every shape the data path meets — a segment length, a tail chunk, a
column-elided matrix — compiles a new program inside a client write or
read, and a daemon restart would pay for all of them again. One rule,
applied by every process before its first compile: when
``JAX_COMPILATION_CACHE_DIR`` is set the operator placed the cache and
jax reads that variable itself, so no directory is set in code;
otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored) — never a temp name, pid or
timestamp, because a directory that moves never hits.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and keep every program in
    it; returns the directory in use. Call before the first compile:
    jax opens the cache once per process."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the defaults skip programs that compiled in under a second or are
    # small: exactly the per-segment programs the data path is made of
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
