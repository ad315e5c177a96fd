"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is found again only under the same cache path, so the
path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), else
``<checkout>/.jax_cache`` (gitignored).  It never holds a temp name, a
pid or a time.

A program's op names (its ``named_scope`` paths, which the profiler
reports and the benchmark's trace reduction reads) are part of its cache
key: by default JAX leaves them out, and a program that differs from a
cached one only in its scopes would load the cached executable with the
other program's names.  Source locations enter the key by file name
alone, so a checkout at another path still finds its programs.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
