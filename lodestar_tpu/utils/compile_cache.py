"""Where JAX keeps its persistent compilation cache.

Every launcher (bench.py, chip_smoke.py, replay.py, bench_configs.py,
__graft_entry__.py, dev/ scripts, tests/conftest.py) calls `configure()`
once before its first compile.  The directory is placed from outside:
`JAX_COMPILATION_CACHE_DIR` when the environment sets it, otherwise
`<checkout>/.jax_cache` (gitignored).  A fixed path matters because the
path is part of what a later process must find again.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache"
    )


def configure() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()` and
    return that directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
