"""Where JAX keeps its persistent compilation cache for this repo's entry points.

A cold engine bucket compiles three phase programs, tens of seconds on a
TPU.  JAX's persistent cache keys entries partly by the cache path, so a
path that moves never hits: the directory is either the one the caller
names in `JAX_COMPILATION_CACHE_DIR` (which JAX reads itself) or, on an
accelerator, the fixed `<repo>/.jax_cache` (git-ignored).

Entry points (`chip_smoke.py`, `repro.launch.mine`, `repro.launch.mine_serve`)
call `enable_compile_cache()` before their first compile.  Nothing calls it
at import, so tests and library users never write into the checkout.
"""

from __future__ import annotations

import os

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed fallback: <repo>/.jax_cache (this file is src/repro/launch/)
REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))


def enable_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its directory; returns it.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX already uses it and nothing
    here overrides it.  On the CPU backend no cache is placed (returns
    None): CPU compiles are cheap, and XLA:CPU logs a host-feature mismatch
    error for every entry it loads back.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
