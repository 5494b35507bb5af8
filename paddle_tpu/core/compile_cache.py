"""Where JAX's persistent compilation cache lives for the on-chip entry points.

A chip machine is handed out fresh per call, and a 7B-width train step plus
the serving engine's decode/prefill programs take minutes to compile cold, so
every entry point that runs on a chip (`chip_smoke.py`, `bench.py`, the
`inference/serve.py` CLI) calls `enable_compile_cache()` once, before its
first compile.

The cache is placed from OUTSIDE the program: when `JAX_COMPILATION_CACHE_DIR`
is set JAX reads it by itself and nothing is set in code; otherwise the cache
goes to one fixed directory inside the checkout — never a temp name, pid or
timestamp, because the path is part of the cache key and a directory that
moves never hits. Deliberately NOT called at `import paddle_tpu` or from
`tests/conftest.py`: CPU executables cached in a sandbox would travel with
the tree to a machine with other CPU features.

Stdlib + jax only, and loadable by file path: the standalone serving CLI
runs under an import hook that forbids every `paddle_tpu.*` import.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """`<checkout>/.jax_cache` (git-ignored), next to the `paddle_tpu`
    package directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call before the first compile of the process."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
