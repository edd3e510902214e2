"""Where the persistent XLA compile cache lives — one rule for every
entry point that compiles (train.py via the Trainer, bench.py,
tools/serve_http.py, tools/generate_cli.py, chip_smoke.py).

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
(or anywhere else in the repo) sets another directory. Unset: one fixed
directory inside the checkout, because the path is part of the cache's
key — a temporary name, a pid or a timestamp would never hit. The
caller's preference (``obs.compile_cache_dir``, the launcher's per-worker
directory) only replaces that default; it never overrides the
environment. A process that runs with JAX's own
``jax_enable_compilation_cache`` off (the test suite, tests/conftest.py)
gets no cache at all.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — listed in .gitignore and .chiprunignore.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable(preferred: str = "") -> str | None:
    """Turn the persistent cache on and return the directory in use
    (None when the cache is disabled for this process)."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    path = preferred or DEFAULT_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
