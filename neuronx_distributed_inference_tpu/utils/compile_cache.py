"""Where the persistent XLA compilation cache lives — the ONE place in the
tree that points jax at a cache directory.

Rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, that directory (whoever runs
the program places the cache — a serving host's shared volume, the test
suite's per-user temp dir); otherwise one fixed, git-ignored directory at
the root of the checkout. The path is part of jax's cache key, so it must
not move between runs — no ``mkdtemp``, no per-model directory.

The application, ``benchmark/run.py`` and ``tests/conftest.py``
all call :func:`configure_compile_cache`; nothing else may set
``jax_compilation_cache_dir`` (pinned by tests/test_chip_rules.py).
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fixed fallback: ``<checkout>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def resolve_compile_cache_dir() -> str:
    """The directory the rule above selects (no side effects)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at the resolved directory
    and return it. Idempotent; every executable is cached regardless of
    how long it took to build, so a second process walking the same graph
    ladder builds nothing."""
    path = resolve_compile_cache_dir()
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax decides once, at the first compile, whether a cache is in
        # use; a process that compiled before this call must re-decide
        from jax.experimental.compilation_cache import \
            compilation_cache as cc
        cc.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
