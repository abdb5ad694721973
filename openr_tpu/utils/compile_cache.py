"""Persistent XLA compilation cache, placed from outside the program.

Every jax entry point (``chip_smoke.py``, ``chipbench.run``, the
daemon, the test conftest) runs in a fresh process and would re-pay
every jit compile; a cold engine build at 10k nodes is mostly compile.
jax's persistent cache keys executables by computation, platform and
version, so pointing every process at one directory lets the second one
skip to execution.

Where the cache lives is the caller's environment's decision, not the
code's: ``JAX_COMPILATION_CACHE_DIR`` when set, and otherwise the fixed
``<checkout>/.jax_cache`` (git-ignored). Never a home directory, a
temporary name, a pid or a time: the machines that hold the chip keep no
home, and a directory that moves never hits. The cache grows without
bound — see docs/RUNBOOK.md for the pruning note.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable() -> str:
    """Point jax's persistent compilation cache at ``cache_dir()`` and
    return that directory. Idempotent; jax's own threshold (compiles
    under a second are not stored) is left alone."""
    import jax

    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
