"""Where compiled programs and measured kernel choices are kept.

One ignored directory inside the checkout holds everything this tree
caches between runs: JAX's persistent compilation cache and the
kernel-autotune winners.  The path is part of the compilation cache's
key, so it is fixed — no temp dir, pid or time in it — and a second
process of the same command (or the next command on a machine that
keeps its disk) finds what the first one compiled.

``JAX_COMPILATION_CACHE_DIR`` places the compilation cache from
outside: when it is set jax reads it itself and nothing is set here.
"""
from __future__ import annotations

import os

#: <checkout>/.cache — listed in .gitignore
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache")


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory and
    return that directory.  Called by every entry point that compiles
    for the chip (chip_smoke.py, bench.py, the bench tools, the servers'
    ``start``) — never at package import, and tests place it in a temp
    dir of the session through the variable (tests/conftest.py)."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    path = os.path.join(CACHE_ROOT, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
