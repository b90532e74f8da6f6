"""Where JAX's persistent compilation cache lives.

A cold full-width run compiles one whole serving step per width on the
ladder; the persistent cache lets a second process (or a second run on
the same machine) load those executables instead. A later run finds them
only if the path does not move between runs, so it is either what
``JAX_COMPILATION_CACHE_DIR`` says — then JAX reads the variable itself
and nothing is set here — or the fixed ``<repo>/.jax_cache``, worked out
from this file's location (gitignored).
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.normpath(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
