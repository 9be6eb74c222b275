"""Where JAX keeps its persistent compilation cache for this checkout.

A cold run on the chip spends much of its time compiling (the rebuild at
capacity 2^25 alone takes minutes), so every entry point that touches the
device calls ``use_compile_cache()`` before its first compile.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it as its cache
  directory; nothing else is set in code, so whoever runs the program
  decides where the cache lives.
* Unset: ``<checkout>/.jax_cache`` (gitignored).  The path is fixed:
  a later run looks for its programs where this one wrote them, so a
  per-run or temporary directory would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
