"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed partly by the directory that holds it, so the
directory must not move between runs: it is either the one the
environment names, or one fixed directory inside the checkout (listed in
``.gitignore``).  Entry points call ``setup_compile_cache()`` once,
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the in-checkout cache directory used when the environment names none
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable
    itself and nothing is set here; otherwise the cache goes to
    ``CHECKOUT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
