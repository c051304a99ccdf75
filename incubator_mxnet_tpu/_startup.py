"""Process start-up: where XLA's persistent compilation cache lives.

A cold `mx.serve` engine compiles one program per prefill bucket plus the
decode step, and a BERT-base train step is a compile of its own; a machine
that is thrown away after each run pays all of it again unless the
executables are kept on disk. The directory is part of the cache key, so it
must never move between runs: it is placed from outside with
``JAX_COMPILATION_CACHE_DIR`` (which jax reads itself — nothing is set in
code then), and otherwise it is one fixed directory inside the checkout.
"""
from __future__ import annotations

import os

__all__ = ["configure_compile_cache"]

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure_compile_cache():
    """Point jax's persistent compilation cache at its directory and return
    that directory. Must run before the first compile (the package calls it
    at import); calling it again is harmless."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
