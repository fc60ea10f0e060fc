"""Small shared utilities with no dependencies on the engine layers."""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["normalize_cost_analysis", "compiled_costs", "place_compile_cache"]


def place_compile_cache() -> str:
    """Put JAX's persistent compilation cache in a fixed place.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own and is left
    alone; otherwise the cache lives in ``<repo>/.jax_cache``, inside
    the checkout this package runs from.  Entry points call this once
    at start-up; importing sets nothing.  Returns the directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        # src/repro/utils.py → the checkout's root
        path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def normalize_cost_analysis(cost) -> dict:
    """Normalize ``compiled.cost_analysis()`` output to a plain dict.

    jax 0.4.37-era jaxlibs return a single-element ``[dict]`` (one entry
    per computation), newer ones a bare ``dict``, and some backends
    ``None``.  Every reader of ``cost_analysis`` must go through this
    helper instead of re-discovering the list case.
    """
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def compiled_costs(compiled) -> dict:
    """``normalize_cost_analysis`` applied to a compiled executable."""
    return normalize_cost_analysis(compiled.cost_analysis())
