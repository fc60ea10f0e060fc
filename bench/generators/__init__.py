"""Traffic generators, one module per kind.

A traffic mix is a data file ``bench/traffic/<mix>.json`` whose
``generator`` key names a module here.  Each module has
``generate(mix, *, seed, seconds, vocab) -> Work``.  The same seed
gives the same work; each module says what its seed draws.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from statistics import NormalDist

import numpy as np

__all__ = ["Req", "Work", "load", "draw", "quantiles", "rng_for", "jax_key"]


@dataclasses.dataclass
class Req:
    rid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int
    shared: int = 0         # leading tokens that repeat another prompt's


@dataclasses.dataclass
class Work:
    requests: list          # [Req], sorted by due time

    @property
    def prompt_lens(self) -> list[int]:
        return [len(r.prompt) for r in self.requests]

    def max_rows(self) -> int:
        """Most cache rows any request needs: prompt plus output."""
        return max(len(r.prompt) + r.max_new for r in self.requests)

    def min_rows(self) -> int:
        """Fewest cache rows a request holds once admitted."""
        return min(len(r.prompt) + 1 for r in self.requests)


def load(kind: str):
    """The generator module for ``kind`` (``bench/generators/<kind>.py``)."""
    return importlib.import_module(f"bench.generators.{kind}")


def rng_for(seed: int) -> np.random.Generator:
    """NumPy generator for any non-negative integer seed (beyond 32 bits)."""
    return np.random.default_rng(int(seed))


def jax_key(seed: int):
    """A JAX PRNG key for any non-negative integer seed (beyond 32 bits)."""
    import jax
    import jax.numpy as jnp

    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(s, jnp.uint32))


def draw(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer values drawn from ``dist``.

    ``dist`` is ``{"dist": "uniform", "min", "max"}`` (both ends
    included), ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
    (clipped to [min, max]) or ``{"dist": "fixed", "value"}``.
    """
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]))
    if kind == "uniform":
        return rng.integers(int(dist["min"]), int(dist["max"]) + 1, n)
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)
    raise ValueError(f"unknown distribution {kind!r}")


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer values of ``dist`` (as :func:`draw` takes it) at its
    quantiles (k + 1/2) / n: the same for every seed."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]))
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def zipf_weights(k: int, s: float) -> np.ndarray:
    """Probabilities of ranks 1..k proportional to 1/rank^s."""
    w = 1.0 / np.arange(1, k + 1) ** s
    return w / w.sum()


def n_requests(rate: float, seconds: float) -> int:
    return max(1, int(math.floor(rate * seconds + 0.5)))
