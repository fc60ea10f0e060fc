"""Persistent tuning cache: a versioned, corruption-tolerant JSON store.

Keys are canonical — mode letters are renamed to a fixed alphabet in
order of first appearance, so ``"mk,pkn->pmn"`` and ``"ab,cbd->cad"`` at
the same dims share one entry — and qualified by dims signature, operand
dtype, and the JAX backend platform (a CPU-measured winner says nothing
about TPU).  Values record every measured candidate's median µs plus the
winner, so the einsum path optimizer can re-rank steps from the same
entries the dispatcher executes from.

Durability rules:

* **atomic writes** — serialize to a sibling temp file, fsync, then
  ``os.replace`` (POSIX-atomic): a crash mid-save leaves the previous
  cache intact, never a half-written JSON;
* **corruption-tolerant loads** — unreadable files, invalid JSON, wrong
  schema versions, or structurally bogus payloads degrade to an *empty*
  cache with a ``warnings.warn`` (the autotuner re-measures; it never
  refuses to start).
"""

from __future__ import annotations

import itertools
import json
import os
import string
import tempfile
import warnings

import jax
import jax.numpy as jnp

from repro.core.notation import ContractionSpec, parse_spec

__all__ = [
    "SCHEMA_VERSION",
    "TuningCache",
    "canonical_key",
    "canonical_spec",
    "valid_entry",
]

SCHEMA_VERSION = 1

#: per-process unique ids for cache instances (see TuningCache.fingerprint)
_CACHE_UIDS = itertools.count()


def canonical_spec(spec: str | ContractionSpec, dims: dict) -> tuple[str, tuple]:
    """(renamed spec string, dims signature) — the shape-equivalence class.

    Modes are renamed ``a, b, c, …`` in order of first appearance across
    ``A‖B‖C``; the dims signature lists sizes in that same order.
    """
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    order = list(dict.fromkeys(cs.a_modes + cs.b_modes + cs.c_modes))
    ren = {m: string.ascii_lowercase[i] for i, m in enumerate(order)}

    def r(modes: str) -> str:
        return "".join(ren[m] for m in modes)

    sig = tuple(int(dims[m]) for m in order)
    return f"{r(cs.a_modes)},{r(cs.b_modes)}->{r(cs.c_modes)}", sig


def canonical_key(
    spec: str | ContractionSpec,
    dims: dict,
    dtype,
    platform: str | None = None,
) -> str:
    """Full cache key: canonical spec | dims | dtype | platform."""
    cspec, sig = canonical_spec(spec, dims)
    platform = platform or jax.default_backend()
    return f"{cspec}|{'x'.join(map(str, sig))}|{jnp.dtype(dtype).name}|{platform}"


def valid_entry(entry) -> bool:
    """Structural validation of one cache entry.

    ``best`` must be a parseable candidate key present in ``results``,
    and every result a number.  Extra keys ride along untouched — the
    transpose audit adds ``transposes``, and caches written by an older
    cost-model policy carry ``predicted``/``confidence`` — so caches
    grown by other code versions stay loadable.
    """
    if not (
        isinstance(entry, dict)
        and isinstance(entry.get("best"), str)
        and isinstance(entry.get("results"), dict)
        and all(
            isinstance(k, str) and isinstance(v, (int, float))
            for k, v in entry["results"].items()
        )
        and entry["best"] in entry["results"]
    ):
        return False
    from repro.tuning.candidates import Candidate  # deferred: no cycle

    try:  # "best" must name an executable candidate, not arbitrary text
        Candidate.from_key(entry["best"])
    except (ValueError, TypeError):
        return False
    return True


class TuningCache:
    """Dict-like persistent store mapping canonical keys to entries.

    An entry is ``{"best": candidate_key, "results": {candidate_key: us}}``.
    With ``path=None`` the cache is purely in-memory (the dispatcher's
    default for throwaway tuning).
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = os.fspath(path) if path is not None else None
        self.entries: dict[str, dict] = {}
        self._uid = next(_CACHE_UIDS)   # distinguishes cache instances
        self._version = 0               # bumped on every put
        if self.path is not None:
            self._load()

    def fingerprint(self) -> tuple:
        """A value that changes whenever this cache's content may have:
        (instance uid, mutation counter, size).  Consumers that bake
        decisions off cache content (the compiled-program signature for
        ``tuned`` programs) key on this so content changes — including
        same-size overwrites or a swapped-in cache instance — invalidate
        them."""
        return (self._uid, self._version, len(self.entries))

    # ------------------------------------------------------------- load/save
    def _load(self) -> None:
        if not os.path.exists(self.path):
            return
        try:
            with open(self.path, encoding="utf-8") as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            warnings.warn(
                f"tuning cache {self.path!r} is unreadable ({e}); starting empty"
            )
            return
        if not isinstance(payload, dict) or payload.get("schema") != SCHEMA_VERSION:
            got = payload.get("schema") if isinstance(payload, dict) else type(payload)
            warnings.warn(
                f"tuning cache {self.path!r} has schema {got!r} "
                f"(expected {SCHEMA_VERSION}); starting empty"
            )
            return
        entries = payload.get("entries")
        if not isinstance(entries, dict):
            warnings.warn(
                f"tuning cache {self.path!r} has no valid 'entries'; starting empty"
            )
            return
        kept = {k: v for k, v in entries.items() if valid_entry(v)}
        dropped = len(entries) - len(kept)
        if dropped:
            warnings.warn(
                f"tuning cache {self.path!r}: dropped {dropped} malformed entries"
            )
        self.entries = kept

    def save(self) -> None:
        """Atomically persist to ``self.path`` (no-op for in-memory caches)."""
        if self.path is None:
            return
        payload = {"schema": SCHEMA_VERSION, "entries": self.entries}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(self.path) + ".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # ------------------------------------------------------------ dict-like
    def get(self, key: str) -> dict | None:
        return self.entries.get(key)

    def put(self, key: str, entry: dict, *, persist: bool = True) -> None:
        if not valid_entry(entry):
            raise ValueError(f"malformed tuning entry for {key!r}: {entry!r}")
        self.entries[key] = entry
        self._version += 1
        if persist:
            self.save()

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    def __len__(self) -> int:
        return len(self.entries)
