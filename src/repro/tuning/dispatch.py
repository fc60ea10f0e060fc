"""Empirical dispatch: pick a contraction's execution mode by measurement.

``tuned_contract(spec, A, B)`` (or ``contract(..., strategy="tuned")``)
routes a pairwise contraction through a :class:`Dispatcher`:

1. look up the canonical key (spec-shape class, dims, dtype, platform) in
   the persistent :class:`~repro.tuning.cache.TuningCache`;
2. on a **hit**, execute the recorded winner — no measurement, ever;
3. on a **miss**, behavior follows the :data:`TuningPolicy`:

   * ``"measure"`` (default) — enumerate legal candidates
     (:mod:`repro.tuning.candidates`), time each
     (:mod:`repro.tuning.measure`), persist the results, run the winner;
   * ``"cached"`` — no measurement; fall back to the analytic
     ``strategy="auto"`` plan (warm caches only, e.g. CI);
   * ``"off"`` — always the analytic plan (a kill switch).

Under a ``jit`` trace operands are abstract and cannot be timed: misses
silently degrade to the analytic plan (hits still dispatch the winner —
the winner's identity is static, so it traces fine).  Counters
(``hits`` / ``misses`` / ``measurements``) are exposed
on the dispatcher so callers can assert "a warm cache performs zero new
measurements".

Demo::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.tuning.dispatch --demo
"""

from __future__ import annotations

import argparse
import os
import warnings
from typing import Iterable, Literal, get_args

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.notation import ContractionSpec, parse_spec
from repro.obs import trace as _trace
from repro.tuning.cache import TuningCache, canonical_key
from repro.tuning.candidates import Candidate, enumerate_candidates
from repro.tuning.measure import measure_candidates

__all__ = [
    "TuningPolicy",
    "Dispatcher",
    "tuned_contract",
    "get_dispatcher",
    "set_dispatcher",
    "default_cache_path",
    "path_cost",
    "pick_best",
    "ANALYTIC_FLOPS_PER_US",
]

TuningPolicy = Literal["off", "cached", "measure"]
_POLICIES = get_args(TuningPolicy)

#: legacy flops→µs bridge (10 GFLOP/s).  :func:`path_cost` no longer
#: uses it — unmeasured steps are priced by the per-step roofline bound
#: (:func:`repro.obs.roofline.roofline_bound_us`, real hardware
#: constants).  Kept exported for external callers of the old pricing.
ANALYTIC_FLOPS_PER_US = 1.0e4

#: cache keys whose entry turned out structurally dangling (``best`` not
#: in ``results`` — possible after hand edits or buggy external merges):
#: each is warned about once per process, then silently treated as a miss.
_WARNED_DANGLING: set[str] = set()


#: ties break toward the analytic plan: a challenger must beat
#: ``strategy="auto"`` by more than this factor to dethrone it.  With
#: measurement noise, a hair-thin "win" is as likely to be a loss — and
#: auto is the choice the rest of the stack reasons about.
TIE_MARGIN = 0.85

_AUTO_KEY = "xla:auto"


def pick_best(results: dict) -> str:
    """Winner over a per-candidate µs map, ties broken toward ``xla:auto``
    (see :data:`TIE_MARGIN`)."""
    best = min(results, key=results.get)
    if (
        best != _AUTO_KEY
        and _AUTO_KEY in results
        and results[best] > TIE_MARGIN * results[_AUTO_KEY]
    ):
        best = _AUTO_KEY
    return best


def default_cache_path() -> str:
    """``$REPRO_TUNING_CACHE``, else ``~/.cache/repro/tuning.json``."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "tuning.json")


class Dispatcher:
    """Cache-backed empirical dispatcher for pairwise contractions.

    Args:
      cache: a :class:`TuningCache`, a path for one, or ``None`` for an
        in-memory cache.
      policy: ``"measure"`` | ``"cached"`` | ``"off"`` (see module doc);
        anything else raises ``ValueError``.
      backends: backends candidates may use; default
        :func:`~repro.tuning.candidates.default_backends` (XLA-only off
        TPU — Pallas interpret mode is never the wall-clock winner there).
      iters/warmup: measurement repeats per candidate.
      audit_transposes: scan each measured candidate's optimized HLO for
        surviving transposes and store the counts in the cache entry —
        a Fig. 1-style regression signal.
    """

    def __init__(
        self,
        cache: TuningCache | str | os.PathLike | None = None,
        *,
        policy: TuningPolicy = "measure",
        backends: tuple[str, ...] | None = None,
        iters: int = 5,
        warmup: int = 2,
        audit_transposes: bool = False,
    ):
        if not isinstance(cache, TuningCache):
            cache = TuningCache(cache)
        self.cache = cache
        self.policy = policy
        self.backends = backends
        self.iters = iters
        self.warmup = warmup
        self.audit_transposes = audit_transposes
        self.hits = 0
        self.misses = 0
        self.measurements = 0   # individual candidate timings performed

    @property
    def policy(self) -> TuningPolicy:
        return self._policy

    @policy.setter
    def policy(self, policy: TuningPolicy) -> None:
        if policy not in _POLICIES:
            raise ValueError(
                f"unknown tuning policy {policy!r}; choose from {_POLICIES}")
        self._policy = policy

    # ---------------------------------------------------------------- lookup
    def lookup(self, spec, dims, dtype) -> tuple[Candidate, float] | None:
        """Cached (winning candidate, median µs) or ``None`` — no counters.

        Hardened against dangling entries whose ``best`` key is missing
        from ``results`` or unparseable (possible in hand-edited
        caches): those are treated as a miss with
        a once-per-key warning, never a ``KeyError`` on the serve path.
        """
        key = canonical_key(spec, dims, dtype)
        entry = self.cache.get(key)
        if entry is None:
            return None
        try:
            best = entry["best"]
            us = float(entry["results"][best])
            return Candidate.from_key(best), us
        except (KeyError, TypeError, ValueError):
            if key not in _WARNED_DANGLING:
                _WARNED_DANGLING.add(key)
                warnings.warn(
                    f"tuning cache entry for {key!r} is dangling "
                    f"(best={entry.get('best')!r} not usable); treating as "
                    f"a miss"
                )
            return None

    def step_us(self, spec, dims, dtype) -> float | None:
        """Measured best µs for one contraction, for path re-ranking."""
        hit = self.lookup(spec, dims, dtype)
        return hit[1] if hit else None

    # ------------------------------------------------------------------ tune
    def tune(self, spec, A, B) -> dict:
        """Measure every not-yet-measured legal candidate and persist.

        Incremental across schema growth: when the cache already holds an
        entry for this key (e.g. written before a new strategy existed),
        its per-candidate timings are kept and only the *new* candidate
        keys are timed — then the winner is re-picked over the merged
        results.  Candidates are timed with interleaved sampling
        (:func:`~repro.tuning.measure.measure_candidates`) so machine
        drift cannot bias the winner.  Counts one measurement per newly
        timed candidate.  Returns the stored entry.

        A prior entry flagged ``"predicted"`` (written by an older
        cost-model policy) is *discarded*, not merged — its µs are
        guesses, and keeping them would pass a guess off as a
        measurement.
        """
        cs = parse_spec(spec) if isinstance(spec, str) else spec
        from repro.core.contract import infer_dims

        dims = infer_dims(cs, A, B)
        dtype = jnp.result_type(A.dtype, B.dtype)
        key = canonical_key(cs, dims, dtype)
        with _trace.span("tune", "tuning") as sp:
            cands = enumerate_candidates(
                cs, dims, dtype=dtype, backends=self.backends)
            prior = self.cache.get(key)
            if prior is not None and prior.get("predicted"):
                prior = None
            results = dict(prior["results"]) if prior else {}
            transposes = dict(prior.get("transposes") or {}) if prior else {}
            todo = [c for c in cands if c.key() not in results]
            measured = (
                measure_candidates(
                    todo, cs, A, B, iters=self.iters, warmup=self.warmup,
                    audit_transposes=self.audit_transposes)
                if todo
                else {}
            )
            self.measurements += len(measured)
            results.update({k: m.us for k, m in measured.items()})
            transposes.update({
                k: m.transposes for k, m in measured.items()
                if m.transposes is not None
            })
            best = pick_best(results)
            entry = {"best": best, "results": results}
            if transposes:
                entry["transposes"] = transposes
            self.cache.put(key, entry)
            if sp:
                sp.set(spec=cs.spec_str(), n_candidates=len(cands),
                       n_measured=len(measured), winner=best,
                       best_us=float(results[best]))
            return entry

    # -------------------------------------------------------------- contract
    def contract(
        self,
        spec: str | ContractionSpec,
        A,
        B,
        *,
        preferred_element_type=jnp.float32,
        out_dtype=None,
    ):
        """Execute one contraction under the tuning policy (see module doc)."""
        from repro.core.contract import contract, infer_dims

        cs = parse_spec(spec) if isinstance(spec, str) else spec
        dims = infer_dims(cs, A, B)
        dtype = jnp.result_type(A.dtype, B.dtype)

        def analytic():
            return contract(
                cs, A, B, strategy="auto",
                preferred_element_type=preferred_element_type, out_dtype=out_dtype,
            )

        if self.policy == "off":
            return analytic()

        hit = self.lookup(cs, dims, dtype)
        if hit is None:
            self.misses += 1
            concrete = not (
                isinstance(A, jax.core.Tracer) or isinstance(B, jax.core.Tracer)
            )
            if _trace.enabled():
                _trace.instant(
                    "tuning_miss", "tuning", spec=cs.spec_str(),
                    policy=self.policy, concrete=concrete,
                )
            if self.policy != "measure" or not concrete:
                return analytic()
            entry = self.tune(cs, A, B)
            cand = Candidate.from_key(entry["best"])
        else:
            self.hits += 1
            cand = hit[0]
            if _trace.enabled():
                from repro.obs.roofline import (
                    contraction_record, device_peaks, measured_fraction,
                )

                rec = contraction_record(cs, dims, dtype)
                measured_us = hit[1]
                share = {}
                peaks = device_peaks()
                if peaks is not None:  # a CPU timing is no share
                    share["roofline_fraction"] = measured_fraction(
                        rec["flops"], rec["bytes"], measured_us, peaks)
                _trace.instant(
                    "tuning_hit", "tuning", spec=cs.spec_str(),
                    winner=cand.key(), measured_us=measured_us,
                    flops=rec["flops"], bytes=rec["bytes"],
                    intensity=rec["intensity"], **share,
                )
        return contract(
            cs, A, B,
            strategy=cand.strategy, backend=cand.backend,
            tiles=cand.tiles_dict or None,
            preferred_element_type=preferred_element_type, out_dtype=out_dtype,
        )

    # --------------------------------------------------------------- pretune
    def pretune(self, records: Iterable[tuple], *, seed: int = 0) -> dict:
        """Warm the cache for a contraction working set before serving.

        ``records`` are ``(spec_str, dims, dtype_str)`` tuples, e.g. from
        :func:`repro.core.contract.record_contractions` around a model
        trace.  Deduplicates by canonical key, skips existing entries, and
        measures the rest on synthetic operands.  Returns summary stats.
        """
        rng = np.random.default_rng(seed)
        stats = {"unique": 0, "cached": 0, "tuned": 0, "skipped": 0}
        seen: set[str] = set()
        with _trace.span("pretune", "tuning") as sp:
            for spec_str, dims, dtype_str in records:
                cs = parse_spec(spec_str)
                dtype = jnp.dtype(dtype_str)
                key = canonical_key(cs, dims, dtype)
                if key in seen:
                    continue
                seen.add(key)
                stats["unique"] += 1
                if key in self.cache:
                    stats["cached"] += 1
                    continue
                if self.policy != "measure":
                    stats["skipped"] += 1
                    continue
                A = jnp.asarray(
                    rng.standard_normal([dims[m] for m in cs.a_modes]), dtype
                )
                B = jnp.asarray(
                    rng.standard_normal([dims[m] for m in cs.b_modes]), dtype
                )
                self.tune(cs, A, B)
                stats["tuned"] += 1
            if sp:
                sp.set(**stats)
        return stats

    # ----------------------------------------------------------------- stats
    @property
    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "measurements": self.measurements,
            "entries": len(self.cache),
            "policy": self.policy,
        }

    def reset_counters(self) -> None:
        """Zero the hit/miss/measurement counters (cache untouched).

        The serving runtime calls this after its pretune+precompile
        warm-up so the serve-phase counters start from a deterministic
        zero (see ``ServingRuntime.pretune_stats["dispatcher"]`` for the
        warm-up's own numbers)."""
        self.hits = 0
        self.misses = 0
        self.measurements = 0


# -------------------------------------------------------------- path pricing
def path_cost(steps, dims: dict, dtype, dispatcher: "Dispatcher | None" = None
              ) -> tuple[float, int]:
    """Measured-cost price of a contraction path: ``(total µs, -n_measured)``.

    ``steps`` may be :class:`~repro.core.einsum.PathStep` or
    :class:`~repro.core.program.ContractionStep` objects — anything with a
    pairwise ``spec`` and analytic ``flops``.  Steps with a cache entry
    cost their recorded best µs; cold steps are priced by the per-step
    **roofline bound**
    (:func:`repro.obs.roofline.roofline_bound_us` — hardware ceilings,
    not the old one-size 10 GFLOP/s :data:`ANALYTIC_FLOPS_PER_US`
    scalar, which underpriced memory-bound steps by orders of
    magnitude).  The second component prefers the path with more
    cache-backed (trusted) steps on µs ties.  This is the objective
    behind ``optimize="tuned"`` — both the eager re-rank
    (:func:`repro.core.einsum.contraction_path`) and the
    compiled-program pass (:class:`repro.core.passes.TunedRerankPass`).
    """
    from repro.obs.roofline import contraction_record

    disp = dispatcher or get_dispatcher()
    total, trusted = 0.0, 0
    for s in steps:
        cs = s.spec if isinstance(s.spec, ContractionSpec) else parse_spec(s.spec)
        us = None
        if cs.c_modes and cs.a_modes and cs.b_modes:
            us = disp.step_us(cs, dims, dtype)
        if us is not None:
            total += us
            trusted += 1
            continue
        total += contraction_record(cs, dims, dtype)["roofline_bound_us"]
    return (total, -trusted)


# ------------------------------------------------------------------ default
_DEFAULT: Dispatcher | None = None


def get_dispatcher() -> Dispatcher:
    """The process-wide dispatcher behind ``strategy="tuned"``.

    Created lazily against :func:`default_cache_path`; replace it with
    :func:`set_dispatcher` (tests and the serving warm-up do).
    """
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Dispatcher(default_cache_path())
    return _DEFAULT


def set_dispatcher(dispatcher: Dispatcher | None) -> None:
    """Install (or clear, with ``None``) the process-wide dispatcher."""
    global _DEFAULT
    _DEFAULT = dispatcher


def tuned_contract(
    spec: str | ContractionSpec,
    A,
    B,
    *,
    dispatcher: Dispatcher | None = None,
    preferred_element_type=jnp.float32,
    out_dtype=None,
):
    """Module-level convenience: dispatch through ``dispatcher`` (default:
    the process-wide one)."""
    d = dispatcher or get_dispatcher()
    return d.contract(
        spec, A, B,
        preferred_element_type=preferred_element_type, out_dtype=out_dtype,
    )


# ---------------------------------------------------------------------- demo
def _demo(cache_path: str, size: int) -> None:
    from repro.core.table2 import CASES

    disp = Dispatcher(cache_path, iters=5, warmup=2)
    dims = {m: size for m in "mnpk"}
    rng = np.random.default_rng(0)
    print(f"# tuning cache: {cache_path}  (platform={jax.default_backend()})")
    for label in ("1.1", "1.3", "2.4", "3.4"):
        rm = CASES[label].row_major()
        cs = parse_spec(rm)
        A = jnp.asarray(rng.standard_normal([dims[m] for m in cs.a_modes]), jnp.float32)
        B = jnp.asarray(rng.standard_normal([dims[m] for m in cs.b_modes]), jnp.float32)
        disp.contract(cs, A, B)
        cand, us = disp.lookup(cs, dims, jnp.float32)
        entry = disp.cache.get(canonical_key(cs, dims, jnp.float32))
        losers = {k: round(v, 1) for k, v in sorted(entry["results"].items())}
        print(f"case {label} {rm}: winner={cand.key()} ({us:.1f} µs)  all={losers}")
    print(f"# stats: {disp.stats}")
    disp2 = Dispatcher(cache_path)
    for label in ("1.1", "1.3", "2.4", "3.4"):
        rm = CASES[label].row_major()
        cs = parse_spec(rm)
        A = jnp.asarray(rng.standard_normal([dims[m] for m in cs.a_modes]), jnp.float32)
        B = jnp.asarray(rng.standard_normal([dims[m] for m in cs.b_modes]), jnp.float32)
        disp2.contract(cs, A, B)
    print(f"# second run (same cache): {disp2.stats}  <- zero new measurements")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="contraction autotuner CLI")
    ap.add_argument("--demo", action="store_true",
                    help="tune a few Table II cases and show the cache round-trip")
    ap.add_argument("--cache", default=None, help="cache path (default: env/XDG)")
    ap.add_argument("--size", type=int, default=64, help="mode size for --demo")
    args = ap.parse_args(argv)
    if args.demo:
        _demo(args.cache or default_cache_path(), args.size)
    else:
        ap.print_help()


if __name__ == "__main__":
    main()
