"""Empirical autotuner + persistent dispatch cache for contraction kernels.

The paper's Figs. 5–8 show the fastest evaluation mode for a contraction
is shape-dependent and not reliably predicted by static rules.  This
subsystem closes the loop empirically:

:mod:`repro.tuning.candidates` — enumerate legal (strategy × backend ×
    tile-config) executions of a spec, VMEM-validated;
:mod:`repro.tuning.measure`    — warmup + median-of-k timing harness;
:mod:`repro.tuning.cache`      — persistent JSON store (canonical keys,
    atomic writes, versioned schema, corruption-tolerant loads);
:mod:`repro.tuning.dispatch`   — ``tuned_contract`` / :class:`Dispatcher`
    tying them together under a :data:`TuningPolicy`
    (off / cached / measure).

Entry points upward: ``contract(..., strategy="tuned")``,
``xeinsum(..., optimize="tuned")``, and the serving engine's warm-up pass
(``ServeEngine(..., pretune=True)``).
"""

from repro.tuning.cache import (
    SCHEMA_VERSION,
    TuningCache,
    canonical_key,
    valid_entry,
)
from repro.tuning.candidates import (
    Candidate,
    enumerate_candidates,
    validate_tiles,
)
from repro.tuning.dispatch import (
    Dispatcher,
    TuningPolicy,
    default_cache_path,
    get_dispatcher,
    pick_best,
    set_dispatcher,
    tuned_contract,
)
from repro.tuning.measure import Measurement, measure_candidate, time_callable

__all__ = [
    "SCHEMA_VERSION",
    "TuningCache",
    "canonical_key",
    "valid_entry",
    "Candidate",
    "enumerate_candidates",
    "validate_tiles",
    "Dispatcher",
    "TuningPolicy",
    "default_cache_path",
    "get_dispatcher",
    "set_dispatcher",
    "tuned_contract",
    "pick_best",
    "Measurement",
    "measure_candidate",
    "time_callable",
]
