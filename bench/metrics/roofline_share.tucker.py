"""Per cent of the roofline a whole decomposition reaches: the summed
least times of its contractions (unpadded, each the longer of its
operations at bf16 peak and its bytes at HBM bandwidth; bench/counts.py)
over tucker_ms of the same run (host clock).  Eigendecompositions and
the error norm are not counted as work."""

from bench import counts


def read(run):
    r, c = run.record, run.conf
    if not r.get("jobs") or run.peaks is None:
        return None
    bound = sum(run.peaks.bound_s(x.flops, x.nbytes) for x in
                counts.hooi_contractions(c["n"], c["ranks"], c["n_iter"]))
    return 100.0 * bound / (r["window_s"] / r["jobs"])
