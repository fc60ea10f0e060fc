"""Per cent of the chip's bf16 peak that a whole decomposition reaches:
the operations of its contractions (bench/counts.py) over tucker_ms of
the same run (host clock)."""

from bench import counts


def read(run):
    r, c = run.record, run.conf
    if not r.get("jobs") or run.peaks is None:
        return None
    flops = sum(x.flops for x in counts.hooi_contractions(
        c["n"], c["ranks"], c["n_iter"]))
    return 100.0 * flops / (r["window_s"] / r["jobs"]) / run.peaks.flops
