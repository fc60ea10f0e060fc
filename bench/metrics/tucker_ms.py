"""Milliseconds per decomposition: the window over the whole
decompositions completed in it (host clock)."""


def read(run):
    r = run.record
    return 1e3 * r["window_s"] / r["jobs"] if r.get("jobs") else None
