"""99th percentile of the gaps between consecutive output tokens of a
request, over all requests in the window (host clock)."""

from bench.metrics._shared import gaps, percentile


def read(run):
    p = percentile(gaps(run.record), 99)
    return None if p is None else 1e3 * p
