"""Arithmetic the metric readers share."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile (linear between order statistics), or
    None for no values."""
    return float(np.percentile(np.asarray(values, float), q)) if len(
        values) else None


def gaps(record: dict) -> list[float]:
    """Every gap between consecutive output tokens of a request."""
    return [b - a for ts in record["times"] for a, b in zip(ts, ts[1:])]


def launches(run, program: str):
    """Device seconds of each launch of ``program`` in the trace."""
    if run.trace is None:
        return None
    got = run.trace["launches"].get(program)
    return got or None


def idle_share(run):
    """Per cent of the traced window in which no op ran on the device."""
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def decode_bounds(run):
    """(mean least time, mean operations) per recorded decode launch."""
    calls = run.record.get("launches", {}).get("decode")
    if not calls or run.peaks is None:
        return None
    dec = run.record["decoder"]
    fb = [dec.decode_step(c) for c in calls]
    bound = np.mean([run.peaks.bound_s(f, b) for f, b in fb])
    return bound, float(np.mean([f for f, _ in fb]))
