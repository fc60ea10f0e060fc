"""Arithmetic the sparse decoder's readers share (counts:
bench/counts_moe.py)."""

from __future__ import annotations

import numpy as np

from bench.metrics._shared import launches

#: the program whose launches are the decode steps
DECODE = "serve_decode"


def expert_s(run):
    """Device seconds per decode launch in its Pallas kernels (the
    ``tpu_custom_call`` ops of ``serve_decode``), over the traced
    window."""
    d = launches(run, DECODE)
    if d is None:
        return None
    k = sum(v for (m, op), v in run.trace["op_s"].items()
            if m == DECODE and op.endswith(":tpu_custom_call"))
    return k / len(d) if k > 0 else None


def bounds(run):
    """Means over the recorded decode launches: (least time of the step,
    operations of the step, least time of the expert kernel)."""
    r = run.record.get("launches", {})
    calls, ex = r.get("decode"), r.get("experts")
    if not calls or not ex or run.peaks is None:
        return None
    m = run.record["decoder"]
    step = [m.decode_step(c, t, h) for c, (t, h) in zip(calls, ex)]
    kern = [m.experts(len(c), t, h) for c, (t, h) in zip(calls, ex)]
    return (float(np.mean([run.peaks.bound_s(f, b) for f, b in step])),
            float(np.mean([f for f, _ in step])),
            float(np.mean([run.peaks.bound_s(f, b) for f, b in kern])))
