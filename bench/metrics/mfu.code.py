"""Per cent of the chip's bf16 peak that a whole decode step reaches:
the operations of its tokens at their live context lengths (sliding
layers at most the window; experts only for the routed pairs on held
experts) over its device time.  Means over the launches of the traced
window (counts: bench/counts_moe.py; time: trace)."""

import numpy as np

from bench.metrics._shared import launches
from bench.metrics._sparse import DECODE, bounds


def read(run):
    d, b = launches(run, DECODE), bounds(run)
    if d is None or b is None:
        return None
    return 100.0 * b[1] / float(np.mean(d)) / run.peaks.flops
