"""Per cent of the roofline a decode launch reaches: its least time
(every weight read once, plus the live KV rows of the active slots read
and one row each written, at HBM bandwidth; or its operations at bf16
peak, whichever is longer), over its device time.  Means over the
launches of the traced window (counts: bench/counts.py; time: trace)."""

import numpy as np

from bench.metrics._shared import decode_bounds, launches


def read(run):
    d, b = launches(run, "serve_decode"), decode_bounds(run)
    if d is None or b is None:
        return None
    return 100.0 * b[0] / float(np.mean(d))
