"""Device milliseconds per decode launch (the ``serve_decode`` program),
mean over the traced window (trace)."""

import numpy as np

from bench.metrics._shared import launches


def read(run):
    d = launches(run, "serve_decode")
    return None if d is None else 1e3 * float(np.mean(d))
