"""Device milliseconds per decode launch spent in the expert kernel: the
``tpu_custom_call`` ops of ``serve_decode`` (its only Pallas kernel),
over its launches in the traced window (trace)."""

from bench.metrics._sparse import expert_s


def read(run):
    s = expert_s(run)
    return None if s is None else 1e3 * s
