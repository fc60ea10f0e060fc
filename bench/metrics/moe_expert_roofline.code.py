"""Per cent of the roofline the expert kernel reaches: its least time
(each held expert a token chose read once, every layer's token rows read
and written, at HBM bandwidth; or the routed pairs' operations at bf16
peak, whichever is longer; from the runtime's routing counters of each
recorded launch and bench/counts_moe.py), over its device time per
launch (trace)."""

from bench.metrics._sparse import bounds, expert_s


def read(run):
    s, b = expert_s(run), bounds(run)
    if s is None or b is None:
        return None
    return 100.0 * b[2] / s
