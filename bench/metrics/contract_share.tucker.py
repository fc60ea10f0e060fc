"""Per cent of the device's busy time spent in contractions (Pallas
kernels and XLA dots) over the traced window; the rest is eigh, copies,
pads and the error norm (trace)."""


def read(run):
    t = run.trace
    if t is None or t["busy_s"] <= 0 or t["contract_s"] <= 0:
        return None
    return 100.0 * t["contract_s"] / t["busy_s"]
