"""Output tokens served in the window, all requests, over the window
(host clock): the rate a cell above the knee is judged on."""


def read(run):
    r = run.record
    n = sum(len(ts) for ts in r["times"])
    return n / r["window_s"] if n else None
