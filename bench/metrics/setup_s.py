"""Set-up: weights or data made, programs compiled or loaded, warm-up
run (host clock, from the start of set-up to the window)."""


def read(run):
    return run.setup_s
