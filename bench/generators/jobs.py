"""Jobs run back to back on resident data until the window closes.

The configuration says what one job is; the mix has no parameters
beyond its kind.  The window ends when the last job started in it ends,
so every job counted is whole.
"""

from __future__ import annotations

from bench.generators import Work


def generate(mix: dict, *, seed: int, seconds: float, vocab: int) -> Work:
    del mix, seed, seconds, vocab
    return Work(requests=[])
