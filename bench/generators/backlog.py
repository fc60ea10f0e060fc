"""A backlog: every request is queued when the window opens.

Mix parameters::

    requests        how many (enough that the queue never empties)
    prompt_len      dist of the prompts (fresh tokens, nothing shared)
    output_len      dist of the output tokens (a fixed count per request)

Every seed gets the same sizes in the same order: the quantiles of each
distribution, paired by one fixed shuffle.  The seed draws the tokens.
The slots serve the queue's head, and while a decode step takes ~0.15 s
the window never gets past the first requests, whose lengths set the
decode step's page bucket: sizes drawn per seed moved ``output_tok_s``
by 8.0-11.6 tokens/s across six seeds, where two runs of one seed agreed
within 0.6% (PERF.md).
"""

from __future__ import annotations

import numpy as np

from bench.generators import Req, Work, quantiles, rng_for


def generate(mix: dict, *, seed: int, seconds: float, vocab: int) -> Work:
    del seconds
    rng = rng_for(seed)
    n = int(mix["requests"])
    fixed = np.random.default_rng(0)
    prompt = fixed.permutation(quantiles(mix["prompt_len"], n))
    out = fixed.permutation(quantiles(mix["output_len"], n))
    return Work(requests=[
        Req(rid=i, due=0.0,
            prompt=rng.integers(0, vocab, int(prompt[i]), dtype="int32"),
            max_new=int(out[i]))
        for i in range(n)])
