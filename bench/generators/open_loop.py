"""Open-loop arrivals: requests are due on a schedule, served or not.

Mix parameters::

    rate_per_s      mean arrival rate
    arrivals        {"kind": "poisson"} or {"kind": "bursty", "burst": b}
                    (b requests due at once, groups at rate / b)
    tenants         {"count", "zipf", "system_len": dist, "shared": bool}
                    or null: each prompt starts with its tenant's system
                    prompt; popularity is Zipf; "shared": false draws the
                    same lengths with fresh tokens (no common prefix)
    user_len        dist of the user turn, appended to the system prompt
    output_len      dist of the output tokens (a fixed count per request)

The run's requests number ``rate_per_s * seconds`` and all fall due
inside the window.  The seed draws arrival times, sizes and tokens.
"""

from __future__ import annotations

import numpy as np

from bench.generators import (
    Req, Work, draw, n_requests, rng_for, zipf_weights,
)


def arrival_times(mix: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Poisson group arrivals, scaled so that the last group is due
    before the window closes."""
    arr = mix.get("arrivals", {"kind": "poisson"})
    if arr["kind"] not in ("poisson", "bursty"):
        raise ValueError(f"unknown arrivals {arr['kind']!r}")
    burst = int(arr.get("burst", 1)) if arr["kind"] == "bursty" else 1
    groups = -(-n // burst)
    starts = np.cumsum(rng.exponential(1.0, groups + 1))
    starts = starts[:-1] * seconds / starts[-1]
    return np.repeat(starts, burst)[:n]


def generate(mix: dict, *, seed: int, seconds: float, vocab: int) -> Work:
    rng = rng_for(seed)
    n = n_requests(mix["rate_per_s"], seconds)
    due = arrival_times(mix, n, seconds, rng)
    user = draw(mix["user_len"], n, rng)
    out = draw(mix["output_len"], n, rng)
    ten = mix.get("tenants")
    if ten:
        k = int(ten["count"])
        tenant = rng.choice(k, n, p=zipf_weights(k, ten["zipf"]))
        shared = ten.get("shared", True)
        systems = [rng.integers(0, vocab, L, dtype=np.int32)
                   for L in draw(ten["system_len"], k, rng)]
    reqs = []
    for i in range(n):
        body = rng.integers(0, vocab, int(user[i]), dtype=np.int32)
        head, common = np.zeros(0, np.int32), 0
        if ten:
            t = int(tenant[i])
            if shared:
                head, common = systems[t], len(systems[t])
            else:
                head = rng.integers(0, vocab, len(systems[t]), dtype=np.int32)
        reqs.append(Req(rid=i, due=float(due[i]),
                        prompt=np.concatenate([head, body]),
                        max_new=int(out[i]), shared=common))
    return Work(requests=reqs)
