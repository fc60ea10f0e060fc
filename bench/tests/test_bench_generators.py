"""Traffic generators: determinism from the seed and the declared
distributions."""

import numpy as np
import pytest

from bench import generators
from bench.generators import draw, rng_for, zipf_weights

CHAT = {
    "generator": "open_loop", "rate_per_s": 2.0,
    "arrivals": {"kind": "poisson"},
    "tenants": {"count": 8, "zipf": 1.0, "shared": True,
                "system_len": {"dist": "uniform", "min": 256, "max": 512}},
    "user_len": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                 "min": 16, "max": 1024},
    "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.6,
                   "min": 16, "max": 256},
}
BACKLOG = {"generator": "backlog", "requests": 48,
           "prompt_len": {"dist": "uniform", "min": 64, "max": 256},
           "output_len": {"dist": "uniform", "min": 512, "max": 1024}}
BIG_SEED = 2**31 + 12345


def _gen(mix, seed, seconds=50.0, vocab=1000):
    return generators.load(mix["generator"]).generate(
        mix, seed=seed, seconds=seconds, vocab=vocab)


def _shape(work):
    return [(r.due, len(r.prompt), r.max_new, r.shared)
            for r in work.requests]


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["open_loop", "backlog"])
def test_same_seed_same_inputs(mix):
    a, b = _gen(mix, BIG_SEED), _gen(mix, BIG_SEED)
    assert _shape(a) == _shape(b)
    assert all((x.prompt == y.prompt).all()
               for x, y in zip(a.requests, b.requests))


def test_open_loop_seed_draws_its_own_work():
    a, b = _gen(CHAT, 7), _gen(CHAT, BIG_SEED)
    assert _shape(a) != _shape(b)
    assert len(a.requests) == len(b.requests)


def test_backlog_gives_every_seed_the_same_sizes_with_other_tokens():
    a, b = _gen(BACKLOG, 7), _gen(BACKLOG, BIG_SEED)
    assert _shape(a) == _shape(b)
    assert not all((x.prompt[:8] == y.prompt[:8]).all()
                   for x, y in zip(a.requests, b.requests))
    assert len({len(r.prompt) for r in a.requests}) > 1    # not one length
    lens = sorted(len(r.prompt) for r in a.requests)
    assert lens == sorted(generators.quantiles(BACKLOG["prompt_len"], 48))


def test_open_loop_rate_window_and_order():
    w = _gen(CHAT, 3, seconds=50.0)
    due = [r.due for r in w.requests]
    assert len(due) == 100                        # rate x seconds
    assert due == sorted(due) and 0 < due[0] and due[-1] < 50.0
    gaps = np.diff([0.0] + due)
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.3   # exponential


def test_open_loop_tenants_share_their_system_prompt():
    w = _gen(CHAT, 5)
    by_len = {}
    for r in w.requests:
        assert 256 <= r.shared <= 512
        by_len.setdefault(r.shared, []).append(r.prompt[:r.shared])
    assert len(by_len) == 8                       # one length per tenant
    for heads in by_len.values():
        assert all((h == heads[0]).all() for h in heads)
    counts = sorted((len(v) for v in by_len.values()), reverse=True)
    assert counts[0] > 2 * counts[-1]            # a few tenants send most


def test_unshared_tenants_keep_lengths_but_no_common_prefix():
    mix = dict(CHAT, tenants=dict(CHAT["tenants"], shared=False))
    shared, unshared = _gen(CHAT, 9), _gen(mix, 9)
    assert sorted(len(r.prompt) for r in shared.requests) == sorted(
        len(r.prompt) for r in unshared.requests)
    assert all(r.shared == 0 for r in unshared.requests)


def test_declared_distributions():
    rng = rng_for(BIG_SEED)
    user = draw(CHAT["user_len"], 20000, rng)
    assert user.min() >= 16 and user.max() <= 1024
    assert abs(np.median(user) - 192) <= 5
    out = draw(CHAT["output_len"], 20000, rng)
    assert abs(np.median(out) - 64) <= 2 and out.max() <= 256
    uni = draw({"dist": "uniform", "min": 256, "max": 512}, 20000, rng)
    assert uni.min() == 256 and uni.max() == 512
    assert abs(uni.mean() - 384) < 3
    assert list(draw({"dist": "fixed", "value": 5}, 3, rng)) == [5, 5, 5]
    w = zipf_weights(8, 1.0)
    assert abs(w.sum() - 1) < 1e-12 and abs(w[0] / w[1] - 2) < 1e-12


def test_bursty_arrivals_come_in_groups():
    mix = dict(CHAT, arrivals={"kind": "bursty", "burst": 4})
    due = [r.due for r in _gen(mix, 11).requests]
    assert len(set(due)) == 25 and len(due) == 100


def test_backlog_is_all_due_at_once():
    w = _gen(BACKLOG, 4)
    assert len(w.requests) == 48 and {r.due for r in w.requests} == {0.0}
    assert all(64 <= len(r.prompt) <= 256 and 512 <= r.max_new <= 1024
               for r in w.requests)


def test_jobs_run_back_to_back():
    assert _gen({"generator": "jobs"}, 1).requests == []
