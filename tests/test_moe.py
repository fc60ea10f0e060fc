"""The held-expert layer that serving runs, YaRN rope, and the routing
counters: small configurations on seeded random weights, on the CPU (the
expert kernel in interpret mode)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig, YaRN
from repro.models.layers import mlp, rope_freqs
from repro.kernels.sb_gemm import expert_swiglu_pallas
from repro.models.moe import (EXPERT_WEIGHTS, held_weights, init_moe,
                              moe_held, route)


def _cfg(n_experts=16, top_k=4, n_shared=0, **moe):
    cfg = get_config("mellum2-12b-a2.5b", smoke=True)
    return cfg.with_(moe=MoEConfig(
        n_experts=n_experts, top_k=top_k, d_expert=32, n_shared=n_shared,
        d_shared=32 if n_shared else 0, **moe))


def _share(cfg, params, first, n):
    """Config and weights of the share holding experts first..first+n-1."""
    share = cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=n,
                                              first_held=first))
    return share, {k: v[first:first + n] if k in ("wi", "wg", "wo") else v
                   for k, v in params.items()}


def _dense_ref(cfg, params, x):
    """Each token through its own top-k experts, one by one (float32)."""
    m = cfg.moe
    xt = x.reshape(-1, x.shape[-1])
    w, e = route(m, params["router"], xt)
    out = []
    for t in range(xt.shape[0]):
        y = 0.0
        for j in range(m.top_k):
            x_ = int(e[t, j]) - m.first_held
            if 0 <= x_ < m.held:
                g = jax.nn.silu(xt[t] @ params["wg"][x_]) * (xt[t] @ params["wi"][x_])
                y = y + w[t, j] * (g @ params["wo"][x_])
        out.append(y)
    return jnp.stack(out).reshape(x.shape)


# ------------------------------------------------------------------ YaRN
def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow,
                    attention_factor):
    """transformers' ``_compute_yarn_parameters``, transcribed to NumPy."""
    def get_mscale(scale, mscale=1):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    if attention_factor is None:
        attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations, dim, base, max_pos):
        return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def find_correction_range(low_rot, high_rot, dim, base, max_pos):
        low = math.floor(find_correction_dim(low_rot, dim, base, max_pos))
        high = math.ceil(find_correction_dim(high_rot, dim, base, max_pos))
        return max(low, 0), min(high, dim - 1)

    def linear_ramp_factor(min_, max_, dim):
        if min_ == max_:
            max_ += 0.001
        linear_func = (np.arange(dim, dtype=np.float32) - min_) / (max_ - min_)
        return np.clip(linear_func, 0, 1)

    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    low, high = find_correction_range(beta_fast, beta_slow, dim, base, orig)
    extrapolation_factor = 1 - linear_ramp_factor(low, high, dim // 2)
    inv_freq = (inv_freq_interpolation * (1 - extrapolation_factor)
                + inv_freq_extrapolation * extrapolation_factor)
    return inv_freq, attention_factor


@pytest.mark.parametrize("dim,theta,yarn", [
    # mellum2-12b-a2.5b's full-attention layers, as published
    (128, 500_000.0, YaRN(16.0, 8192, 32.0, 1.0, 1.2772588722239782)),
    # the attention factor left to its default, another ramp
    (64, 10_000.0, YaRN(4.0, 4096, 32.0, 1.0)),
])
def test_yarn_frequencies_match_the_published_rule(dim, theta, yarn):
    got, scale = rope_freqs(dim, theta, yarn)
    want, want_scale = _published_yarn(
        dim, theta, yarn.factor, yarn.original_max_positions, yarn.beta_fast,
        yarn.beta_slow, yarn.attention_factor)
    # float32 on both sides; theta ** x is rounded once or twice
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    assert scale == pytest.approx(want_scale, rel=1e-12)
    assert scale == pytest.approx(0.1 * math.log(yarn.factor) + 1.0, rel=1e-6)
    plain, one = rope_freqs(dim, theta)
    assert one == 1.0 and float(jnp.max(jnp.abs(plain - got))) > 0


def test_yarn_layers_differ_from_plain_ones_only_in_their_rope():
    cfg = get_config("mellum2-12b-a2.5b")
    kinds = [(s.window, s.yarn is not None) for s in cfg.layers]
    assert kinds == ([(1024, False)] * 3 + [(None, True)]) * 7
    assert cfg.n_layers == 28 and cfg.moe.top_k == 8


# --------------------------------------------------------- held experts
@pytest.mark.parametrize("n_shared", [0, 1])
def test_held_shares_add_up_to_the_uncut_layer(n_shared):
    """Four disjoint shares of 16 experts: their outputs, with what every
    chip computes alike (the shared expert) counted once, sum to the
    layer that holds all 16; their held counts add up likewise."""
    cfg = _cfg(n_shared=n_shared)
    params = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 5, cfg.d_model))
    whole, held = moe_held(cfg, params, x)
    parts = [moe_held(*_share(cfg, params, 4 * s, 4), x) for s in range(4)]
    total = sum(y for y, _ in parts)
    if n_shared:
        shared = mlp(cfg, jax.tree.map(lambda p: p[0], params["shared"]), x)
        total = total - 3 * shared
    # float32; the shares sum the same terms in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(h) for _, h in parts], -1), held)
    assert int(held.sum()) == 2 * 5 * cfg.moe.top_k


def test_dropless_when_every_token_picks_one_expert():
    """A router that sends every token to expert 3 first: each token still
    gets its whole top-k, whatever the imbalance (no capacity)."""
    cfg = _cfg()
    params = init_moe(jax.random.key(2), cfg)
    params["router"] = params["router"].at[:, 3].set(0.0).at[0, 3].set(50.0)
    x = jax.random.normal(jax.random.key(3), (1, 24, cfg.d_model))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    y, held = moe_held(cfg, params, x)
    assert np.all(np.asarray(held)[..., 3] == 1)
    assert int(held.sum()) == 24 * cfg.moe.top_k
    # float32 kernel against token-by-token matmuls
    np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_ref(
        cfg, params, x)), rtol=2e-5, atol=2e-5)


def test_vmapped_layer_is_one_group_of_the_stacked_tokens():
    """Under vmap (the runtime's decode over slots) the layer runs once
    over all the batch's tokens: one kernel of B rows, the same output
    and counts as the unbatched layer on the stacked tokens."""
    cfg = _cfg()
    params = init_moe(jax.random.key(4), cfg)
    x = jax.random.normal(jax.random.key(5), (6, 1, 1, cfg.d_model))
    f = jax.vmap(lambda x: moe_held(cfg, params, x))
    y, held = f(x)
    want_y, want_held = moe_held(cfg, params, x.reshape(1, 6, -1))
    np.testing.assert_allclose(np.asarray(y).reshape(6, -1),
                               np.asarray(want_y).reshape(6, -1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(held).reshape(6, -1),
                                  np.asarray(want_held).reshape(6, -1))
    calls = _kernels(jax.make_jaxpr(f)(x).jaxpr)
    assert len(calls) == 1
    assert calls[0].invars[2].aval.shape[0] == 16      # 6 rows, padded


#: periods of the stacks the kernel reads in place
PERIODS = 3


@pytest.mark.parametrize("x_dtype,w_dtype", [
    ("float32", "float32"),
    ("bfloat16", "bfloat16"),
    ("bfloat16", "float32"),     # cast a block at a time in the kernel
])
def test_expert_kernel_reads_each_period_of_a_stack_in_place(x_dtype,
                                                             w_dtype):
    """Given a stack of three periods' weights and a period p, the kernel
    gives, bit for bit, what it gives for period p's weights alone, cast
    to the activations' dtype first.  Every other period and the held
    experts no token chose (1 and 5) are NaN: the kernel reads neither."""
    X, E, F, T = 8, 32, 16, 5
    ks = jax.random.split(jax.random.key(8), 5)
    wi, wg, wo = (jax.random.normal(k, (PERIODS, X) + s).astype(w_dtype)
                  for k, s in zip(ks, ((E, F), (E, F), (F, E))))
    x = jax.random.normal(ks[3], (T, E)).astype(x_dtype)
    comb = jax.random.uniform(ks[4], (T, X)).at[:, jnp.array([1, 5])].set(0.0)
    for p in range(PERIODS):
        want = expert_swiglu_pallas(
            x, comb, *(w[p:p + 1].astype(x_dtype) for w in (wi, wg, wo)), 0)
        other = (jnp.arange(PERIODS) != p)[:, None]
        untouched = jnp.isin(jnp.arange(X), jnp.array([1, 5]))[None]
        poison = (other | untouched)[..., None, None]
        got = expert_swiglu_pallas(
            x, comb, *(jnp.where(poison, jnp.nan, w) for w in (wi, wg, wo)),
            p)
        assert got.dtype == x.dtype and bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_layer_of_a_stack_runs_as_its_own_weights():
    """A sparse layer outside the period scan (a prefix layer) holds one
    layer's weights and runs them as a stack of one period: bit for bit
    what the layer gives reading the same weights from a stack of three
    periods, for every period."""
    cfg = _cfg()
    stack = jax.vmap(lambda k: init_moe(k, cfg))(
        jax.random.split(jax.random.key(9), PERIODS))
    x = jax.random.normal(jax.random.key(10), (2, 3, cfg.d_model))
    for p in range(PERIODS):
        layer = jax.tree.map(lambda v: v[p], stack)
        y, held = moe_held(cfg, layer, x)
        y_in_place, held_in_place = moe_held(
            cfg, {**layer, **{k: stack[k] for k in EXPERT_WEIGHTS}}, x, p)
        np.testing.assert_array_equal(np.asarray(y_in_place), np.asarray(y))
        np.testing.assert_array_equal(held_in_place, held)


def _kernels(jaxpr):
    """Every ``pallas_call`` in ``jaxpr`` and the jaxprs inside it."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
        for sub in jax.core.jaxprs_in_params(e.params):
            out += _kernels(sub)
    return out


def test_held_weights_count_only_the_held_choices():
    m = MoEConfig(n_experts=8, top_k=3, d_expert=4, n_held=2, first_held=4)
    top_w = jnp.asarray([[0.5, 0.3, 0.2], [0.6, 0.3, 0.1]])
    top_e = jnp.asarray([[4, 0, 5], [1, 2, 3]])
    comb, held = held_weights(m, top_w, top_e)
    np.testing.assert_allclose(comb, [[0.5, 0.2], [0.0, 0.0]])
    np.testing.assert_array_equal(held, [[1, 1], [0, 0]])


# ----------------------------------------------------------- counters
def test_runtime_routing_counters_match_a_hand_count():
    """top-k = all 4 published experts, this chip holding 2: every token
    routes 4 pairs a layer, 2 of them held, and every launch touches
    both held experts in every layer."""
    from repro.models.transformer import init_params
    from repro.runtime.engine import ServingRuntime
    from repro.runtime.scheduler import Request

    cfg = get_config("mellum2-12b-a2.5b", smoke=True).with_(n_periods=1)
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, top_k=4, n_held=2,
                                            first_held=2))
    L = cfg.n_layers
    params = init_params(jax.random.key(6), cfg)
    rt = ServingRuntime(cfg, params, slots=2, max_len=32, paged=True,
                        page_size=8, prefill_chunk=8, precompile=False)
    reqs = [Request(rid=i, prompt=np.arange(3, 3 + n, dtype=np.int32),
                    max_new_tokens=4) for i, n in enumerate((11, 6))]
    rt.serve(reqs)
    s = rt.metrics.snapshot()
    chunks, decodes = s["prefill_chunks"], s["decode_calls"]
    assert chunks == 3 + 2                          # 8+2+1, 4+2
    assert s["moe_pairs_routed_prefill"] == 17 * 4 * L
    assert s["moe_pairs_held_prefill"] == 17 * 2 * L
    assert s["moe_experts_touched_prefill"] == chunks * 2 * L
    assert s["moe_pairs_routed_decode"] == 6 * 4 * L
    assert s["moe_pairs_held_decode"] == 6 * 2 * L
    assert s["moe_experts_touched_decode"] == decodes * 2 * L


def test_capacity_path_refuses_a_share():
    cfg = _cfg(n_held=4)
    params = init_moe(jax.random.key(7), cfg)
    from repro.models.moe import moe_ffn

    with pytest.raises(NotImplementedError):
        moe_ffn(cfg, params, jnp.zeros((1, 4, cfg.d_model)))
