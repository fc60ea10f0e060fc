"""Transformer building blocks.

Every matmul routes through :func:`repro.core.einsum.xeinsum` — the
n-ary front-end of the paper's strided-batched contraction engine — so
model compute and decomposition compute share one planned code path.
Attention's QKᵀ/PV products *are* strided-batched GEMMs (batch =
(batch, head-group)); projections are flattened GEMMs.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.einsum import xeinsum
from repro.distributed.sharding import logical
from repro.kernels.flash_attn import paged_attention

__all__ = [
    "rms_norm", "rope", "rope_freqs", "attention", "mlp", "init_attn", "init_mlp",
    "dense", "init_dense", "softcap", "KVPages",
]

_NEG_INF = -2.0**30  # large-negative mask value safe in bf16


def _ctr(cfg: ModelConfig):
    return functools.partial(
        xeinsum, strategy=cfg.contract_strategy, backend=cfg.contract_backend
    )


def softcap(x, cap: float | None):
    """Gemma-2 style logit soft-capping."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------- norms
def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms(key, d):
    del key
    return jnp.zeros((d,), jnp.float32)


# ---------------------------------------------------------------- rope
def rope_freqs(d: int, theta: float, yarn=None):
    """Inverse frequencies of a rope over ``d`` features, and the factor
    cos and sin are scaled by.

    ``yarn`` (a :class:`~repro.configs.base.YaRN`) follows transformers'
    ``_compute_yarn_parameters``: dimension ``i`` keeps its original
    frequency ``theta^(-2i/d)`` where it turns more than ``beta_fast``
    times over ``original_max_positions``, is divided by ``factor``
    where it turns fewer than ``beta_slow`` times, and blends linearly
    between (correction range floored and ceiled, clipped to
    ``[0, d - 1]``)."""
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn is None:
        return freqs, 1.0

    def dim_of(rotations):
        return (d * math.log(yarn.original_max_positions
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    freqs = freqs * (1.0 - ramp + ramp / yarn.factor)
    scale = yarn.attention_factor
    if scale is None:
        scale = 0.1 * math.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0
    return freqs, scale


def rope(x, positions, theta: float = 10_000.0, yarn=None):
    """Rotary embedding on the last axis of x: (..., seq, heads, head_dim).
    ``yarn``: the layer's rope scaling (:func:`rope_freqs`)."""
    d = x.shape[-1]
    half = d // 2
    freqs, scale = rope_freqs(d, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos, sin = cos * scale, sin * scale
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ------------------------------------------------------------ projections
def dense(cfg: ModelConfig, x, w, spec: str = "bse,ef->bsf"):
    """Linear layer via the contraction engine."""
    return _ctr(cfg)(spec, x, w.astype(x.dtype))


def init_dense(key, d_in, d_out, dtype=jnp.float32, scale=None):
    scale = scale or d_in**-0.5
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


# ------------------------------------------------------------- attention
def init_attn(key, cfg: ModelConfig):
    E, H, G, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = jnp.dtype(cfg.param_dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, E, H * D, dt),
        "wk": init_dense(kk, E, G * D, dt),
        "wv": init_dense(kv, E, G * D, dt),
        "wo": init_dense(ko, H * D, E, dt, scale=(H * D) ** -0.5),
    }


class KVPages(NamedTuple):
    """One attention layer's cached K/V as decode reads them: in place,
    from the page-major pool of :class:`repro.runtime.pages.PagedKV`.
    ``k``, ``v``: pool leaves ``(n_pages, L, 1, page_size, G * D)``, of
    which ``layer`` is this layer's index on the ``L`` axis; ``tables``:
    (B, P) page ids; ``lengths``: (B,) rows each request has cached."""
    k: Any
    v: Any
    tables: Any
    lengths: Any
    layer: Any = 0


def _attn_mask(q_pos, k_pos, *, causal: bool, window: int | None):
    """(q, k) boolean mask: True = attend."""
    rel = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(rel.shape, bool)
    if causal:
        ok &= rel >= 0
    if window is not None:
        ok &= rel < window
    return ok


def attention(
    cfg: ModelConfig,
    params,
    x,                      # (B, S, E)
    *,
    positions,              # (S,) token positions (for rope + causal mask)
    window: int | None = None,
    yarn=None,              # the layer's rope scaling (None: plain rope)
    kv_cache=None,          # optional dict(k=(B,T,G,D), v=..., length=()),
                            # or KVPages
):
    """GQA/MQA attention.  Returns (out, new_kv_cache | None).

    QKᵀ and PV are evaluated through the engine with shared batch modes
    (b, g) — strided-batched GEMMs in the paper's sense, with the repeat
    group r of GQA riding the GEMM's free rows (granite's MQA: G=1 and the
    K/V operands are *broadcast* across q-heads — Listing 1's lo=0).

    With ``kv_cache`` a :class:`KVPages` (decode, ``S == 1``, each row of
    the batch its own request at position ``positions[b, 0]``), the
    cached rows are read in place by
    :func:`~repro.kernels.flash_attn.paged_attention` and the new cache
    is only the step's own K/V row, ``dict(k=(B,1,G,D), v=...,
    length=(B,))``, in the pool's dtype.
    """
    ctr = _ctr(cfg)
    B, S, E = x.shape
    H, G, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    R = H // G
    q = dense(cfg, x, params["wq"]).reshape(B, S, G, R, D)
    k = dense(cfg, x, params["wk"]).reshape(B, S, G, D)
    v = dense(cfg, x, params["wv"]).reshape(B, S, G, D)
    q = rope(q.reshape(B, S, H, D), positions, cfg.rope_theta,
             yarn).reshape(B, S, G, R, D)
    k = rope(k, positions, cfg.rope_theta, yarn)
    q = logical(q, "batch", None, "kv_heads", None, None)
    k = logical(k, "batch", None, "kv_heads", None)

    paged = isinstance(kv_cache, KVPages)
    if paged:
        k, v = k.astype(kv_cache.k.dtype), v.astype(kv_cache.v.dtype)
        new_cache = {"k": k, "v": v, "length": kv_cache.lengths + S}
    elif kv_cache is not None:
        # decode: append new k/v at cache.length
        T = kv_cache["k"].shape[1]
        idx = kv_cache["length"]
        if "k_scale" in kv_cache:  # int8 KV cache (per token×head scales)
            ks = jnp.max(jnp.abs(k), axis=-1).astype(jnp.float32) / 127.0 + 1e-9
            vs = jnp.max(jnp.abs(v), axis=-1).astype(jnp.float32) / 127.0 + 1e-9
            kq = jnp.round(k.astype(jnp.float32) / ks[..., None]).astype(jnp.int8)
            vq = jnp.round(v.astype(jnp.float32) / vs[..., None]).astype(jnp.int8)
            upd = lambda c, u: jax.lax.dynamic_update_slice(
                c, u, (0, idx) + (0,) * (c.ndim - 2))
            new_cache = {
                "k": upd(kv_cache["k"], kq), "v": upd(kv_cache["v"], vq),
                "k_scale": upd(kv_cache["k_scale"], ks),
                "v_scale": upd(kv_cache["v_scale"], vs),
                "length": idx + S,
            }
            k = (new_cache["k"].astype(jnp.float32)
                 * new_cache["k_scale"][..., None]).astype(q.dtype)
            v = (new_cache["v"].astype(jnp.float32)
                 * new_cache["v_scale"][..., None]).astype(q.dtype)
        else:
            ck = jax.lax.dynamic_update_slice(
                kv_cache["k"], k.astype(kv_cache["k"].dtype), (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                kv_cache["v"], v.astype(kv_cache["v"].dtype), (0, idx, 0, 0))
            k, v = ck, cv
            new_cache = {"k": ck, "v": cv, "length": idx + S}
        k_pos = jnp.arange(T)
        valid = k_pos <= (idx + S - 1)
    else:
        k_pos = positions
        valid = None
        new_cache = None

    causal = cfg.causal and not cfg.encoder_only
    if paged:
        c = kv_cache
        out = paged_attention(q[:, 0], k[:, 0], v[:, 0], c.k, c.v, c.tables,
                              c.lengths, c.layer, window=window,
                              softcap=cfg.attn_softcap)[:, None]
    elif cfg.attn_impl == "chunked" and kv_cache is None and S > cfg.attn_chunk:
        out = _chunked_attention(
            cfg, q, k.astype(q.dtype), v.astype(q.dtype), positions, k_pos,
            causal=causal, window=window,
        )
    else:
        # scores: contract over D with shared batch (b, g) — sb_gemm territory
        scores = ctr("bsgrd,btgd->bgrst", q, k.astype(q.dtype))
        scores = scores.astype(jnp.float32) * (D**-0.5)
        scores = softcap(scores, cfg.attn_softcap)

        mask = _attn_mask(positions, k_pos, causal=causal, window=window)
        if valid is not None:
            mask &= valid[None, :]
        scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)

        out = ctr("bgrst,btgd->bsgrd", probs, v.astype(x.dtype))
    out = out.reshape(B, S, H * D)
    out = dense(cfg, out, params["wo"], "bsh,he->bse")
    return logical(out, "batch", "seq_sharded", None), new_cache


def _chunked_attention(cfg, q, k, v, q_pos, k_pos, *, causal, window):
    """Flash-style streaming attention: scan KV in blocks, online softmax.

    Live memory per layer is O(S·chunk) instead of O(S·T); with the period
    scan + remat this removes the quadratic score buffers that dominate the
    memory roofline term for 32k prefill (§Perf hillclimb: granite-20b).
    Returns (B, S, G, R, D).
    """
    B, S, G, R, D = q.shape
    T = k.shape[1]
    Ck = cfg.attn_chunk
    pad = (-T) % Ck
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.concatenate([k_pos, jnp.full((pad,), -(10**9), k_pos.dtype)])
    nC = k.shape[1] // Ck
    kc = k.reshape(B, nC, Ck, G, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nC, Ck, G, D).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(nC, Ck)
    scale = D**-0.5

    def step(carry, inp):
        m, l, acc = carry
        k_i, v_i, p_i = inp
        s = xeinsum("bsgrd,btgd->bgrst", q, k_i, strategy="direct")
        s = s.astype(jnp.float32) * scale
        s = softcap(s, cfg.attn_softcap)
        ok = _attn_mask(q_pos, p_i, causal=causal, window=window)  # (S, Ck)
        s = jnp.where(ok[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        upd = xeinsum("bgrst,btgd->bgrsd", p.astype(q.dtype), v_i,
                      strategy="direct").astype(jnp.float32)
        acc = acc * corr[..., None] + upd
        return (m_new, l, acc), None

    m0 = jnp.full((B, G, R, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, G, R, S), jnp.float32)
    a0 = jnp.zeros((B, G, R, S, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,S,G,R,D)


# ------------------------------------------------------------------ mlp
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    E = cfg.d_model
    F = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    if cfg.mlp_act == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "wi": init_dense(k1, E, F, dt),
            "wg": init_dense(k2, E, F, dt),
            "wo": init_dense(k3, F, E, dt, scale=F**-0.5),
        }
    k1, k2 = jax.random.split(key, 2)
    return {
        "wi": init_dense(k1, E, F, dt),
        "wo": init_dense(k2, F, E, dt, scale=F**-0.5),
    }


def mlp(cfg: ModelConfig, params, x):
    h = dense(cfg, x, params["wi"], "bse,ef->bsf")
    if cfg.mlp_act == "swiglu":
        g = dense(cfg, x, params["wg"], "bse,ef->bsf")
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = logical(h, "batch", None, "ff")
    return dense(cfg, h, params["wo"], "bsf,fe->bse")
