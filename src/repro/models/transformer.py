"""The LM backbone: pattern-scanned blocks over all 10 architectures.

Layers are organised as ``prefix`` (run once, e.g. kimi's first dense
layer) + a repeating ``pattern`` scanned ``n_periods`` times with stacked
parameters — so the traced HLO contains each distinct block exactly once
regardless of depth (compile-time sanity for the 512-device dry-run) and
``jax.checkpoint`` gives per-period rematerialisation for training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.einsum import xeinsum
from repro.distributed.sharding import logical
from repro.models import layers as L
from repro.models.frontend import apply_frontend, init_frontend
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import init_mamba, init_ssm_cache, mamba_mixer

__all__ = [
    "init_params", "forward", "prefill", "lm_loss", "init_cache",
    "decode_step", "Model",
]


def _ctr(cfg: ModelConfig):
    return functools.partial(
        xeinsum, strategy=cfg.contract_strategy, backend=cfg.contract_backend
    )


# ------------------------------------------------------------------ blocks
def _init_block(key, cfg: ModelConfig, spec: LayerSpec):
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    p = {
        "norm1": L.init_rms(kn1, cfg.d_model),
        "norm2": L.init_rms(kn2, cfg.d_model),
    }
    if spec.mixer == "attn":
        p["attn"] = L.init_attn(km, cfg)
    else:
        p["mamba"] = init_mamba(km, cfg)
    if spec.ff == "dense":
        p["mlp"] = L.init_mlp(kf, cfg)
    elif spec.ff == "moe":
        p["moe"] = init_moe(kf, cfg)
    return p


def _block(cfg: ModelConfig, spec: LayerSpec, params, x, *, positions, cache=None):
    """Pre-norm residual block.  Returns (x, new_cache, aux)."""
    aux = {}
    h = L.rms_norm(x, params["norm1"], cfg.rms_eps)
    if spec.mixer == "attn":
        out, new_cache = L.attention(
            cfg, params["attn"], h, positions=positions,
            window=spec.window, kv_cache=cache,
        )
    else:
        out, new_cache = mamba_mixer(
            cfg, params["mamba"], h, positions=positions, kv_cache=cache
        )
    x = x + out
    if spec.ff != "none":
        h = L.rms_norm(x, params["norm2"], cfg.rms_eps)
        if spec.ff == "dense":
            x = x + L.mlp(cfg, params["mlp"], h)
        else:
            y, aux = moe_ffn(cfg, params["moe"], h)
            x = x + y
    return x, new_cache, aux


# ------------------------------------------------------------------ params
def init_params(key, cfg: ModelConfig):
    keys = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt),
        "final_norm": L.init_rms(keys[1], cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(keys[2], cfg.d_model, cfg.vocab_size, dt)
    if cfg.frontend is not None:
        params["frontend"] = init_frontend(keys[3], cfg)
    if cfg.prefix:
        params["prefix"] = [
            _init_block(k, cfg, s)
            for k, s in zip(jax.random.split(keys[4], max(len(cfg.prefix), 1)), cfg.prefix)
        ]
    # pattern params stacked over periods: tree of (n_periods, ...) leaves,
    # made stacked (vmap over the period keys) so no per-period copy exists
    def one_period(k):
        ks = jax.random.split(k, len(cfg.pattern))
        return [_init_block(kk, cfg, s) for kk, s in zip(ks, cfg.pattern)]

    period_keys = jax.random.split(keys[5], cfg.n_periods)
    params["pattern"] = jax.vmap(one_period)(period_keys)
    return params


def _acc_aux(acc, aux):
    out = dict(acc)
    for k, v in (aux or {}).items():
        out[k] = out.get(k, jnp.zeros((), jnp.float32)) + jnp.asarray(v, jnp.float32).sum()
    return out


# -------------------------------------------------------------- the stack
def _embed_inputs(cfg: ModelConfig, params, batch):
    dt = cfg.activation_dtype()
    if cfg.frontend is not None and cfg.frontend.kind == "audio":
        # audio: precomputed frames are the whole sequence (tokens = targets)
        return apply_frontend(cfg, params["frontend"], batch["features"].astype(dt))
    x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    if cfg.frontend is not None:  # vision: prepend projected patch tokens
        x = apply_frontend(cfg, params["frontend"], batch["features"].astype(dt), x)
    return x


def _run_stack(cfg: ModelConfig, params, x, positions, cache=None, remat=False):
    """Shared stack runner.  Returns (x, new_cache | None, aux)."""
    aux_acc = {"load_balance_loss": jnp.zeros((), jnp.float32)}
    new_prefix = []
    prefix_caches = cache["prefix"] if cache is not None else [None] * len(cfg.prefix)
    for spec, p, c in zip(cfg.prefix, params.get("prefix", []), prefix_caches):
        x, nc, aux = _block(cfg, spec, p, x, positions=positions, cache=c)
        aux_acc = _acc_aux(aux_acc, aux)
        new_prefix.append(nc)

    if cache is None:

        def period_body(x, period_params):
            aux_p = {"load_balance_loss": jnp.zeros((), jnp.float32)}
            for spec, p in zip(cfg.pattern, period_params):
                x, _, aux = _block(cfg, spec, p, x, positions=positions)
                aux_p = _acc_aux(aux_p, aux)
            return x, aux_p

        body = jax.checkpoint(period_body) if remat else period_body
        x, aux_scan = jax.lax.scan(lambda x, p: body(x, p), x, params["pattern"])
        aux_acc = _acc_aux(aux_acc, jax.tree.map(jnp.sum, aux_scan))
        return x, None, aux_acc

    def period_body_cached(x, inp):
        period_params, period_cache = inp
        new_caches = []
        for j, spec in enumerate(cfg.pattern):
            x, nc, _ = _block(
                cfg, spec, period_params[j], x, positions=positions,
                cache=period_cache[j],
            )
            new_caches.append(nc)
        return x, new_caches

    x, new_pattern = jax.lax.scan(
        period_body_cached, x, (params["pattern"], cache["pattern"])
    )
    new_cache = {
        "prefix": new_prefix,
        "pattern": new_pattern,
        "length": cache["length"] + positions.shape[0],
    }
    return x, new_cache, aux_acc


def _lm_head(cfg: ModelConfig, params, x):
    dt = cfg.activation_dtype()
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    spec = "bse,ve->bsv" if cfg.tie_embeddings else "bse,ev->bsv"
    logits = _ctr(cfg)(spec, x, head.astype(dt))
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logical(logits, "batch", None, "vocab")


# ----------------------------------------------------------------- forward
def forward(cfg: ModelConfig, params, batch, *, remat: bool = True):
    """Training forward.  Returns (logits, aux)."""
    x = _embed_inputs(cfg, params, batch)
    x = logical(x, "batch", "seq_sharded", None)
    positions = jnp.arange(x.shape[1])
    x, _, aux = _run_stack(cfg, params, x, positions, remat=remat)
    return _lm_head(cfg, params, x), aux


def prefill(cfg: ModelConfig, params, batch, cache):
    """Serving prefill: runs the prompt, fills the cache.

    Returns (last_logits (B, V), new_cache).  Only the last position hits
    the LM head — at 32k prompts the full-seq logits tensor must never be
    materialized.

    Positions continue from ``cache["length"]``, so a prompt may be
    prefilled in chunks (the runtime's chunked prefill): each chunk sees
    its absolute positions for RoPE and the causal mask attends the
    cached prefix.  A fresh cache has length 0 — identical to the old
    ``arange`` behavior."""
    x = _embed_inputs(cfg, params, batch)
    positions = cache["length"] + jnp.arange(x.shape[1])
    x, new_cache, _ = _run_stack(cfg, params, x, positions, cache=cache)
    return _lm_head(cfg, params, x[:, -1:])[:, -1], new_cache


def lm_loss(cfg: ModelConfig, params, batch, *, remat: bool = True,
            lb_coeff: float = 0.01):
    """Next-token (or frame-target) cross-entropy + MoE balance loss."""
    logits, aux = forward(cfg, params, batch, remat=remat)
    if cfg.encoder_only or cfg.frontend is not None:
        # targets provided explicitly, aligned to the end of the sequence
        targets = batch["labels"]
        logits_t = logits[:, -targets.shape[1]:]
    else:
        targets = batch["tokens"][:, 1:]
        logits_t = logits[:, :-1]
    logp = jax.nn.log_softmax(logits_t, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, -targets.shape[1]:]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    else:
        loss = jnp.mean(nll)
    total = loss + lb_coeff * aux.get("load_balance_loss", 0.0)
    return total, {"ce_loss": loss, **aux}


# ------------------------------------------------------------------ decode
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """Per-layer cache, stacked over periods for the scanned pattern."""
    dt = dtype or cfg.activation_dtype()
    G, D = cfg.n_kv_heads, cfg.hd

    def one(spec: LayerSpec):
        if spec.mixer == "attn":
            if cfg.kv_quant:
                return {
                    "k": jnp.zeros((batch, max_len, G, D), jnp.int8),
                    "v": jnp.zeros((batch, max_len, G, D), jnp.int8),
                    "k_scale": jnp.zeros((batch, max_len, G), jnp.float32),
                    "v_scale": jnp.zeros((batch, max_len, G), jnp.float32),
                    "length": jnp.zeros((), jnp.int32),
                }
            return {
                "k": jnp.zeros((batch, max_len, G, D), dt),
                "v": jnp.zeros((batch, max_len, G, D), dt),
                "length": jnp.zeros((), jnp.int32),
            }
        return init_ssm_cache(cfg, batch, dt)

    prefix = [one(s) for s in cfg.prefix]
    pattern = [
        jax.tree.map(lambda *xs: jnp.stack(xs), *[one(s) for _ in range(cfg.n_periods)])
        for s in cfg.pattern
    ]
    return {"prefix": prefix, "pattern": pattern, "length": jnp.zeros((), jnp.int32)}


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """One decode step.  tokens: (B, 1).  Returns (logits (B, V), new_cache)."""
    if cfg.encoder_only:
        raise ValueError(f"{cfg.arch_id} is encoder-only: no decode step")
    dt = cfg.activation_dtype()
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    pos = cache["length"][None]
    x, new_cache, _ = _run_stack(cfg, params, x, pos, cache=cache)
    return _lm_head(cfg, params, x)[:, -1], new_cache


class Model:
    """Thin OO wrapper tying config + functions (public API convenience)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, key):
        return init_params(key, self.cfg)

    def __call__(self, params, batch, **kw):
        return forward(self.cfg, params, batch, **kw)

    def loss(self, params, batch, **kw):
        return lm_loss(self.cfg, params, batch, **kw)

    def prefill(self, params, batch, cache):
        return prefill(self.cfg, params, batch, cache)

    def init_cache(self, batch, max_len, dtype=None):
        return init_cache(self.cfg, batch, max_len, dtype)

    def decode_step(self, params, cache, tokens):
        return decode_step(self.cfg, params, cache, tokens)
