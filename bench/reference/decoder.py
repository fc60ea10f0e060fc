"""Plain float32 forward of a llama-style decoder, from its equations.

Imports nothing of the program.  Weights are the benchmark's own (see
``make_weights``), stacked over layers; each layer is cast to float32
inside a scan, so only one layer at a time is held in float32.  No
cache, no paging, no batching across requests: one sequence, causal
attention over all of it, matmuls at ``precision="highest"``.

Per layer, with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * gain::

    h = RMSNorm1(x)
    q, k, v = h Wq, h Wk, h Wv         (heads of head_dim; RoPE on q, k)
    x = x + softmax(q k^T / sqrt(head_dim) + causal) v Wo
    h = RMSNorm2(x)
    x = x + (silu(h Wgate) * (h Wup)) Wdown

then logits = RMSNorm_final(x) E^T with the tied embedding E.  RoPE
rotates the two halves of each head: (x1 cos - x2 sin, x2 cos + x1 sin)
with frequencies theta^(-i / (head_dim/2)).

``quant="fp8"`` is the control: every matmul operand is rounded to
float8_e4m3fn with one scale per tensor (weights) or per row
(activations), the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators import jax_key

__all__ = ["make_weights", "served_gaps", "FP8_MAX"]

FP8_MAX = 448.0  # largest finite float8_e4m3fn


def make_weights(conf: dict, seed: int):
    """The model's weights from ``seed``, made on the device in one jitted
    call, in the dtype they are served in.  Rows of the embedding past
    ``vocab_size`` (padding) are zero."""
    E, F = conf["hidden_size"], conf["intermediate_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    G, D = conf["num_key_value_heads"], conf["head_dim"]
    V = conf["vocab_size"]
    Vp = conf["assumed"].get("vocab_padded", V)
    dt = jnp.dtype(conf["dtype"])
    nt = jnp.dtype(conf.get("norm_dtype", "float32"))

    def make(key):
        k = jax.random.split(key, 11)
        n = lambda kk, shape, scale: (  # noqa: E731
            jax.random.normal(kk, shape, dt) * jnp.asarray(scale, dt))
        emb = n(k[0], (Vp, E), 0.02)
        emb = jnp.where(jnp.arange(Vp)[:, None] < V, emb, jnp.zeros((), dt))
        gain = lambda kk, shape: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)).astype(nt)
        return {
            "embed": emb,
            "wq": n(k[1], (L, E, H * D), E ** -0.5),
            "wk": n(k[2], (L, E, G * D), E ** -0.5),
            "wv": n(k[3], (L, E, G * D), E ** -0.5),
            "wo": n(k[4], (L, H * D, E), (H * D) ** -0.5),
            "w_gate": n(k[5], (L, E, F), E ** -0.5),
            "w_up": n(k[6], (L, E, F), E ** -0.5),
            "w_down": n(k[7], (L, F, E), F ** -0.5),
            "gain_attn": gain(k[8], (L, E)),
            "gain_mlp": gain(k[9], (L, E)),
            "gain_final": gain(k[10], (E,)),
        }

    return jax.jit(make)(jax_key(seed))


# ------------------------------------------------------------------ pieces
def _fp8(x, axis):
    """Round to float8_e4m3fn with a scale per slice along ``axis``
    (None: one scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """``x @ w`` in float32 at highest precision; fp8 operands under the
    control."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq      # (S, 1, half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(conf, quant, x, lw):
    S = x.shape[0]
    H, G, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
               conf["head_dim"])
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    f = {k: v.astype(jnp.float32) for k, v in lw.items()}
    pos = jnp.arange(S)
    h = _rms(x, f["gain_attn"], eps)
    q = _rope(_mm(h, f["wq"], quant).reshape(S, H, D), pos, theta)
    k = _rope(_mm(h, f["wk"], quant).reshape(S, G, D), pos, theta)
    v = _mm(h, f["wv"], quant).reshape(S, G, D)
    k, v = jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1)
    qq, kk = (q, k) if quant != "fp8" else (_fp8(q, -1), _fp8(k, -1))
    s = jnp.einsum("shd,thd->hst", qq, kk,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(D)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    if quant == "fp8":
        p, v = _fp8(p, -1), _fp8(v, 0)
    a = jnp.einsum("hst,thd->shd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(a.reshape(S, H * D), f["wo"], quant)
    h = _rms(x, f["gain_mlp"], eps)
    g = jax.nn.silu(_mm(h, f["w_gate"], quant)) * _mm(h, f["w_up"], quant)
    return x + _mm(g, f["w_down"], quant), None


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "gain_attn", "gain_mlp")


@functools.partial(jax.jit, static_argnames=("conf_items", "quant"))
def _logits(w, tokens, rows, *, conf_items, quant):
    """Float32 logits (unpadded vocabulary) at positions ``rows`` of the
    sequence ``tokens``."""
    conf = dict(conf_items)
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in _LAYER_KEYS}
    x, _ = jax.lax.scan(functools.partial(_layer, conf, quant), x, layers)
    x = _rms(x[rows], w["gain_final"].astype(jnp.float32),
             conf["rms_norm_eps"])
    return _mm(x, w["embed"][:conf["vocab_size"]].astype(jnp.float32).T,
               quant)


@jax.jit
def _gap(ref, pick):
    """(best - ref[pick]) / std of each row, and the row's argmax."""
    got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return (ref.max(-1) - got) / ref.std(-1)


def served_gaps(conf: dict, w, seqs, width: int, rows_width: int,
                quant: str | None = None):
    """For each ``(tokens, first, served)`` in ``seqs`` — a sequence of
    prompt plus served tokens, the position ``first`` whose logits chose
    the first served token, and the served tokens — the gap by which
    each served token's float32 logit lies below the best one, in
    standard deviations of its logits row.

    ``quant="fp8"``: the control, read at the same positions: the gap of
    the token that the fp8 forward puts first.  Sequences are padded to
    ``width`` positions and the rows to ``rows_width`` (one compile);
    padding follows every real token and so changes no causal row.
    """
    conf_items = tuple(sorted(
        (k, v) for k, v in conf.items()
        if k in ("vocab_size", "num_attention_heads", "num_key_value_heads",
                 "head_dim", "rms_norm_eps", "rope_theta")))
    out = []
    for tokens, first, served in seqs:
        n = len(served)
        toks = np.zeros(width, np.int32)
        toks[:len(tokens)] = tokens
        rows = np.zeros(rows_width, np.int32)
        rows[:n] = first + np.arange(n)
        pick = np.zeros(rows_width, np.int32)
        with jax.default_matmul_precision("highest"):
            ref = _logits(w, toks, rows, conf_items=conf_items, quant=None)
            if quant is None:
                pick[:n] = served
            else:
                pick = jnp.argmax(_logits(w, toks, rows, conf_items=conf_items,
                                          quant=quant), -1)
            gaps = _gap(ref, jnp.asarray(pick, jnp.int32))
        out.append(np.asarray(gaps, np.float64)[:n])
    return out
