"""Plain float32 forward of this chip's share of Mellum2-12B-A2.5B, from
its equations.

Imports nothing of the program.  Weights are the benchmark's own (see
``make_weights``), one group per position of the layer pattern, each
stacked over the pattern's periods; a scan over the periods casts one
period at a time to float32.  No cache, no paging, no batching across
requests: one sequence, matmuls at ``precision="highest"``, attention in
blocks of ``QUERY_BLOCK`` queries against every key.

Per layer, with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * gain::

    h = RMSNorm1(x)
    q, k, v = h Wq, h Wk, h Wv   (32 query heads, 4 KV heads of 128; RoPE)
    x = x + softmax(q k^T / sqrt(128) + mask) v Wo
    h = RMSNorm2(x)
    p = softmax(h R)             (R: 2304 x 64, float32)
    w, e = top-8 of p, w / sum(w)
    x = x + sum over the chosen e held here of
            w_e (silu(h Wgate_e) * (h Wup_e)) Wdown_e

then logits = RMSNorm_final(x) Whead.  The mask is causal; a
``sliding_attention`` layer also drops keys ``sliding_window`` or more
positions back (query i sees keys j with i - j < window).  RoPE rotates
the two halves of each head, (x1 cos - x2 sin, x2 cos + x1 sin), at
frequencies theta^(-2i/128); a ``full_attention`` layer takes them from
its YaRN parameters (transformers' ``_compute_yarn_parameters``: the
frequencies divided by ``factor`` below the ``beta_slow`` rotation
count, kept above ``beta_fast``, a linear ramp between, over
``original_max_position_embeddings``) and scales cos and sin by
``attention_factor``.  Held experts are ``first_held_expert`` onward,
``num_experts`` of them; the other experts' terms are left out, as the
configuration states.

``quant="fp8"`` is the control: every matmul operand (the router's
too) is rounded to float8_e4m3fn with one scale per tensor (weights) or
per row (activations), the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators import jax_key

__all__ = ["make_weights", "pattern", "served_gaps", "FP8_MAX",
           "QUERY_BLOCK"]

FP8_MAX = 448.0  # largest finite float8_e4m3fn
#: queries per attention block: 32 heads x 512 x 3584 float32 scores
QUERY_BLOCK = 512
HIGHEST = jax.lax.Precision.HIGHEST


def pattern(conf: dict) -> list[str]:
    """The shortest repeating unit of ``layer_types``."""
    types = conf["layer_types"]
    for n in range(1, len(types) + 1):
        if len(types) % n == 0 and types == types[:n] * (len(types) // n):
            return types[:n]
    raise AssertionError("unreachable")


def make_weights(conf: dict, seed: int):
    """This chip's weights from ``seed``, made on the device in one jitted
    call, in the dtype they are served in: ``embed`` (V, E), ``lm_head``
    (E, V), ``gain_final`` (E,), and ``layers``, one dict per position
    of the layer pattern whose arrays lead with the period axis."""
    E, F = conf["hidden_size"], conf["moe_intermediate_size"]
    H, G, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
               conf["head_dim"])
    V, X, R = (conf["vocab_size"], conf["num_experts"],
               conf["num_experts_published"])
    kinds = pattern(conf)
    Pn = conf["num_hidden_layers"] // len(kinds)
    dt = jnp.dtype(conf["dtype"])
    nt = jnp.dtype(conf.get("norm_dtype", "float32"))

    def make(key):
        top, *per = jax.random.split(key, 1 + len(kinds))
        k = jax.random.split(top, 3)
        n = lambda kk, shape, scale: (  # noqa: E731
            jax.random.normal(kk, shape, dt) * jnp.asarray(scale, dt))
        gain = lambda kk, shape: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)).astype(nt)

        def layer(kk):
            k = jax.random.split(kk, 10)
            return {
                "wq": n(k[0], (Pn, E, H * D), E ** -0.5),
                "wk": n(k[1], (Pn, E, G * D), E ** -0.5),
                "wv": n(k[2], (Pn, E, G * D), E ** -0.5),
                "wo": n(k[3], (Pn, H * D, E), (H * D) ** -0.5),
                "router": n(k[4], (Pn, E, R), E ** -0.5),
                "w_gate": n(k[5], (Pn, X, E, F), E ** -0.5),
                "w_up": n(k[6], (Pn, X, E, F), E ** -0.5),
                "w_down": n(k[7], (Pn, X, F, E), F ** -0.5),
                "gain_attn": gain(k[8], (Pn, E)),
                "gain_moe": gain(k[9], (Pn, E)),
            }

        return {"embed": n(k[0], (V, E), 0.02),
                "lm_head": n(k[1], (E, V), E ** -0.5),
                "gain_final": gain(k[2], (E,)),
                "layers": [layer(kk) for kk in per]}

    return jax.jit(make)(jax_key(seed))


# ------------------------------------------------------------------ pieces
def _fp8(x, axis):
    """Round to float8_e4m3fn with a scale per slice along ``axis``
    (None: one scale for the tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_table(D: int, theta: float, yarn: dict | None):
    """(inverse frequencies, cos/sin scale) of a rope over ``D``
    features, in float64 from the published rule."""
    half = D // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    if yarn is None:
        return inv, 1.0
    factor, orig = yarn["factor"], yarn["original_max_position_embeddings"]

    def dim(rotations):
        return D * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim(yarn["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    return inv, yarn["attention_factor"]


def _rope(x, pos, table):
    inv, scale = table
    half = x.shape[-1] // 2
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(inv,
                                                               jnp.float32)
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, window, quant):
    """Causal (and windowed) attention in query blocks: q (S, H, D), k
    and v (S, H, D) already repeated to the query heads."""
    S, H, D = q.shape
    pos = jnp.arange(S)
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)

    def block(inp):
        qb, qp = inp
        s = jnp.einsum("shd,thd->hst", qb, k, precision=HIGHEST) / np.sqrt(D)
        ok = qp[:, None] >= pos[None, :]
        if window is not None:
            ok &= qp[:, None] - pos[None, :] < window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
        if quant == "fp8":
            p = _fp8(p, -1)
        return jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)

    nb = S // QUERY_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, QUERY_BLOCK, H, D),
                              pos.reshape(nb, QUERY_BLOCK)))
    return out.reshape(S, H, D)


def _top(h, router, m):
    """Renormalised top-k weights and experts of float32 routing."""
    w, e = jax.lax.top_k(jax.nn.softmax(h @ router, -1), m)
    return w / jnp.sum(w, -1, keepdims=True), e


def _layer(conf, quant, kind, x, lw):
    S = x.shape[0]
    H, G, D = (conf["num_attention_heads"], conf["num_key_value_heads"],
               conf["head_dim"])
    eps = conf["rms_norm_eps"]
    rp = conf["rope_parameters"][kind]
    yarn = rp if rp["rope_type"] == "yarn" else None
    table = rope_table(D, rp["rope_theta"], yarn)
    window = conf["sliding_window"] if kind == "sliding_attention" else None
    f = {k: v.astype(jnp.float32) for k, v in lw.items()
         if not k.startswith("w_")}
    pos = jnp.arange(S)
    h = _rms(x, f["gain_attn"], eps)
    q = _rope(_mm(h, f["wq"], quant).reshape(S, H, D), pos, table)
    k = _rope(_mm(h, f["wk"], quant).reshape(S, G, D), pos, table)
    v = _mm(h, f["wv"], quant).reshape(S, G, D)
    k, v = jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1)
    a = _attention(q, k, v, window, quant)
    x = x + _mm(a.reshape(S, H * D), f["wo"], quant)

    h = _rms(x, f["gain_moe"], eps)
    m = conf["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        hq, rq = (h, f["router"]) if quant != "fp8" else (
            _fp8(h, -1), _fp8(f["router"], None))
        w, e = _top(hq, rq, m)
        # routing of the hidden state rounded to bfloat16: a near-tie in
        # the top-k that the serving precision can flip
        _, e16 = _top(h.astype(jnp.bfloat16).astype(jnp.float32),
                      f["router"], m)
    flip = jnp.any(jnp.sort(e, -1) != jnp.sort(e16, -1), -1)
    local = e - conf["first_held_expert"]
    X = conf["num_experts"]
    comb = jnp.sum(jnp.where(local[..., None] == jnp.arange(X),
                             w[..., None], 0.0), 1)           # (S, X)

    def expert(y, inp):
        wg, wu, wd, c = (t.astype(jnp.float32) for t in inp)
        g = jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant)
        return y + c[:, None] * _mm(g, wd, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], comb.T))
    return x + y, flip


@functools.partial(jax.jit, static_argnames=("conf_items", "quant"))
def _logits(w, tokens, rows, *, conf_items, quant):
    """Float32 logits at positions ``rows`` of the sequence ``tokens``,
    and which (layer, position) routings flip on a bfloat16 rounding of
    the router's input."""
    conf = dict(conf_items)
    conf["rope_parameters"] = {k: dict(v)
                               for k, v in conf["rope_parameters"]}
    conf["layer_types"] = list(conf["layer_types"])
    kinds = pattern(conf)
    x = w["embed"][tokens].astype(jnp.float32)

    def period(x, lws):
        flips = []
        for kind, lw in zip(kinds, lws):
            x, fl = _layer(conf, quant, kind, x, lw)
            flips.append(fl)
        return x, jnp.stack(flips)

    x, flips = jax.lax.scan(period, x, w["layers"])
    x = _rms(x[rows], w["gain_final"].astype(jnp.float32),
             conf["rms_norm_eps"])
    return _mm(x, w["lm_head"].astype(jnp.float32), quant), flips


@jax.jit
def _gap(ref, pick):
    """(best - ref[pick]) / std of each row."""
    got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return (ref.max(-1) - got) / ref.std(-1)


#: the configuration keys the forward reads
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rms_norm_eps", "rope_parameters", "sliding_window",
         "layer_types", "num_experts_per_tok", "num_experts",
         "first_held_expert")


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, list):
        return tuple(v)
    return v


def served_gaps(conf: dict, w, seqs, width: int, rows_width: int,
                quant: str | None = None):
    """For each ``(tokens, first, served)`` in ``seqs`` — prompt plus
    served tokens, the position ``first`` whose logits chose the first
    served token, and the served tokens — the gap by which each served
    token's float32 logit lies below the best one, in standard
    deviations of its row, and for each served row the number of layers
    whose routing of that position flips on a bfloat16 rounding of the
    router's input.

    ``quant="fp8"``: the control, read at the same positions: the gap of
    the token that the fp8 forward puts first.  Sequences are padded to
    ``width`` positions (a multiple of ``QUERY_BLOCK``), the rows to
    ``rows_width``; padding follows every real token and so changes no
    causal row.  Returns (gaps, flips), lists of arrays per sequence."""
    items = tuple(sorted((k, _freeze(conf[k])) for k in _KEYS))
    width = -(-width // QUERY_BLOCK) * QUERY_BLOCK
    gaps, flips = [], []
    for tokens, first, served in seqs:
        n = len(served)
        toks = np.zeros(width, np.int32)
        toks[:len(tokens)] = tokens
        rows = np.zeros(rows_width, np.int32)
        rows[:n] = first + np.arange(n)
        pick = np.zeros(rows_width, np.int32)
        with jax.default_matmul_precision("highest"):
            ref, fl = _logits(w, toks, rows, conf_items=items, quant=None)
            if quant is None:
                pick[:n] = served
            else:
                pick = jnp.argmax(_logits(w, toks, rows, conf_items=items,
                                          quant=quant)[0], -1)
            g = _gap(ref, jnp.asarray(pick, jnp.int32))
        gaps.append(np.asarray(g, np.float64)[:n])
        fl = np.asarray(fl).reshape(-1, width)          # (layers, width)
        flips.append(fl[:, first:first + n].sum(0))
    return gaps, flips
