"""Mixture-of-Experts: a dropless layer over the held experts (serving)
and GShard-style grouped, capacity-based routing (training).

Serving (:func:`moe_held`, every cached call: prefill chunks and decode
steps) routes in float32 over all ``n_experts`` published experts,
renormalises the top-k weights, and adds only the terms of the experts
this chip holds (``MoEConfig.n_held`` from ``first_held``; an
expert-parallel share, whose exchange with the other chips is not run).
Its expert FFN is one Pallas kernel over the held experts
(:func:`repro.kernels.sb_gemm.expert_swiglu_pallas`): no capacity, no
dropped token, no weight gathered per token, and a scanned layer's
weights read in place from their period stacks.  The decode step is vmapped
over slots, so the layer carries its own batching rule: under ``vmap``
the whole batch's tokens are one token group.

Training (:func:`moe_ffn`) splits tokens into *groups* (sharded over
the data axes); each group dispatches into per-expert capacity slots
through one-hot dispatch/combine tensors — the einsum formulation GSPMD
partitions into all-to-alls, rather than the sort/scatter formulation it
can only replicate.

The expert FFN itself is the paper's primitive incarnate: a strided-batched
GEMM with the *expert* as batch mode — ``contract("xge,xef->xgf", ...)``
walks expert weight matrices at constant stride exactly like ``sb_gemm``'s
``loa`` walk, and is planned by the engine as such.

Sharding (production rules): groups → ("pod","data"), experts → "model",
expert FFN hidden → "data"; GSPMD inserts the dispatch all-to-all between
the group-sharded and expert-sharded einsum operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.einsum import xeinsum
from repro.distributed.sharding import logical
from repro.kernels.sb_gemm import expert_swiglu_pallas
from repro.models.layers import init_dense, init_mlp, mlp

__all__ = ["init_moe", "moe_ffn", "moe_held", "route", "held_weights",
           "router_aux_loss", "EXPERT_WEIGHTS"]


def _ctr(cfg: ModelConfig):
    return functools.partial(
        xeinsum, strategy=cfg.contract_strategy, backend=cfg.contract_backend
    )


def init_moe(key, cfg: ModelConfig):
    m: MoEConfig = cfg.moe
    E, F = cfg.d_model, m.d_expert
    dt = jnp.dtype(cfg.param_dtype)
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    X = m.held  # the router spans all published experts, weights the held
    params = {
        "router": init_dense(kr, E, m.n_experts, jnp.float32),
        "wi": (jax.random.normal(k1, (X, E, F)) * E**-0.5).astype(dt),
        "wo": (jax.random.normal(k3, (X, F, E)) * F**-0.5).astype(dt),
    }
    if cfg.mlp_act == "swiglu":
        params["wg"] = (jax.random.normal(k2, (X, E, F)) * E**-0.5).astype(dt)
    if m.n_shared:
        sub = []
        for _ in range(m.n_shared):
            ks, ki = jax.random.split(ks)
            sub.append(init_mlp(ki, cfg, d_ff=m.d_shared or m.d_expert))
        params["shared"] = jax.tree.map(lambda *xs: jnp.stack(xs), *sub)
    return params


#: tokens per dispatch group (GShard "group size"); groups shard over data.
GROUP_SIZE = 4096


def _dispatch_tensors(gates, top_w, top_e, n_experts: int, capacity: int):
    """Build one-hot dispatch/combine tensors, slot-by-slot (GShard alg).

    gates: (g, t, X); top_w/top_e: (g, t, k).
    Returns dispatch (g,t,X,C) in {0,1} and combine (g,t,X,C) weights.
    """
    g, t, k = top_e.shape
    counts = jnp.zeros((g, n_experts), jnp.int32)
    dispatch = 0.0
    combine = 0.0
    for i in range(k):
        oh = jax.nn.one_hot(top_e[:, :, i], n_experts, dtype=jnp.int32)  # (g,t,X)
        pos_in_e = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]
        pos = jnp.sum(pos_in_e * oh, axis=-1)                 # (g,t) slot index
        keep = pos < capacity
        counts = counts + jnp.sum(oh, axis=1)
        slot_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (g,t,C)
        d_i = (oh.astype(jnp.float32) * keep[..., None])[..., None] * slot_oh[:, :, None, :]
        dispatch = dispatch + d_i
        combine = combine + d_i * top_w[:, :, i, None, None]
    return dispatch, combine


def route(m: MoEConfig, router, x):
    """Float32 routing of tokens ``x`` (T, E) over all ``m.n_experts``:
    softmax, top-k, renormalised.  Returns (weights, experts), each
    (T, k)."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top_w, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    return top_w / jnp.sum(top_w, axis=-1, keepdims=True), top_e


def held_weights(m: MoEConfig, top_w, top_e):
    """(T, n_held) float32 combine weights of the held experts (zero
    where a token did not choose the expert) and the (T, n_held) int32
    count of each token's choices that land on them."""
    local = top_e - m.first_held
    chose = local[..., None] == jnp.arange(m.held)          # (T, k, X)
    comb = jnp.sum(jnp.where(chose, top_w[..., None], 0.0), axis=1)
    return comb, jnp.sum(chose, axis=1, dtype=jnp.int32)


#: the routed experts' weights, the held-expert kernel's operands
EXPERT_WEIGHTS = ("wi", "wg", "wo")


def _held_ffn(cfg: ModelConfig, params, x, period):
    m: MoEConfig = cfg.moe
    if cfg.mlp_act != "swiglu":
        raise NotImplementedError("the held-expert kernel is SwiGLU")
    B, S, E = x.shape
    xt = x.reshape(B * S, E)
    with jax.named_scope("moe_route"):
        comb, held = held_weights(m, *route(m, params["router"], xt))
    with jax.named_scope("moe_experts"):
        y = expert_swiglu_pallas(
            xt, comb, *(params[k] for k in EXPERT_WEIGHTS), period)
    y = y.reshape(B, S, E)
    for i in range(m.n_shared):  # every chip computes these alike
        y = y + mlp(cfg, jax.tree.map(lambda p, i=i: p[i], params["shared"]), x)
    return y, held.reshape(B, S, -1)


def moe_held(cfg: ModelConfig, params, x, period=None):
    """The dropless held-expert layer.  x: (B, S, E) → (y (B, S, E),
    held (B, S, n_held) int32: each token's choices among the held
    experts).

    ``period``: where ``params``' routed-expert weights are a scanned
    pattern's whole stacks (``(n_periods, n_held, ...)``), the period
    whose experts run, read in place by the kernel; ``None`` for one
    layer's weights.

    Under ``vmap`` over ``x`` (the runtime's decode over slots) the
    batch's tokens run as one group: one routing, one kernel launch,
    each held expert's weights read once for all of them."""
    if period is None:   # one layer's weights: a stack of one period
        params = {k: v[None] if k in EXPERT_WEIGHTS else v
                  for k, v in params.items()}
        period = 0
    fn = jax.custom_batching.custom_vmap(functools.partial(_held_ffn, cfg))

    @fn.def_vmap
    def _one_group(axis_size, in_batched, params, x, period):
        p_batched, x_batched, period_batched = in_batched
        if any(jax.tree.leaves(p_batched)) or period_batched:
            raise NotImplementedError("expert weights batched under vmap")
        if not x_batched:
            return fn(params, x, period), (False, False)
        y, held = fn(params, x.reshape((-1,) + x.shape[2:]), period)
        return (y.reshape(x.shape), held.reshape(x.shape[:-1] + (-1,))), (
            True, True)

    return fn(params, x, jnp.asarray(period, jnp.int32))


def moe_ffn(cfg: ModelConfig, params, x, *, capacity: int | None = None):
    """x: (B, S, E) → (B, S, E), plus aux metrics dict (training)."""
    ctr = _ctr(cfg)
    m: MoEConfig = cfg.moe
    if m.held != m.n_experts:
        raise NotImplementedError("the capacity path holds every expert")
    B, S, E = x.shape
    T = B * S
    dt = x.dtype

    if cfg.moe_impl == "a2a":
        from repro.distributed.sharding import current_rules

        rules = current_rules()
        if rules is not None and T % int(
            __import__("numpy").prod(rules.mesh.devices.shape)
        ) == 0:
            from repro.distributed.moe_a2a import moe_ffn_a2a

            y = moe_ffn_a2a(cfg, params, x, rules.mesh)
            if m.n_shared:
                y_sh = mlp(cfg, jax.tree.map(lambda p: p[0], params["shared"]), x)
                for i in range(1, m.n_shared):
                    y_sh = y_sh + mlp(
                        cfg, jax.tree.map(lambda p, i=i: p[i], params["shared"]), x
                    )
                y = y + y_sh
            # router stats recomputed under auto sharding (cheap: E×X); the
            # load-balance loss gradient flows through this pass.
            gl = xeinsum("bse,ef->bsf", x.astype(jnp.float32),
                         params["router"], strategy="direct")
            gates = jax.nn.softmax(gl, axis=-1).reshape(T, -1)
            _, top_e = jax.lax.top_k(gates, m.top_k)
            aux = router_aux_loss(gates, top_e, m.n_experts)
            return logical(y, "batch", "seq_sharded", None), aux
        # no mesh context (smoke tests) → fall through to the gshard path
    group = min(GROUP_SIZE, T)
    while T % group:
        group -= 1
    n_g = T // group
    xt = x.reshape(n_g, group, E)
    xt = logical(xt, "batch", None, None)

    gate_logits = xeinsum(
        "gte,ef->gtf", xt.astype(jnp.float32), params["router"], strategy="direct"
    )
    gates = jax.nn.softmax(gate_logits, axis=-1)                  # (g,t,X)
    top_w, top_e = jax.lax.top_k(gates, m.top_k)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)

    C = capacity or max(int(m.capacity_factor * m.top_k * group / m.n_experts) + 1, 4)
    dispatch, combine = _dispatch_tensors(gates, top_w, top_e, m.n_experts, C)
    dispatch = logical(dispatch.astype(dt), "batch", None, "expert", None)
    combine = logical(combine.astype(dt), "batch", None, "expert", None)

    # dispatch: (g,t,X,C),(g,t,E) → (X,g,C,E) — data movement (all-to-all
    # under EP), evaluated direct; the GEMMs below are the paper's kernels.
    expert_in = xeinsum("gtxc,gte->xgce", dispatch, xt, strategy="direct")
    expert_in = logical(expert_in, "expert", "batch", None, None)

    # ---- expert FFN: strided-batched GEMM, batch mode = expert ----------
    wi = params["wi"].astype(dt)
    h = ctr("xgce,xef->xgcf", expert_in, wi)
    if "wg" in params:
        g_ = ctr("xgce,xef->xgcf", expert_in, params["wg"].astype(dt))
        h = jax.nn.silu(g_) * h
    else:
        h = jax.nn.gelu(h)
    h = logical(h, "expert", "batch", None, "expert_ff")
    out = ctr("xgcf,xfe->xgce", h, params["wo"].astype(dt))

    # combine back to tokens (the inverse all-to-all)
    y = xeinsum("gtxc,xgce->gte", combine, out, strategy="direct")

    if m.n_shared:
        xs = xt.reshape(B, S, E)
        y_shared = mlp(cfg, jax.tree.map(lambda p: p[0], params["shared"]), xs)
        for i in range(1, m.n_shared):
            y_shared = y_shared + mlp(
                cfg, jax.tree.map(lambda p, i=i: p[i], params["shared"]), xs
            )
        y = y + y_shared.reshape(n_g, group, E)

    aux = router_aux_loss(gates.reshape(T, -1), top_e.reshape(T, -1), m.n_experts)
    return logical(y.reshape(B, S, E), "batch", "seq_sharded", None), aux


def router_aux_loss(gates, top_e, n_experts: int):
    """Switch-style load-balancing loss + routing stats."""
    T = gates.shape[0]
    frac_tokens = jnp.zeros(n_experts).at[top_e.reshape(-1)].add(1.0) / (
        T * top_e.shape[-1]
    )
    frac_probs = jnp.mean(gates, axis=0)
    lb = n_experts * jnp.sum(frac_tokens * frac_probs)
    return {"load_balance_loss": lb, "max_expert_frac": jnp.max(frac_tokens)}
