"""Expert parallelism via explicit fixed-capacity all-to-all (shard_map).

The GShard one-hot dispatch (``models.moe``) is the paper-faithful GSPMD
baseline, but its dispatch tensor is O(tokens × experts × capacity) — at
kimi-k2 scale (384 experts, top-8) that is tens of TB and the dry-run
shows it.  This module is the production path (§Perf hillclimb #1): a
manual shard_map pipeline in which

  1. tokens live device-local (sharded over *all* mesh axes),
  2. each device routes its tokens, sorts by destination device, and
     gathers them into a fixed-capacity ``(n_devices, cap, E)`` send
     buffer — all local index ops, no one-hot tensors;
  3. one ``lax.all_to_all`` delivers token slices to the devices owning
     their experts (experts are round-robin over devices, padded to a
     multiple of the device count);
  4. each device runs its local experts as one strided-batched GEMM —
     the paper's primitive, batch mode = local expert;
  5. the inverse all-to-all returns outputs; senders combine with their
     routing weights (pure gathers — fully differentiable).

Capacity is ``cap = T_loc·k/D·capacity_factor`` per destination device;
overflow drops (standard capacity-based routing semantics, same as the
baseline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.contract import contract

__all__ = ["moe_ffn_a2a", "pad_expert_params"]


def pad_expert_params(params: dict, n_devices: int) -> dict:
    """Pad expert-stacked weights to a multiple of the device count.

    Virtual (padded) experts have zero weights and are never routed to.
    """
    out = dict(params)
    for name in ("wi", "wg", "wo"):
        if name in params:
            w = params[name]
            X = w.shape[0]
            Xv = -(-X // n_devices) * n_devices
            if Xv != X:
                out[name] = jnp.concatenate(
                    [w, jnp.zeros((Xv - X,) + w.shape[1:], w.dtype)], 0
                )
    return out


def _ranks_within_groups(groups, order, starts):
    """Position of each element inside its group, given the stable sort."""
    n = groups.shape[0]
    slot_sorted = jnp.arange(n) - starts[groups[order]]
    return jnp.zeros(n, jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))


def moe_ffn_a2a(cfg, params, x, mesh, *, strategy=None, backend=None):
    """x: (B, S, E) → (B, S, E).  Must run under ``mesh``'s pjit context.

    ``params`` uses the standard moe layout; expert weights are padded
    in-graph to a device multiple (zero-cost for already-divisible counts).
    """
    m = cfg.moe
    axes = tuple(mesh.axis_names)
    D = int(np.prod(mesh.devices.shape))
    B, S, E = x.shape
    T = B * S
    assert T % D == 0, (T, D)
    T_loc = T // D
    k = m.top_k
    Xv = -(-m.n_experts // D) * D
    Xloc = Xv // D
    cap = max(int(T_loc * k / D * m.capacity_factor) + 1, 1)
    C2 = cap * D // Xloc  # local per-expert capacity after the exchange
    dt = x.dtype
    strategy = strategy or cfg.contract_strategy
    backend = backend or cfg.contract_backend

    wpad = pad_expert_params(params, D)
    has_g = "wg" in params

    def local_fn(xt, router, wi, wg, wo):
        # shard_map hands local blocks: xt (T_loc, E), wi/wg/wo (Xloc, E, F)
        wg_ = wg if has_g else None

        logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)            # (T_loc, X)
        top_w, top_e = lax.top_k(gates, k)
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-9)

        flat_e = top_e.reshape(-1)                         # (N,) N = T_loc·k
        flat_w = top_w.reshape(-1).astype(dt)
        tok = jnp.repeat(jnp.arange(T_loc), k)
        dest = (flat_e % D).astype(jnp.int32)              # owning device
        local_e = (flat_e // D).astype(jnp.int32)          # slot on owner

        # ---- sort by destination, fixed-capacity send buffer (gathers) --
        order = jnp.argsort(dest, stable=True)
        counts = jnp.bincount(dest, length=D)
        starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        slot = _ranks_within_groups(dest, order, starts)   # (N,)
        kept = slot < cap

        pick = starts[:, None] + jnp.arange(cap)[None]     # (D, cap)
        valid = jnp.arange(cap)[None] < jnp.minimum(counts, cap)[:, None]
        item = order[jnp.clip(pick, 0, flat_e.shape[0] - 1)]
        send = xt[tok[item]] * valid[..., None].astype(dt)  # (D, cap, E)
        send_le = jnp.where(valid, local_e[item], Xloc)     # Xloc = trash bin

        recv = lax.all_to_all(send, axes, 0, 0)             # (D, cap, E)
        recv_le = lax.all_to_all(send_le, axes, 0, 0)

        # ---- regroup by local expert (gathers again) ---------------------
        e2 = recv_le.reshape(-1)                            # (D·cap,)
        rflat = recv.reshape(-1, E)
        order2 = jnp.argsort(e2, stable=True)
        counts2 = jnp.bincount(e2, length=Xloc + 1)
        starts2 = (jnp.cumsum(counts2) - counts2).astype(jnp.int32)
        slot2 = _ranks_within_groups(e2, order2, starts2)

        pick2 = starts2[:Xloc, None] + jnp.arange(C2)[None]
        valid2 = jnp.arange(C2)[None] < jnp.minimum(counts2[:Xloc], C2)[:, None]
        item2 = order2[jnp.clip(pick2, 0, e2.shape[0] - 1)]
        ebuf = rflat[item2] * valid2[..., None].astype(dt)  # (Xloc, C2, E)

        # ---- the paper's kernel: expert-batched strided GEMM -------------
        ctr = functools.partial(contract, strategy=strategy, backend=backend)
        h = ctr("xce,xef->xcf", ebuf, wi.astype(dt))
        if has_g:
            h = jax.nn.silu(ctr("xce,xef->xcf", ebuf, wg_.astype(dt))) * h
        else:
            h = jax.nn.gelu(h)
        obuf = ctr("xcf,xfe->xce", h, wo.astype(dt))        # (Xloc, C2, E)

        # ---- route back: gather to recv layout, inverse a2a, combine -----
        ok_back = (e2 < Xloc) & (slot2 < C2)
        back_flat = obuf[jnp.clip(e2, 0, Xloc - 1),
                         jnp.clip(slot2, 0, C2 - 1)] * ok_back[:, None].astype(dt)
        back = lax.all_to_all(back_flat.reshape(D, cap, E), axes, 0, 0)

        vals = back[dest, jnp.clip(slot, 0, cap - 1)]       # (N, E)
        vals = vals * kept[:, None].astype(dt)
        y = jnp.zeros((T_loc, E), dt).at[tok].add(vals * flat_w[:, None])
        return y

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axes), P(None, None), P(axes), P(axes) if has_g else P(),
                  P(axes)),
        out_specs=P(axes),
        check_vma=False,
    )
    xt = x.reshape(T, E)
    wg_in = wpad["wg"] if has_g else jnp.zeros((), dt)
    y = fn(xt, params["router"], wpad["wi"], wg_in, wpad["wo"])
    return y.reshape(B, S, E)
