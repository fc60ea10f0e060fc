"""Flash attention as a Pallas TPU kernel.

The §Perf hillclimb showed dense (S,T) score materialization dominates the
memory roofline term at 32k prefill (and, via GSPMD gather-repairs, the
collective term).  ``models.layers._chunked_attention`` is the XLA-level
fix; this kernel is the TPU-native version: the KV loop is the innermost
*grid* dimension, scores live only as a (bq, bk) VMEM tile, and the online
softmax state (m, l, acc) persists in VMEM scratch across KV steps.

Forward-only (training uses the XLA chunked path, which autodiffs);
validated in interpret mode against the dense oracle in
``tests/test_flash_attn.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sb_gemm import interpret_mode

__all__ = ["flash_attention", "DEFAULT_BLOCKS"]

DEFAULT_BLOCKS = {"q": 128, "k": 128}
_NEG_INF = -2.0**30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, scale: float, causal: bool,
            t_real: int, out_dtype, upcast: bool):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (bq, D)
    k = k_ref[0]                       # (bk, D)
    v = v_ref[0]
    if upcast:  # interpret-on-CPU: some bf16 dot thunks are unimplemented
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                          # (bq, bk)
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kj < t_real  # padded key columns never win the softmax
    if causal:
        ok &= qi >= kj
    s = jnp.where(ok, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(out_dtype)


def flash_attention(q, k, v, *, causal: bool = True, blocks: dict | None = None):
    """q: (BH, S, D); k/v: (BH, T, D) → (BH, S, D).

    GQA callers fold (batch, kv_head, q_per_kv) into BH and pass the kv
    head's K/V for each q head (broadcast view — XLA keeps it unmaterialized).
    S, T, D padded to block multiples by the caller or here.  Interpreted
    off-TPU (:func:`~repro.kernels.sb_gemm.interpret_mode`).
    """
    interpret = interpret_mode(q, k, v)
    blocks = {**DEFAULT_BLOCKS, **(blocks or {})}
    BH, S, D = q.shape
    T = k.shape[1]
    bq, bk = min(blocks["q"], S), min(blocks["k"], T)
    pad_q, pad_k = (-S) % bq, (-T) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    Sp, Tp = q.shape[1], k.shape[1]
    nq, nk = Sp // bq, Tp // bk
    scale = D**-0.5

    out = pl.pallas_call(
        functools.partial(
            _kernel, nk=nk, bq=bq, bk=bk, scale=scale, causal=causal,
            t_real=T, out_dtype=q.dtype,
            upcast=interpret and q.dtype != jnp.float32,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :S]
