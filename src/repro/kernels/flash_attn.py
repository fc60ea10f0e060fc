"""Flash attention as Pallas TPU kernels: dense, and decode over pages.

The §Perf hillclimb showed dense (S,T) score materialization dominates the
memory roofline term at 32k prefill (and, via GSPMD gather-repairs, the
collective term).  ``models.layers._chunked_attention`` is the XLA-level
fix; :func:`flash_attention` is the TPU-native version: the KV loop is the
innermost *grid* dimension, scores live only as a (bq, bk) VMEM tile, and
the online softmax state (m, l, acc) persists in VMEM scratch across KV
steps.

:func:`paged_attention` is the serving decode's attention: one query row
per request against the K/V rows it has cached in the page-major pool of
:class:`repro.runtime.pages.PagedKV`, copied page by page from the pool
through the request's page table, never gathered into a view.  Both
kernels fold blocks of scores into the softmax state with
:func:`_fold`.

Forward-only (training uses the XLA chunked path, which autodiffs);
validated in interpret mode against dense oracles in
``tests/test_flash_attn.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import sb_gemm

__all__ = ["flash_attention", "paged_attention", "page_span",
           "pages_per_block", "DEFAULT_BLOCKS"]

DEFAULT_BLOCKS = {"q": 128, "k": 128}
_NEG_INF = -2.0**30

#: bytes of K (and as many of V) one block of the paged kernel copies at
#: most, whole 128-row tiles; at least one tile
BLOCK_BYTES = 512 * 1024


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _fold(s, ok, m_ref, l_ref, acc_ref, pv):
    """Fold one block of float32 scores ``s`` (rows, cols), of which
    ``ok`` marks the real ones, into the online softmax state in place:
    running max ``m`` and sum ``l`` (rows, 1), accumulator ``acc``.
    ``pv(p)`` gives the block's probabilities times its values, (rows,
    features) float32.  A masked score weighs exactly zero, even in a
    block with no real score."""
    s = jnp.where(ok, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + pv(p)
    m_ref[...] = m_new


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

    s = _dot(q, k, ((1,), (1,))) * scale          # (bq, bk)
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kj < t_real  # padded key columns never win the softmax
    if causal:
        ok &= qi >= kj
    _fold(s, ok, m_ref, l_ref, acc_ref,
          lambda p: _dot(p.astype(v.dtype), v, ((1,), (0,))))

    @pl.when(j == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


def flash_attention(q, k, v, *, causal: bool = True, blocks: dict | None = None):
    """q: (BH, S, D); k/v: (BH, T, D) → (BH, S, D).

    GQA callers fold (batch, kv_head, q_per_kv) into BH and pass the kv
    head's K/V for each q head (broadcast view — XLA keeps it unmaterialized).
    S, T, D padded to block multiples by the caller or here.  Interpreted
    off-TPU (:func:`~repro.kernels.sb_gemm.interpret_mode`).
    """
    interpret = sb_gemm.interpret_mode(q, k, v)
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
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :S]


# ------------------------------------------------------------ paged decode
def page_span(length, window, page_size: int, xp=jnp):
    """(start, first, end): a request with ``length`` cached rows attends
    rows ``[start, length)`` of them, held by pages ``[first, end)`` of
    its table.  ``start`` is 0, or on a window layer the first row less
    than ``window`` positions before the new row (``_attn_mask``'s ``rel
    < window``).  ``xp``: the array module of ``length`` (NumPy for the
    host's counters)."""
    start = 0 if window is None else xp.maximum(length - window + 1, 0)
    first = start // page_size
    end = xp.where(length > start, (length + page_size - 1) // page_size,
                   first)
    return start, first, end


def pages_per_block(page_size: int, row_bytes: int, span: int) -> int:
    """Pages the paged kernel copies per block: whole 128-row tiles of
    at most :data:`BLOCK_BYTES` of K, from the bytes of one cached row,
    and no more pages than the ``span`` a request can read."""
    rows = 128 * max(1, BLOCK_BYTES // (128 * row_bytes))
    return max(1, min(span, rows // page_size))


def _paged_kernel(tables_ref, lengths_ref, layer_ref, q_ref, kn_ref, vn_ref,
                  k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, *,
                  n_table: int, ps: int, ppb: int, window, scale: float,
                  softcap, upcast: bool):
    b = pl.program_id(0)
    length = lengths_ref[b]
    layer = layer_ref[0]
    start, first, end = page_span(length, window, ps)
    n_blocks = (end - first + ppb - 1) // ppb

    @pl.when(b == 0)
    def _zero():
        # a block's pages past a request's end are not copied, and weigh
        # exactly zero only if the rows left in their buffer are finite
        vbuf[...] = jnp.zeros_like(vbuf)

    def copy_block(j, slot, wait: bool):
        """Start (or wait for) the K and V copies of block ``j``'s pages
        that hold the request's rows, into buffer ``slot``."""
        base = first + j * ppb

        def page(i, carry):
            at = tables_ref[b * n_table + jnp.minimum(base + i, n_table - 1)]
            rows = pl.ds(pl.multiple_of(i * ps, ps), ps)
            for n, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                c = pltpu.make_async_copy(src.at[at, layer, 0],
                                          dst.at[slot, rows], sems.at[n, slot])
                c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, end - base), page, 0)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)
    q = q_ref[...]                                  # (G*R, G*D)
    if upcast:  # interpret-on-CPU: some bf16 dot thunks are unimplemented
        q = q.astype(jnp.float32)

    def cap(s):
        return s if softcap is None else softcap * jnp.tanh(s / softcap)

    copy_block(0, 0, wait=False)

    def block(j, carry):
        slot = j % 2
        copy_block(j, slot, wait=True)

        @pl.when(j + 1 < n_blocks)
        def _next():
            copy_block(j + 1, 1 - slot, wait=False)

        k, v = kbuf[slot], vbuf[slot]               # (ppb * ps, G*D)
        if upcast:
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        s = cap(_dot(q, k, ((1,), (1,))) * scale)   # (G*R, ppb * ps)
        pos = (first + j * ppb) * ps + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        _fold(s, (pos >= start) & (pos < length), m_ref, l_ref, o_ref,
              lambda p: _dot(p.astype(v.dtype), v, ((1,), (0,))))
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # the step's own row, last
    kn = kn_ref[...].astype(jnp.float32)            # (1, G*D)
    vn = vn_ref[...].astype(jnp.float32)
    s = cap(jnp.sum(q.astype(jnp.float32) * kn, axis=1, keepdims=True)
            * scale)
    _fold(s, True, m_ref, l_ref, o_ref, lambda p: p * vn)
    o_ref[...] = o_ref[...] / l_ref[...]


def paged_attention(q, k_new, v_new, k_pages, v_pages, tables, lengths,
                    layer, *, window: int | None = None,
                    softcap: float | None = None):
    """Decode attention reading K/V in place from a page-major pool.

    ``q``: (B, G, R, D), one query row per request, R query heads per
    kv head; ``k_new``, ``v_new``: (B, G, D), the step's own row, which
    sits at position ``lengths[b]`` and is not in the pool yet;
    ``k_pages``, ``v_pages``: pool leaves ``(n_pages, L, 1, page_size,
    G * D)``, of which layer ``layer`` is read; ``tables``: (B, P) page
    ids; ``lengths``: (B,) rows each request has cached.  Request ``b``
    attends its cached rows ``[start, lengths[b])`` (:func:`page_span`)
    and its new row, softmax in float32 with ``D**-0.5`` scaling and an
    optional ``softcap`` on the scores.  Returns (B, G, R, D) in ``q``'s
    dtype.

    One grid step per request copies the pages holding its rows
    straight from ``pool[tables[b, p], layer, 0]``, a block of pages at
    a time (:func:`pages_per_block`), double-buffered; blocks past its
    rows are neither copied nor computed.  The kv heads stay packed in
    the row's ``G * D`` lanes whatever ``D``: each query head is laid on
    its kv head's lanes, zero elsewhere, so one matmul against a block
    scores every head and one more gives every head's output on its own
    lanes, which are taken out afterwards."""
    interpret = sb_gemm.interpret_mode(q, k_pages)
    B, G, R, D = q.shape
    _, _, _, ps, F = k_pages.shape
    P = tables.shape[1]
    span = P if window is None else min(P, (window + ps - 2) // ps + 1)
    ppb = pages_per_block(ps, F * k_pages.dtype.itemsize, span)
    GR = G * R
    eye = jnp.eye(G, dtype=q.dtype)
    q_lanes = (q[:, :, :, None, :] * eye[None, :, None, :, None]).reshape(
        B, GR, F)
    row = lambda x: x.reshape(B, 1, F).astype(k_pages.dtype)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, n_table=P, ps=ps, ppb=ppb, window=window,
            scale=D**-0.5, softcap=softcap,
            upcast=interpret and q.dtype != jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, GR, F), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((None, 1, F), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((None, 1, F), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, GR, F), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb * ps, F), k_pages.dtype),
                pltpu.VMEM((2, ppb * ps, F), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((GR, 1), jnp.float32),
                pltpu.VMEM((GR, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, GR, F), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q_lanes, row(k_new),
      row(v_new), k_pages, v_pages)
    # head (g, r) sits on kv head g's lanes of its row
    out = out.reshape(B, G, R, G, D)
    return jnp.einsum("bgrhd,gh->bgrd", out,
                      eye.astype(jnp.float32)).astype(q.dtype)
