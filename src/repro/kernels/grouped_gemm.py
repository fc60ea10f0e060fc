"""Grouped (variable-batch) StridedBatchedGEMM as a Pallas kernel.

The paper's STRIDEDBATCHEDGEMM (Listing 1) walks ``P`` *identically
shaped* problems at a fixed stride — exactly what breaks under serving
traffic, where the live batch is ragged: each request contributes its own
``(m, n, k)`` (a prefill chunk, a decode token against its own KV
length).  Padding every group to the worst case restores uniformity but
wastes FLOPs and bandwidth quadratically in the spread; running one GEMM
per group forfeits the single-kernel dispatch the paper's primitive
exists to provide.

This module is the variable-batch extension: one kernel launch over a
*group descriptor table*.  Operands are packed row-major into flat 2D
buffers (each group padded only up to its tile multiples, never to the
largest group) and an int32 descriptor row per group carries its padded
``(m, n, k)``, the row offsets of its A/B/C blocks, and its operand
layout flags:

    desc[g] = (m_p, n_p, k_p, a_row_off, b_row_off, c_row_off,
               trans_a, trans_b)

The ``trans_*`` flags are the grouped analogue of the native-layout tile
loaders in :mod:`repro.kernels.sb_gemm`: a group whose A arrives stored
``(k, m)`` (or B stored ``(n, k)``) is consumed in place — the kernel
selects the transposed tile fetch per group instead of the caller
pre-permuting the operand.  Groups may also be *empty* (any of
``m``/``n``/``k`` zero): a ``k == 0`` group emits exact zeros, a
``m == 0``/``n == 0`` group contributes no tiles at all.

The grid is ``(group, u_blocks, v_blocks, k_blocks)`` sized by the
*largest* group; blocks outside a group's extent are predicated off with
``pl.when``, so small groups cost only their own tiles plus a predicate
test.  Within a group the inner loops are exactly the paper's kernel:
k-innermost accumulation into an f32 VMEM scratch tile, emitted on the
group's last k step.

As in :mod:`repro.kernels.sb_gemm`, the kernel is interpreted off-TPU
(:func:`~repro.kernels.sb_gemm.interpret_mode`).  On real TPUs the flat
operands should be staged
HBM→VMEM with explicit DMA (the descriptor-driven ``pl.ds`` loads below
mark the tile fetches to convert); the descriptor table itself belongs in
SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sb_gemm import interpret_mode

__all__ = [
    "GROUPED_DEFAULT_TILES",
    "GroupProblem",
    "pack_groups",
    "grouped_gemm_pallas",
    "grouped_gemm_ref",
]

#: role → tile size for the grouped kernel.  ``v`` rides the lane axis
#: (128 wide on TPU); ``u`` is kept at the sublane granularity so ragged
#: groups pad by at most 7 rows — the whole point of the variable batch.
GROUPED_DEFAULT_TILES = {"u": 8, "v": 128, "k": 128}

#: descriptor row layout (int32): padded dims, packed row offsets, and
#: per-group operand layout flags (1 = stored transposed).
DESC_FIELDS = ("m_p", "n_p", "k_p", "a_off", "b_off", "c_off",
               "trans_a", "trans_b")


class GroupProblem:
    """Static shape record of one group: ``(m, k) @ (k, n)``.

    Zero-size dims are legal — an empty group (drained request slot,
    zero-length KV segment) packs to zero rows and is predicated off in
    the kernel (``k == 0`` still emits exact zeros for its C block).
    """

    __slots__ = ("m", "n", "k")

    def __init__(self, m: int, n: int, k: int):
        if min(m, n, k) < 0:
            raise ValueError(f"group dims must be non-negative: {(m, n, k)}")
        self.m, self.n, self.k = int(m), int(n), int(k)

    def __repr__(self):
        return f"GroupProblem(m={self.m}, n={self.n}, k={self.k})"


def _pad_up(d: int, tile: int) -> int:
    return -(-d // tile) * tile


def _norm_flags(flag, n: int, name: str) -> list[bool]:
    """Broadcast a scalar trans flag, or validate a per-group list."""
    if isinstance(flag, (bool, int)):
        return [bool(flag)] * n
    flags = [bool(f) for f in flag]
    if len(flags) != n:
        raise ValueError(f"{name} needs one flag per group: got {len(flags)} "
                         f"for {n} groups")
    return flags


def pack_groups(As, Bs, tiles: dict | None = None, *, trans_a=False,
                trans_b=False):
    """Pack per-group operands into flat buffers + a descriptor table.

    ``As[g]`` is ``(m_g, k_g)`` — or ``(k_g, m_g)`` where ``trans_a``
    flags group ``g``; ``Bs[g]`` is ``(k_g, n_g)`` — or ``(n_g, k_g)``
    under ``trans_b``.  The flags (scalar or per-group sequence) record
    each operand's *storage* layout; nothing is permuted here — the
    kernel's tile fetch absorbs the layout.  Each group is zero-padded to
    its tile multiples (exact for a contraction) and appended row-wise.
    Returns ``(A_flat, B_flat, descs, problems)`` where ``descs`` is the
    ``(G, 8)`` int32 table of :data:`DESC_FIELDS` and ``problems`` the
    unpadded :class:`GroupProblem` list (needed to slice results back
    out).
    """
    tiles = {**GROUPED_DEFAULT_TILES, **(tiles or {})}
    if len(As) != len(Bs) or not As:
        raise ValueError("need one A and one B per group (at least one group)")
    ta = _norm_flags(trans_a, len(As), "trans_a")
    tb = _norm_flags(trans_b, len(Bs), "trans_b")
    problems = []
    for g, (A, B) in enumerate(zip(As, Bs)):
        if A.ndim != 2 or B.ndim != 2:
            raise ValueError(
                f"group operands must be 2D matrices: {A.shape} @ {B.shape}"
            )
        m, k_a = (A.shape[1], A.shape[0]) if ta[g] else A.shape
        k_b, n = (B.shape[1], B.shape[0]) if tb[g] else B.shape
        if k_a != k_b:
            raise ValueError(
                f"group {g}: contracted dims disagree: A gives k={k_a}, "
                f"B gives k={k_b} (trans_a={ta[g]}, trans_b={tb[g]})"
            )
        problems.append(GroupProblem(m, n, k_a))
    G = len(problems)
    mp = [_pad_up(p.m, tiles["u"]) for p in problems]
    np_ = [_pad_up(p.n, tiles["v"]) for p in problems]
    kp = [_pad_up(p.k, tiles["k"]) for p in problems]
    # stored-layout row/col extents per group (what actually packs)
    a_rows = [kp[g] if ta[g] else mp[g] for g in range(G)]
    a_cols = [mp[g] if ta[g] else kp[g] for g in range(G)]
    b_rows = [np_[g] if tb[g] else kp[g] for g in range(G)]
    b_cols = [kp[g] if tb[g] else np_[g] for g in range(G)]
    a_off = np.concatenate([[0], np.cumsum(a_rows)[:-1]])
    b_off = np.concatenate([[0], np.cumsum(b_rows)[:-1]])
    c_off = np.concatenate([[0], np.cumsum(mp)[:-1]])
    # Both layout branches of the kernel's tile fetch are traced, so each
    # flat buffer must statically admit both slice shapes — (tu, tk) and
    # its transpose for A, (tk, tv) and its transpose for B.  Pad to at
    # least one tile per dim (reads there are predicated off).
    a_min = max(tiles["u"], tiles["k"])
    b_min = max(tiles["k"], tiles["v"])
    a_wide, b_wide = max(max(a_cols), a_min), max(max(b_cols), b_min)
    a_tall, b_tall = max(sum(a_rows), a_min), max(sum(b_rows), b_min)
    rows = [
        (mp[g], np_[g], kp[g], int(a_off[g]), int(b_off[g]), int(c_off[g]),
         int(ta[g]), int(tb[g]))
        for g in range(G)
    ]
    descs = jnp.asarray(np.asarray(rows, np.int32))

    traced = any(isinstance(x, jax.core.Tracer) for x in (*As, *Bs))
    if not traced:
        # concrete operands: pack host-side — two device transfers total
        # instead of 2·G dispatches each copying the whole flat buffer
        A_np = np.zeros((a_tall, a_wide), jnp.dtype(As[0].dtype))
        B_np = np.zeros((b_tall, b_wide), jnp.dtype(Bs[0].dtype))
        for g, (A, B) in enumerate(zip(As, Bs)):
            A_np[int(a_off[g]):int(a_off[g]) + A.shape[0],
                 :A.shape[1]] = np.asarray(A)
            B_np[int(b_off[g]):int(b_off[g]) + B.shape[0],
                 :B.shape[1]] = np.asarray(B)
        return jnp.asarray(A_np), jnp.asarray(B_np), descs, problems

    A_flat = jnp.zeros((a_tall, a_wide), As[0].dtype)
    B_flat = jnp.zeros((b_tall, b_wide), Bs[0].dtype)
    for g, (A, B) in enumerate(zip(As, Bs)):
        if 0 in A.shape or 0 in B.shape:
            continue
        A_flat = jax.lax.dynamic_update_slice(
            A_flat, jnp.asarray(A), (int(a_off[g]), 0)
        )
        B_flat = jax.lax.dynamic_update_slice(
            B_flat, jnp.asarray(B), (int(b_off[g]), 0)
        )
    return A_flat, B_flat, descs, problems


def _kernel(desc_ref, a_ref, b_ref, o_ref, acc_ref, *, tu: int, tv: int,
            tk: int, out_dtype, upcast: bool):
    """One grid step of one group: accumulate / emit a C tile.

    The descriptor's ``trans_*`` flags select the tile fetch per group —
    a transposed-stored operand is read along its native rows and flipped
    in registers (VMEM), never repacked in HBM.
    """
    g = pl.program_id(0)
    u, v, kk = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    m, n, k = desc_ref[g, 0], desc_ref[g, 1], desc_ref[g, 2]
    a_off, b_off, c_off = desc_ref[g, 3], desc_ref[g, 4], desc_ref[g, 5]
    ta, tb = desc_ref[g, 6], desc_ref[g, 7]
    valid_mn = (u * tu < m) & (v * tv < n)
    valid = valid_mn & (kk * tk < k)

    @pl.when(valid & (kk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid)
    def _accumulate():
        a = jax.lax.cond(
            ta == 1,
            lambda: a_ref[pl.ds(a_off + kk * tk, tk), pl.ds(u * tu, tu)].T,
            lambda: a_ref[pl.ds(a_off + u * tu, tu), pl.ds(kk * tk, tk)],
        )
        b = jax.lax.cond(
            tb == 1,
            lambda: b_ref[pl.ds(b_off + v * tv, tv), pl.ds(kk * tk, tk)].T,
            lambda: b_ref[pl.ds(b_off + kk * tk, tk), pl.ds(v * tv, tv)],
        )
        if upcast:  # interpret-on-CPU: XLA:CPU lacks some bf16 dot thunks
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(valid & (kk == k // tk - 1))
    def _emit():
        o_ref[pl.ds(c_off + u * tu, tu), pl.ds(v * tv, tv)] = (
            acc_ref[...].astype(out_dtype)
        )

    @pl.when(valid_mn & (k == 0) & (kk == 0))
    def _emit_zero():  # empty contraction: C block is exactly zero
        o_ref[pl.ds(c_off + u * tu, tu), pl.ds(v * tv, tv)] = (
            jnp.zeros((tu, tv), out_dtype)
        )


def grouped_gemm_pallas(
    A_flat,
    B_flat,
    descs,
    *,
    grid_dims: tuple[int, int, int],
    tiles: dict | None = None,
    out_cols: int,
    out_rows: int | None = None,
    out_dtype=None,
):
    """Single-launch grouped GEMM over packed operands.

    ``grid_dims = (u_blocks_max, v_blocks_max, k_blocks_max)`` — the
    per-group block counts of the *largest* group (static; the packing in
    :func:`pack_groups` makes every per-group count ≤ these).
    ``out_cols`` is the packed C width (``max n_p``); ``out_rows`` the
    packed C height (``sum m_p`` — defaults to ``A_flat.shape[0]``, which
    is only correct when no group stores A transposed).  Group ``g``
    occupies rows ``c_off .. c_off+m_p``, columns ``0 .. n_p``.
    """
    interpret = interpret_mode(A_flat, B_flat)
    tiles = {**GROUPED_DEFAULT_TILES, **(tiles or {})}
    out_dtype = out_dtype or jnp.result_type(A_flat.dtype, B_flat.dtype)
    tu, tv, tk = tiles["u"], tiles["v"], tiles["k"]
    n_groups = int(descs.shape[0])
    grid = (n_groups,) + tuple(max(int(d), 1) for d in grid_dims)
    if out_rows is None:
        out_rows = int(A_flat.shape[0])
    out_shape = jax.ShapeDtypeStruct((out_rows, out_cols), out_dtype)

    return pl.pallas_call(
        functools.partial(
            _kernel, tu=tu, tv=tv, tk=tk, out_dtype=out_dtype,
            upcast=interpret and A_flat.dtype != jnp.float32,
        ),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=None)] * 3,
        out_specs=pl.BlockSpec(memory_space=None),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tu, tv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(descs, A_flat, B_flat)


def grouped_gemm_ref(As, Bs, *, trans_a=False, trans_b=False):
    """Reference: one ``jnp.dot`` per group (the unfused baseline)."""
    ta = _norm_flags(trans_a, len(As), "trans_a")
    tb = _norm_flags(trans_b, len(Bs), "trans_b")
    out = []
    for g, (A, B) in enumerate(zip(As, Bs)):
        a = A.T if ta[g] else A
        b = B.T if tb[g] else B
        out.append(
            jnp.dot(a, b, preferred_element_type=jnp.float32).astype(
                jnp.result_type(A.dtype, B.dtype)
            )
        )
    return out
