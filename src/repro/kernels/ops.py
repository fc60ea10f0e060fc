"""Jit-ready wrappers around the Pallas kernels.

``execute_plan(plan, A, B)`` is the Pallas backend of
``repro.core.contract``: it pads operands to tile multiples (zero padding
is exact for contractions), assigns mode→role for the kernel, lifts nested
batch modes through ``jax.vmap`` (paper Listing 2's outer loops), and
dispatches to :func:`native_gemm_pallas` with the role tiles mapped onto
modes — a 3D batch brick for the exceptional cases (the
extended-transpose operation, see ``ext_gemm.py``).

``execute_native(spec, A, B)`` is the layout-oblivious entry (the
``"native"`` strategy): no plan, no roles, no layout precondition — the
spec lowers directly onto :func:`native_gemm_pallas`'s per-mode grid.
Plans with no role assignment (degenerate layouts, unfused multi-mode
contractions) route here instead of falling back to the XLA executor,
so the Pallas backend never permutes or copies an operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.notation import CaseKind, ContractionSpec, parse_spec
from repro.core.planner import Plan
from repro.obs import trace as _trace
from repro.kernels.addressing import (
    LANE, kernel_extents, native_mode_tiles, padded_extent, role_mode_tiles,
)
from repro.kernels.sb_gemm import DEFAULT_TILES, native_gemm_pallas

__all__ = [
    "execute_plan", "execute_native", "sb_contract", "plan_roles",
    "padded_dim", "EXT_BATCH_TILE", "grouped_matmul",
]

#: brick depth for the extended-transpose kernel (paper §III-E): how many
#: stride-1-batched matrices are staged in VMEM per load.  The batch mode
#: is the operand's stride-1 (lane) axis, so the block rule makes the
#: brick one lane tile deep (or the whole mode, when shorter).
EXT_BATCH_TILE = LANE


def _pad_to(x, modes: str, targets: dict):
    pads = [(0, targets[m] - d) for m, d in zip(modes, x.shape)]
    if any(p for _, p in pads):
        x = jnp.pad(x, pads)
    return x


def padded_dim(d: int, tile: int) -> int:
    """Dim after padding to a tile multiple (dims ≤ one tile stay as-is)."""
    return padded_extent(d, tile)


def plan_roles(plan: Plan) -> dict | None:
    """Mode→role (u/v/k/b) assignment for the Pallas core of ``plan``.

    Returns ``None`` when the plan has no role-based sb_gemm lowering —
    degenerate layouts and multi-mode contractions whose k-modes could not
    be fused into one view; :func:`execute_plan` routes those through the
    layout-oblivious :func:`execute_native` instead.  Shared by
    :func:`execute_plan` and the autotuner's candidate enumeration
    (:mod:`repro.tuning.candidates`).
    """
    fs = plan.fspec
    kgroup = fs.contracted
    if "degenerate" in plan.notes or len(kgroup) != 1:
        return None
    roles = {kgroup: "k"}
    if plan.gemm_modes is not None:
        u, v, _ = plan.gemm_modes
        if u:
            roles[u] = "u"
        roles[v] = "v"
    else:  # pure GEMM: assign from the (≤2-mode) output
        cm = fs.c_modes
        roles[cm[-1]] = "v"
        if len(cm) == 2:
            roles[cm[0]] = "u"
    if plan.sb_batch:
        roles[plan.sb_batch] = "b"
    return roles


def sb_contract(
    spec_a: str,
    spec_b: str,
    spec_c: str,
    A,
    B,
    *,
    roles: dict,
    tiles: dict | None = None,
    out_dtype=None,
):
    """Pad → kernel → slice for a core contraction (no nested modes).
    One tile table decides both the padding and the kernel's blocks."""
    out_dtype = out_dtype or jnp.result_type(A.dtype, B.dtype)
    dims = {}
    for modes, x in ((spec_a, A), (spec_b, B)):
        for m, d in zip(modes, x.shape):
            dims[m] = d
    mode_tiles = role_mode_tiles(spec_a, spec_b, spec_c, dims, roles, tiles)
    targets = kernel_extents(spec_a, spec_b, spec_c, dims, mode_tiles)
    A = _pad_to(A, spec_a, targets)
    B = _pad_to(B, spec_b, targets)
    out = native_gemm_pallas(
        A, B, a_modes=spec_a, b_modes=spec_b, c_modes=spec_c,
        mode_tiles=mode_tiles, out_dtype=out_dtype,
    )
    slicer = tuple(slice(0, dims[m]) for m in spec_c)
    return out[slicer]


def execute_native(
    spec: str | ContractionSpec,
    A,
    B,
    *,
    tiles: dict | None = None,
    out_dtype=None,
):
    """Layout-oblivious single-kernel contraction (the ``"native"`` strategy).

    Pads each mode to its per-mode tile multiple
    (:func:`~repro.kernels.addressing.native_mode_tiles` maps the
    ``u``/``v``/``k``/``b`` role knobs onto the spec's actual modes),
    launches :func:`~repro.kernels.sb_gemm.native_gemm_pallas` on the
    operands exactly as given — any mode ordering, no permute, no copy —
    and slices the padding back off.  ``tiles`` carries the same role
    overrides as the other Pallas strategies (validated by
    :func:`repro.tuning.candidates.validate_native_tiles` when reached
    via ``contract``).

    Scalar edges (an empty output or a rank-0 operand) have no tileable
    block; they take the direct dot_general, which moves no data either.

    Differentiable: the ``pallas_call`` itself defines no useful JVP, so
    a custom VJP expresses each cotangent as the einsum-transpose
    contraction — the spec's validity rules (free modes must reach the
    output) guarantee ``(c,b)->a`` and ``(c,a)->b`` are themselves legal
    specs, so the backward passes run the native kernel too.
    """
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    out_dtype = out_dtype or jnp.result_type(A.dtype, B.dtype)
    tile_items = None if tiles is None else tuple(sorted(tiles.items()))
    if not _trace.enabled():
        return _native_diff(cs, tile_items, jnp.dtype(out_dtype), A, B)
    with _trace.span("execute_native", "kernels") as sp:
        sp.set(spec=cs.spec_str(),
               tiles=dict(tile_items) if tile_items else None)
        return _native_diff(cs, tile_items, jnp.dtype(out_dtype), A, B)


def _execute_native_impl(cs, A, B, *, tiles, out_dtype):
    if not cs.c_modes or not cs.a_modes or not cs.b_modes:
        from repro.core.contract import _direct

        return _direct(cs, A, B, jnp.float32).astype(out_dtype)
    dims: dict = {}
    for modes, x in ((cs.a_modes, A), (cs.b_modes, B)):
        for m, d in zip(modes, x.shape):
            dims[m] = d
    mode_tiles = native_mode_tiles(cs.a_modes, cs.b_modes, cs.c_modes, dims, tiles)
    targets = kernel_extents(cs.a_modes, cs.b_modes, cs.c_modes, dims,
                             mode_tiles)
    A = _pad_to(A, cs.a_modes, targets)
    B = _pad_to(B, cs.b_modes, targets)
    out = native_gemm_pallas(
        A, B, a_modes=cs.a_modes, b_modes=cs.b_modes, c_modes=cs.c_modes,
        mode_tiles=mode_tiles, out_dtype=out_dtype,
    )
    return out[tuple(slice(0, dims[m]) for m in cs.c_modes)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _native_diff(cs, tile_items, out_dtype, A, B):
    tiles = None if tile_items is None else dict(tile_items)
    return _execute_native_impl(cs, A, B, tiles=tiles, out_dtype=out_dtype)


def _native_diff_fwd(cs, tile_items, out_dtype, A, B):
    return _native_diff(cs, tile_items, out_dtype, A, B), (A, B)


def _native_diff_bwd(cs, tile_items, out_dtype, res, g):
    # Einsum-transpose rule.  Forward tiles are role assignments for the
    # forward spec's mode classes; the transposed specs reclassify, so
    # the backward kernels take the default tile grid.
    A, B = res
    dA = execute_native(
        ContractionSpec(cs.c_modes, cs.b_modes, cs.a_modes), g, B,
        out_dtype=A.dtype)
    dB = execute_native(
        ContractionSpec(cs.c_modes, cs.a_modes, cs.b_modes), g, A,
        out_dtype=B.dtype)
    return dA, dB


_native_diff.defvjp(_native_diff_fwd, _native_diff_bwd)


def grouped_matmul(As, Bs, *, tiles: dict | None = None, out_dtype=None,
                   trans_a=False, trans_b=False):
    """Variable-batch GEMM: one kernel launch over ragged groups.

    ``As[g] (m_g, k_g) @ Bs[g] (k_g, n_g)`` for every group in a single
    :func:`~repro.kernels.grouped_gemm.grouped_gemm_pallas` call — each
    group padded only to its tile multiples, never to the largest group
    (the serving runtime's ragged decode/prefill batches are exactly this
    shape class).  Returns the list of ``(m_g, n_g)`` results.

    ``trans_a``/``trans_b`` (scalar or per-group sequence) flag operands
    stored in transposed layout — ``As[g] (k_g, m_g)`` / ``Bs[g]
    (n_g, k_g)`` — which the kernel consumes in place via its descriptor
    table, the grouped counterpart of the native-layout tile loaders in
    :func:`~repro.kernels.sb_gemm.native_gemm_pallas`.  Zero-size groups
    (``m``/``n``/``k`` of 0) are legal: ``k == 0`` yields exact zeros.

    ``tiles`` overrides ``u``/``v``/``k`` of
    :data:`~repro.kernels.grouped_gemm.GROUPED_DEFAULT_TILES` — the
    grouped kernel's autotuner knob
    (:func:`repro.tuning.candidates.enumerate_grouped_candidates`).
    """
    if not _trace.enabled():
        return _grouped_matmul_impl(
            As, Bs, tiles=tiles, out_dtype=out_dtype,
            trans_a=trans_a, trans_b=trans_b,
        )
    with _trace.span("grouped_matmul", "kernels") as sp:
        sp.set(n_groups=len(As), tiles=tiles)
        return _grouped_matmul_impl(
            As, Bs, tiles=tiles, out_dtype=out_dtype,
            trans_a=trans_a, trans_b=trans_b,
        )


def _grouped_matmul_impl(As, Bs, *, tiles, out_dtype, trans_a, trans_b):
    from repro.kernels.grouped_gemm import (
        GROUPED_DEFAULT_TILES, grouped_gemm_pallas, pack_groups,
    )

    eff = {**GROUPED_DEFAULT_TILES, **(tiles or {})}
    bad = set(eff) - {"u", "v", "k"}
    if bad:
        raise ValueError(
            f"unknown grouped tile roles {sorted(bad)}; valid: ('u','v','k')"
        )
    for role, t in eff.items():
        if not isinstance(t, int) or isinstance(t, bool) or t < 1 or t % 8:
            raise ValueError(
                f"grouped tile {role}={t!r} must be a positive multiple of 8 "
                f"(TPU sublane granularity)"
            )
    A_flat, B_flat, descs, problems = pack_groups(
        As, Bs, eff, trans_a=trans_a, trans_b=trans_b,
    )
    mp_max = max(1, max(-(-p.m // eff["u"]) for p in problems))
    np_max = max(1, max(-(-p.n // eff["v"]) for p in problems))
    kp_max = max(1, max(-(-p.k // eff["k"]) for p in problems))
    out_cols = np_max * eff["v"]
    out_rows = max(eff["u"],
                   sum(-(-p.m // eff["u"]) * eff["u"] for p in problems))
    out = grouped_gemm_pallas(
        A_flat, B_flat, descs,
        grid_dims=(mp_max, np_max, kp_max), tiles=eff, out_cols=out_cols,
        out_rows=out_rows, out_dtype=out_dtype,
    )
    results, row = [], 0
    for p in problems:
        results.append(out[row:row + p.m, :p.n])
        row += -(-p.m // eff["u"]) * eff["u"]
    return results


def execute_plan(plan: Plan, A, B, *, out_dtype=None,
                 tiles: dict | None = None):
    """Pallas-backend execution of a planner :class:`Plan`.

    ``tiles`` overrides individual role tile sizes (``u``/``v``/``k``/``b``)
    on top of :data:`~repro.kernels.sb_gemm.DEFAULT_TILES` (and the
    extended-transpose brick depth for exceptional plans) — the autotuner's
    knob, also reachable from the public API via ``contract(..., tiles=...)``.
    """
    if not _trace.enabled():
        return _execute_plan_impl(plan, A, B, out_dtype=out_dtype, tiles=tiles)
    with _trace.span("execute_plan", "kernels") as sp:
        sp.set(spec=plan.spec.spec_str(), kind=plan.kind,
               nested=plan.nested or None, tiles=tiles,
               has_roles=plan_roles(plan) is not None)
        return _execute_plan_impl(plan, A, B, out_dtype=out_dtype, tiles=tiles)


def _execute_plan_impl(plan: Plan, A, B, *, out_dtype, tiles):
    fs, fd = plan.fspec, plan.fdims
    out_dtype = out_dtype or jnp.result_type(A.dtype, B.dtype)

    roles = plan_roles(plan)
    if roles is None:
        # degenerate layout or a multi-mode contraction whose k-modes could
        # not be fused into one view — no role-based sb_gemm core exists.
        # The native-layout kernel needs neither: every mode gets its own
        # grid axis, so the raw spec runs as-is (no permute, no copy, no
        # XLA fallback).
        return execute_native(plan.spec, A, B, tiles=tiles, out_dtype=out_dtype)

    # flattening reshapes are views (adjacent modes, packed layout)
    if plan.spec.a_modes != fs.a_modes:
        A = A.reshape(tuple(fd[m] for m in fs.a_modes))
    if plan.spec.b_modes != fs.b_modes:
        B = B.reshape(tuple(fd[m] for m in fs.b_modes))

    eff_tiles = dict(DEFAULT_TILES)
    if plan.kind == CaseKind.EXCEPTIONAL:
        eff_tiles["b"] = EXT_BATCH_TILE  # 3D brick: the extended transpose op
    if tiles:
        eff_tiles.update(tiles)
    tiles = eff_tiles

    def core(a, b, a_modes, b_modes, c_modes):
        return sb_contract(
            a_modes, b_modes, c_modes, a, b,
            roles=roles, tiles=tiles, out_dtype=out_dtype,
        )

    # nested batch modes → vmap at native positions (Listing 2 outer loops)
    def build(a_modes: str, b_modes: str, c_modes: str, todo: str):
        if not todo:
            return lambda a, b: core(a, b, a_modes, b_modes, c_modes)
        beta, rest = todo[0], todo[1:]
        inner = build(
            a_modes.replace(beta, ""), b_modes.replace(beta, ""),
            c_modes.replace(beta, ""), rest,
        )
        in_a = a_modes.index(beta) if beta in a_modes else None
        in_b = b_modes.index(beta) if beta in b_modes else None
        return jax.vmap(inner, in_axes=(in_a, in_b), out_axes=c_modes.index(beta))

    out = build(fs.a_modes, fs.b_modes, fs.c_modes, plan.nested)(A, B)
    return out.reshape(tuple(plan.dims[m] for m in plan.spec.c_modes))
