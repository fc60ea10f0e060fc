"""Shard-aware contraction execution over a device mesh.

The paper's STRIDEDBATCHEDGEMM primitive evaluates one pairwise
contraction without copies *on one device*.  This module scales the same
primitive across a ``jax.sharding.Mesh``: every shard runs the existing
planner/kernel stack (:func:`repro.core.contract.contract`) on its local
block under ``shard_map``, and explicit collectives are inserted **only
where the contracted mode is sharded** — the distributed mirror of the
paper's "no copies unless the layout forces one".

Sharding model
--------------

Operand shardings are given as per-operand ``PartitionSpec``s aligned to
the operand's mode string (``P("x", None)`` for modes ``"mk"`` shards
``m`` over mesh axis ``x``).  From these a **global mode→axis map** is
resolved with two invariants (violations raise ``ValueError``):

* a mode sharded in both operands must be sharded identically;
* one mesh axis shards at most one mode (so no tensor anywhere in a
  contraction path can need the same axis twice).

Execution of ``C = A · B`` then follows from the mode classes:

=================  ==========================================================
mode class          treatment
=================  ==========================================================
batch / free        stays sharded; no communication — every shard's block of
                    C depends only on its blocks of A and B
contracted,         each shard holds matching ``k``-slices; local GEMM gives
both operands       a *partial* C block → ``psum`` (all-reduce) over the
                    mode's axes, or ``psum_scatter`` when the caller's
                    ``out_spec`` shards an output mode over those axes
contracted,         the replicated operand is **sliced locally** to the
one operand         matching ``k``-block (``lax.axis_index`` — zero bytes
                    moved), then as above
=================  ==========================================================

A caller-requested ``out_spec`` that disagrees with the natural output
sharding is honored with ``all_gather`` (mode sharded → replicated) and
local slicing (replicated → sharded).

Everything runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the tests and
``benchmarks/fig12_sharded.py`` do exactly that.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.notation import ContractionSpec, parse_spec
from repro.distributed.sharding import specs_equal

__all__ = [
    "resolve_mode_axes",
    "local_dims",
    "ShardedPlan",
    "plan_sharded",
    "sharded_contract",
]

AxisGroup = tuple[str, ...]


def _as_group(entry) -> AxisGroup:
    """Normalize a PartitionSpec entry to a tuple of mesh-axis names."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _entry(group: AxisGroup):
    """Inverse of :func:`_as_group` — the PartitionSpec-style entry."""
    if not group:
        return None
    return group[0] if len(group) == 1 else tuple(group)


def _mode_partition(modes: str, pspec) -> dict[str, AxisGroup]:
    """Align one operand's PartitionSpec to its mode string."""
    entries = tuple(pspec) if pspec is not None else ()
    if len(entries) > len(modes):
        raise ValueError(
            f"PartitionSpec {pspec} has {len(entries)} entries for "
            f"rank-{len(modes)} operand {modes!r}"
        )
    out: dict[str, AxisGroup] = {}
    for m, e in zip(modes, entries):
        g = _as_group(e)
        if g:
            out[m] = g
    return out


def resolve_mode_axes(mode_strings, pspecs, *, mesh: Mesh) -> dict:
    """Global mode → mesh-axis entry map from per-operand PartitionSpecs.

    ``mode_strings`` and ``pspecs`` run parallel (``pspecs`` may be
    ``None`` for all-replicated, and individual entries may be ``None``).
    Values are PartitionSpec-style entries (axis name, or tuple of names
    for a multi-axis sharding).  Raises on: unknown mesh axes, a mode
    sharded differently in two operands, or one mesh axis sharding two
    different modes.
    """
    axis_names = set(mesh.axis_names)
    if pspecs is None:
        pspecs = (None,) * len(mode_strings)
    if len(pspecs) != len(mode_strings):
        raise ValueError(
            f"{len(mode_strings)} operands but {len(pspecs)} PartitionSpecs"
        )
    mode_axes: dict[str, AxisGroup] = {}
    owner: dict[str, str] = {}  # mesh axis -> mode
    for modes, pspec in zip(mode_strings, pspecs):
        for m, g in _mode_partition(modes, pspec).items():
            bad = set(g) - axis_names
            if bad:
                raise ValueError(
                    f"PartitionSpec for {modes!r} names mesh axes {sorted(bad)} "
                    f"not in mesh {tuple(mesh.axis_names)}"
                )
            if m in mode_axes and mode_axes[m] != g:
                raise ValueError(
                    f"mode {m!r} sharded over {mode_axes[m]} in one operand "
                    f"but {g} in another; shard a shared mode identically"
                )
            for ax in g:
                if owner.setdefault(ax, m) != m:
                    raise ValueError(
                        f"mesh axis {ax!r} shards both mode {owner[ax]!r} and "
                        f"{m!r}; one axis may shard at most one mode"
                    )
            mode_axes[m] = g
    return {m: _entry(g) for m, g in mode_axes.items()}


def _axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def local_dims(dims: dict, mode_axes: dict, mesh: Mesh) -> dict:
    """Per-shard dims: sharded modes divide by their axis sizes (validated)."""
    sizes = _axis_sizes(mesh)
    out = dict(dims)
    for m, entry in mode_axes.items():
        if m not in dims:
            continue
        f = math.prod(sizes[a] for a in _as_group(entry))
        if f > 1 and dims[m] % f:
            raise ValueError(
                f"mode {m!r} (size {dims[m]}) is not divisible by its "
                f"sharding {entry} (total {f} shards)"
            )
        out[m] = dims[m] // max(f, 1)
    return out


# --------------------------------------------------------------------------
# Planning
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedPlan:
    """Everything needed to lower one pairwise contraction over a mesh."""

    spec: ContractionSpec
    mesh: Mesh
    mode_axes: dict                      # global mode -> PartitionSpec entry
    a_spec: P                            # shard_map in_specs, aligned to modes
    b_spec: P
    out_spec: P                          # shard_map out_specs (final)
    out_axes: dict                       # output mode -> entry (final)
    #: per-operand local slice-ins: (axis position, axis group, mode)
    slice_a: tuple = ()
    slice_b: tuple = ()
    #: psum_scatter: (output-mode position, axis group) — reduce axes whose
    #: result lands sharded along that output mode
    scatters: tuple = ()
    #: plain all-reduce axes (contracted-mode axes not consumed by scatters)
    psum_axes: tuple = ()
    #: all_gather: (output-mode position, axis group)
    gathers: tuple = ()
    #: output-mode slice-ins applied after reduction: (position, axis group)
    slice_out: tuple = ()

    @property
    def has_communication(self) -> bool:
        return bool(self.scatters or self.psum_axes or self.gathers)

    def describe(self) -> str:
        parts = [f"{self.spec.spec_str()} @ mesh{dict(_axis_sizes(self.mesh))}"]
        if self.mode_axes:
            parts.append(
                "shard{" + ",".join(
                    f"{m}:{e}" for m, e in sorted(self.mode_axes.items())
                ) + "}"
            )
        for s in self.slice_a:
            parts.append(f"slice A[{s[2]}]@{s[1]}")
        for s in self.slice_b:
            parts.append(f"slice B[{s[2]}]@{s[1]}")
        for pos, g in self.scatters:
            parts.append(f"reduce_scatter {self.spec.c_modes[pos]}@{g}")
        if self.psum_axes:
            parts.append(f"psum{self.psum_axes}")
        for pos, g in self.gathers:
            parts.append(f"all_gather {self.spec.c_modes[pos]}@{g}")
        for pos, g in self.slice_out:
            parts.append(f"slice C[{self.spec.c_modes[pos]}]@{g}")
        if not self.has_communication:
            parts.append("no collectives")
        return " ".join(parts)


def plan_sharded(
    spec: str | ContractionSpec,
    dims: dict,
    *,
    mesh: Mesh,
    in_specs,
    out_spec: P | None = None,
) -> ShardedPlan:
    """Plan the sharded lowering of one pairwise contraction.

    ``in_specs`` is a pair of ``PartitionSpec`` (or ``None``) aligned to
    the operands' mode strings; ``out_spec`` optionally requests an
    output sharding (default: the *natural* one — batch/free modes keep
    their input sharding, contracted-mode axes are reduced away).
    """
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    if in_specs is None:
        in_specs = (None, None)
    a_pspec, b_pspec = in_specs
    mode_axes = resolve_mode_axes(
        (cs.a_modes, cs.b_modes), (a_pspec, b_pspec), mesh=mesh
    )
    local_dims(dims, mode_axes, mesh)  # divisibility check, with mode names

    a_shard = _mode_partition(cs.a_modes, a_pspec)
    b_shard = _mode_partition(cs.b_modes, b_pspec)

    # local slice-ins: operand carries a globally-sharded mode replicated —
    # each shard takes its matching block, no bytes moved
    def slices(modes: str, shard: dict) -> tuple:
        out = []
        for i, m in enumerate(modes):
            if m in mode_axes and m not in shard:
                out.append((i, _as_group(mode_axes[m]), m))
        return tuple(out)

    # reduction axes: every axis sharding a contracted mode
    reduce_axes: list[str] = []
    for m in cs.contracted:
        for ax in _as_group(mode_axes.get(m)):
            reduce_axes.append(ax)

    natural = {m: _as_group(mode_axes[m]) for m in cs.c_modes if m in mode_axes}
    if out_spec is None:
        target = dict(natural)
    else:
        entries = tuple(out_spec)
        if len(entries) > len(cs.c_modes):
            raise ValueError(
                f"out_spec {out_spec} has {len(entries)} entries for "
                f"rank-{len(cs.c_modes)} output {cs.c_modes!r}"
            )
        target = {
            m: _as_group(e)
            for m, e in zip(cs.c_modes, entries)
            if _as_group(e)
        }
        sizes = _axis_sizes(mesh)
        used: dict[str, str] = {}
        for m, g in target.items():
            f = math.prod(sizes[a] for a in g)
            bad = set(g) - set(mesh.axis_names)
            if bad:
                raise ValueError(f"out_spec names unknown mesh axes {sorted(bad)}")
            if f > 1 and dims[m] % f:
                raise ValueError(
                    f"out_spec shards mode {m!r} (size {dims[m]}) over {g} "
                    f"({f} shards): not divisible"
                )
            for ax in g:
                if used.setdefault(ax, m) != m:
                    raise ValueError(
                        f"out_spec uses mesh axis {ax!r} for two output modes"
                    )

    scatters, gathers, slice_out = [], [], []
    scattered: set[str] = set()
    for pos, m in enumerate(cs.c_modes):
        ng, tg = natural.get(m, ()), target.get(m, ())
        if tg == ng:
            continue
        if ng:
            gathers.append((pos, ng))
        if tg:
            if not ng and all(ax in reduce_axes for ax in tg):
                # the classic reduce-scatter: partial sums land sharded
                scatters.append((pos, tg))
                scattered.update(tg)
            else:
                slice_out.append((pos, tg))
    psum_axes = tuple(dict.fromkeys(a for a in reduce_axes if a not in scattered))

    final = {m: target.get(m, ()) for m in cs.c_modes}
    return ShardedPlan(
        spec=cs,
        mesh=mesh,
        mode_axes=mode_axes,
        a_spec=P(*[_entry(a_shard.get(m, ())) for m in cs.a_modes]),
        b_spec=P(*[_entry(b_shard.get(m, ())) for m in cs.b_modes]),
        out_spec=P(*[_entry(final[m]) for m in cs.c_modes]),
        out_axes={m: _entry(g) for m, g in final.items() if g},
        slice_a=slices(cs.a_modes, a_shard),
        slice_b=slices(cs.b_modes, b_shard),
        scatters=tuple(scatters),
        psum_axes=psum_axes,
        gathers=tuple(gathers),
        slice_out=tuple(slice_out),
    )


# --------------------------------------------------------------------------
# Execution
# --------------------------------------------------------------------------

def _group_index(group: AxisGroup):
    """Linear shard index over an axis group (outer axis major)."""
    idx = lax.axis_index(group[0])
    for ax in group[1:]:
        idx = idx * lax.psum(1, ax) + lax.axis_index(ax)
    return idx


def _slice_local(x, axis: int, group: AxisGroup, n_shards: int):
    n_local = x.shape[axis] // n_shards
    start = _group_index(group) * n_local
    return lax.dynamic_slice_in_dim(x, start, n_local, axis=axis)


def sharded_contract(
    spec: str | ContractionSpec,
    A,
    B,
    *,
    mesh: Mesh,
    in_specs,
    out_spec: P | None = None,
    strategy: str = "auto",
    backend: str = "xla",
    tiles: dict | None = None,
    preferred_element_type=jnp.float32,
    out_dtype=None,
    return_plan: bool = False,
):
    """Evaluate ``C = A · B`` across ``mesh``, kernels local per shard.

    Operands are *global* arrays (committed to matching shardings or
    not — ``shard_map`` distributes either way).  Every shard executes
    :func:`repro.core.contract.contract` on its local blocks with the
    given ``strategy``/``backend``/``tiles``, then the collectives from
    :func:`plan_sharded` stitch the result (see module docstring).

    With ``return_plan=True`` returns ``(C, plan)`` — the n-ary front-end
    uses the plan's ``out_axes`` to thread intermediate shardings.
    """
    from repro.core.contract import contract, infer_dims  # deferred: no cycle

    cs = parse_spec(spec) if isinstance(spec, str) else spec
    if strategy == "tuned":
        raise ValueError(
            "strategy='tuned' is single-device (the cache holds per-device "
            "measurements); pick an analytic strategy for sharded execution"
        )
    dims = infer_dims(cs, A, B)
    plan = plan_sharded(cs, dims, mesh=mesh, in_specs=in_specs, out_spec=out_spec)
    if out_spec is not None and not specs_equal(plan.out_spec, out_spec):
        # specs_equal, not ==: jax trims trailing Nones, so the planned
        # spec and the caller's spelling of the same sharding may differ
        # textually while naming identical placements
        raise AssertionError(
            f"planned out_spec {plan.out_spec} does not honor requested "
            f"{out_spec}"
        )
    sizes = _axis_sizes(mesh)

    def nshards(group: AxisGroup) -> int:
        return math.prod(sizes[a] for a in group)

    def local_fn(a, b):
        for axis, group, _ in plan.slice_a:
            a = _slice_local(a, axis, group, nshards(group))
        for axis, group, _ in plan.slice_b:
            b = _slice_local(b, axis, group, nshards(group))
        out = contract(
            plan.spec, a, b,
            strategy=strategy, backend=backend, tiles=tiles,
            preferred_element_type=preferred_element_type, out_dtype=out_dtype,
        )
        for pos, group in plan.scatters:
            out = lax.psum_scatter(
                out, _entry(group), scatter_dimension=pos, tiled=True
            )
        if plan.psum_axes:
            out = lax.psum(
                out,
                plan.psum_axes if len(plan.psum_axes) > 1 else plan.psum_axes[0],
            )
        for pos, group in plan.gathers:
            out = lax.all_gather(out, _entry(group), axis=pos, tiled=True)
        for pos, group in plan.slice_out:
            out = _slice_local(out, pos, group, nshards(group))
        return out

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(plan.a_spec, plan.b_spec),
        out_specs=plan.out_spec,
        check_vma=False,
    )
    out = fn(jnp.asarray(A), jnp.asarray(B))
    return (out, plan) if return_plan else out
