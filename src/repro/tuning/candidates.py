"""Legal execution candidates for one pairwise contraction.

The paper's Figs. 5–8 show that the fastest evaluation mode — flattened
GEMM, StridedBatchedGEMM over one batch mode or another, or the
exceptional (extended-transpose) kernel — depends on the shape, and no
static rule picks the winner everywhere (Peise et al. 2014 measure the
same for analytic prediction models).  The autotuner therefore treats
plan selection as an empirical search: this module enumerates the finite
set of *legal* ways to run a :class:`~repro.core.notation.ContractionSpec`
at given dims/dtype — strategy × backend × (for Pallas) a small grid of
tile configurations validated against the VMEM budget — and
:mod:`repro.tuning.measure` times them.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.notation import CaseKind, ContractionSpec, parse_spec
from repro.core.planner import Plan, make_plan
from repro.kernels.addressing import (
    effective_tile, kernel_extents, native_mode_tiles, role_mode_tiles,
)
from repro.kernels.ops import EXT_BATCH_TILE, plan_roles
from repro.kernels.sb_gemm import (
    DEFAULT_TILES, VMEM_LIMIT_BYTES, block_vmem_bytes,
)

__all__ = [
    "Candidate",
    "enumerate_candidates",
    "enumerate_grouped_candidates",
    "validate_tiles",
    "validate_plan_tiles",
    "validate_native_tiles",
    "estimate_vmem_bytes",
    "estimate_native_vmem_bytes",
    "estimate_grouped_vmem_bytes",
    "VMEM_BUDGET_BYTES",
    "PALLAS_TILE_GRID",
    "GROUPED_TILE_GRID",
]

#: per-candidate VMEM budget: the scoped limit every kernel asks the
#: compiler for, against the footprint of
#: :func:`~repro.kernels.sb_gemm.block_vmem_bytes` (double-buffered
#: blocks plus f32 product and accumulator).
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES

#: the Pallas tile-config grid: overrides merged over ``DEFAULT_TILES``.
#: Deliberately small — the measurement harness multiplies it by the
#: number of strategies, and configs that clamp to identical effective
#: tiles for the given dims are deduplicated before timing.
PALLAS_TILE_GRID = (
    {},                        # DEFAULT_TILES: 128³ (the MXU-native tile)
    {"u": 256},
    {"k": 256},
    {"u": 64, "k": 64},
    {"u": 512, "k": 64},
)

#: tile grid for the grouped (variable-batch) kernel: overrides merged
#: over :data:`~repro.kernels.grouped_gemm.GROUPED_DEFAULT_TILES`.  The
#: ``u`` axis stays small (ragged groups pad per-group to ``u``), the
#: lane axis ``v`` and reduction ``k`` trade VMEM residency for reload
#: traffic exactly as in :data:`PALLAS_TILE_GRID`.
GROUPED_TILE_GRID = (
    {},                         # GROUPED_DEFAULT_TILES: u=8, v=128, k=128
    {"u": 16},
    {"u": 32, "k": 64},
    {"v": 256},
    {"k": 256},
)

_ROLE_NAMES = ("u", "v", "k", "b")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One executable configuration: how to run a contraction.

    ``tiles`` is a sorted item tuple (hashable; empty for XLA backends) of
    role→tile overrides applied on top of the kernel defaults.
    """

    strategy: str                               # auto | flatten | batched | direct
    backend: str                                # xla | pallas
    tiles: tuple[tuple[str, int], ...] = ()

    @property
    def tiles_dict(self) -> dict:
        return dict(self.tiles)

    def key(self) -> str:
        """Stable string form used as the cache's result key."""
        base = f"{self.backend}:{self.strategy}"
        if self.tiles:
            body = ",".join(f"{r}={t}" for r, t in self.tiles)
            base += f"[{body}]"
        return base

    @classmethod
    def from_key(cls, key: str) -> "Candidate":
        tiles: tuple[tuple[str, int], ...] = ()
        if "[" in key:
            key, _, body = key.partition("[")
            body = body.rstrip("]")
            tiles = tuple(
                (r, int(t)) for r, t in (item.split("=") for item in body.split(","))
            )
        backend, _, strategy = key.partition(":")
        if not strategy or backend not in ("xla", "pallas"):
            raise ValueError(f"malformed candidate key {key!r}")
        return cls(strategy=strategy, backend=backend, tiles=tiles)


def _check_tile_values(tiles: dict) -> None:
    """Shared role-name/value checks for every tile override form."""
    bad = set(tiles) - set(_ROLE_NAMES)
    if bad:
        raise ValueError(
            f"unknown tile roles {sorted(bad)}; valid roles are {_ROLE_NAMES}"
        )
    for role, t in tiles.items():
        if not isinstance(t, int) or isinstance(t, bool) or t < 1:
            raise ValueError(f"tile {role}={t!r} must be a positive int")
        if role in ("u", "v", "k") and t % 8 != 0:
            raise ValueError(
                f"tile {role}={t} is not divisible by 8 (TPU sublane granularity)"
            )


def validate_tiles(tiles: dict) -> None:
    """Validate a user/tuner tile override; raises ``ValueError``.

    Rules: keys must be kernel roles (``u``/``v``/``k``/``b``); values
    positive ints; ``u``/``v``/``k`` multiples of 8 (the TPU sublane
    granularity — non-divisible tiles force masked partial lanes the MXU
    loader rejects); and the implied VMEM footprint
    (:func:`~repro.kernels.sb_gemm.block_vmem_bytes` in f32,
    conservatively at the requested — unclamped — tile sizes) must fit
    :data:`VMEM_BUDGET_BYTES`.
    """
    _check_tile_values(tiles)
    full = {**DEFAULT_TILES, **tiles}
    u, v, k, b = (full[r] for r in _ROLE_NAMES)
    # worst-case blocks: A=(b,u,k), B=(b,k,v), C=(b,u,v)
    bytes_needed = block_vmem_bytes(
        b * u * k, b * k * v, b * u * v, jnp.float32, jnp.float32,
        accumulate=True)
    if bytes_needed > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"tiles {full} are oversized: ~{bytes_needed / 2**20:.1f} MiB of VMEM "
            f"blocks exceeds the {VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget"
        )


def validate_plan_tiles(plan: Plan, tiles: dict, dtype) -> None:
    """Validate a tile override for a Pallas ``plan``; raises ``ValueError``.

    The gate ``contract(tiles=...)`` and the candidate enumeration share:
    :func:`validate_tiles` on the requested tiles (an exceptional plan at
    the kernel's brick depth unless ``b`` is given), then the footprint
    of the blocks the kernel actually runs, after the block rule raised
    them (:func:`estimate_vmem_bytes`).  A plan with no role assignment
    runs the native kernel, and takes its check.
    """
    checked = dict(tiles)
    if plan.kind == CaseKind.EXCEPTIONAL and "b" not in checked:
        checked["b"] = EXT_BATCH_TILE
    validate_tiles(checked)
    roles = plan_roles(plan)
    if roles is None:
        validate_native_tiles(plan.spec, plan.dims, tiles, dtype=dtype)
        return
    bytes_needed = estimate_vmem_bytes(
        plan, roles, {**DEFAULT_TILES, **checked}, dtype)
    if bytes_needed > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"tiles {checked} are oversized for {plan.spec.spec_str()} as "
            f"the kernel runs them: ~{bytes_needed / 2**20:.1f} MiB of VMEM "
            f"blocks exceeds the {VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget"
        )


def estimate_vmem_bytes(plan: Plan, roles: dict, tiles: dict, dtype) -> int:
    """VMEM bytes for one grid step of ``plan`` under ``tiles``.

    The footprint of :func:`~repro.kernels.sb_gemm.block_vmem_bytes` for
    the blocks the kernel runs: role tiles raised to the block rule
    (:func:`~repro.kernels.addressing.role_mode_tiles`) and clamped to
    the mode dims, exactly as ``ops.sb_contract`` builds them.
    """
    a_modes, b_modes, c_modes = _core_modes(plan, roles)
    dims = {m: plan.fdims[m] for m in roles}
    mode_tiles = role_mode_tiles(a_modes, b_modes, c_modes, dims, roles, tiles)
    return _footprint(a_modes, b_modes, c_modes, dims, mode_tiles, dtype)


def _core_modes(plan: Plan, roles: dict) -> tuple[str, str, str]:
    """The kernel's mode strings: nested batch modes are vmapped outside."""
    fs = plan.fspec
    return tuple("".join(m for m in modes if m in roles)
                 for modes in (fs.a_modes, fs.b_modes, fs.c_modes))


def _footprint(a_modes, b_modes, c_modes, dims, mode_tiles, dtype) -> int:
    ext = kernel_extents(a_modes, b_modes, c_modes, dims, mode_tiles)

    def block_elems(modes: str) -> int:
        n = 1
        for m in modes:
            n *= effective_tile(ext[m], mode_tiles[m])
        return n

    contracted = set(a_modes) & set(b_modes) - set(c_modes)
    return block_vmem_bytes(
        block_elems(a_modes), block_elems(b_modes), block_elems(c_modes),
        dtype, dtype, accumulate=bool(contracted))


def estimate_native_vmem_bytes(
    spec: str | ContractionSpec, dims: dict, tiles: dict, dtype
) -> int:
    """VMEM bytes for one grid step of the ``"native"`` strategy.

    The native kernel carries a *per-mode* tile table
    (:func:`~repro.kernels.addressing.native_mode_tiles`), so its working
    set is the product of every mode's clamped tile per operand block —
    not the fixed 4-role worst case of :func:`validate_tiles`.  With
    several batch modes a brick depth multiplies *each* block once per
    mode, which the role formula undercounts; conversely a spec with few
    modes can afford tiles the role formula would reject.
    """
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    mode_tiles = native_mode_tiles(cs.a_modes, cs.b_modes, cs.c_modes, dims, tiles)
    return _footprint(cs.a_modes, cs.b_modes, cs.c_modes, dims, mode_tiles,
                      dtype)


def validate_native_tiles(
    spec: str | ContractionSpec, dims: dict, tiles: dict, *, dtype=jnp.float32
) -> None:
    """Validate a tile override for ``strategy="native"``; raises
    ``ValueError``.

    Role names/values follow the same rules as :func:`validate_tiles`,
    but the VMEM check accounts for the per-mode tile table the native
    strategy carries (:func:`estimate_native_vmem_bytes`) — so oversized
    configs are rejected at enumeration/call time, never at launch.
    """
    _check_tile_values(tiles)
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    if not cs.c_modes or not cs.a_modes or not cs.b_modes:
        return  # scalar edge: execute_native takes the direct path
    bytes_needed = estimate_native_vmem_bytes(cs, dims, tiles, dtype)
    if bytes_needed > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"native tiles {tiles} are oversized for {cs.spec_str()} at "
            f"{dims}: ~{bytes_needed / 2**20:.1f} MiB of per-mode VMEM "
            f"blocks exceeds the {VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget"
        )


def _effective_tiles(plan: Plan, roles: dict, tiles: dict) -> tuple:
    """Per-mode blocks after the block rule and clamping — the dedup
    signature."""
    a_modes, b_modes, c_modes = _core_modes(plan, roles)
    dims = {m: plan.fdims[m] for m in roles}
    mode_tiles = role_mode_tiles(a_modes, b_modes, c_modes, dims, roles, tiles)
    ext = kernel_extents(a_modes, b_modes, c_modes, dims, mode_tiles)
    return tuple(sorted(
        (m, effective_tile(ext[m], t)) for m, t in mode_tiles.items()))


def estimate_grouped_vmem_bytes(tiles: dict, dtype) -> int:
    """VMEM bytes for one grid step of the grouped kernel under ``tiles``.

    One step stages an A tile ``(u, k)``, a B tile ``(k, v)``, the C tile
    ``(u, v)`` in the operand dtype plus the f32 accumulator scratch —
    the grouped analogue of :func:`estimate_vmem_bytes` (no batch brick:
    the group axis walks whole problems, not tiles).
    """
    from repro.kernels.grouped_gemm import GROUPED_DEFAULT_TILES

    full = {**GROUPED_DEFAULT_TILES, **tiles}
    u, v, k = full["u"], full["v"], full["k"]
    itemsize = jnp.dtype(dtype).itemsize
    return (u * k + k * v + u * v) * itemsize + u * v * 4


def enumerate_grouped_candidates(
    problems,
    *,
    dtype=jnp.float32,
) -> list[Candidate]:
    """Legal tile configs for one grouped-GEMM call over ``problems``.

    ``problems`` is the per-group shape list — ``(m, n, k)`` tuples or
    :class:`~repro.kernels.grouped_gemm.GroupProblem` records; only its
    non-emptiness matters here, because unlike the sb_gemm BlockSpecs
    the grouped kernel never clamps a tile to the dims — every group
    pads *up* to the full tile, so every distinct ``(u, v, k)`` is a
    genuinely different kernel whatever the shapes.  Each config from
    :data:`GROUPED_TILE_GRID` that fits the VMEM budget becomes a
    ``Candidate("grouped", "pallas", tiles)``; the per-group ``jnp.dot``
    loop rides along as the unfused XLA baseline
    (``Candidate("grouped", "xla")``).
    """
    from repro.kernels.grouped_gemm import GROUPED_DEFAULT_TILES

    if not problems:
        raise ValueError("need at least one group")

    out = [Candidate("grouped", "xla")]
    seen: set[tuple] = set()
    for cfg in GROUPED_TILE_GRID:
        tiles = {**GROUPED_DEFAULT_TILES, **cfg}
        # dedup on the merged config only (see docstring: no clamping)
        eff = (tiles["u"], tiles["v"], tiles["k"])
        if eff in seen:
            continue
        seen.add(eff)
        if estimate_grouped_vmem_bytes(tiles, dtype) > VMEM_BUDGET_BYTES:
            continue
        out.append(Candidate("grouped", "pallas", tuple(sorted(cfg.items()))))
    return out


def default_backends() -> tuple[str, ...]:
    """Backends worth measuring on this host.

    Pallas kernels run in *interpret* mode off-TPU — orders of magnitude
    slower than XLA and never the winner — so CPU/GPU hosts only tune the
    XLA candidates by default.  Pass ``backends=`` explicitly to override
    (tests do, with tiny shapes).
    """
    return ("xla", "pallas") if jax.default_backend() == "tpu" else ("xla",)


def _plans_differ(p: Plan, q: Plan) -> bool:
    return (p.kind, p.flatten_groups, p.sb_batch, p.nested) != (
        q.kind, q.flatten_groups, q.sb_batch, q.nested
    )


def enumerate_candidates(
    spec: str | ContractionSpec,
    dims: dict,
    *,
    dtype=jnp.float32,
    backends: tuple[str, ...] | None = None,
) -> list[Candidate]:
    """All legal execution candidates for ``spec`` at ``dims``/``dtype``.

    XLA candidates: ``"auto"`` (Algorithm 2 with flattening), ``"batched"``
    (only when it plans differently from auto), and ``"direct"`` (the
    good-XLA-user reference).  Pallas candidates: each distinct plan ×
    each tile config from :data:`PALLAS_TILE_GRID` (exceptional plans at
    the kernel's brick depth) that clamps to a unique effective tiling
    and fits the VMEM budget — plus the layout-oblivious
    ``"native"`` strategy, whose per-mode tile table is validated with
    :func:`validate_native_tiles` (it is legal for *every* non-scalar
    spec, including the degenerate/multi-k plans that have no role-based
    sb_gemm lowering).
    """
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    if backends is None:
        backends = default_backends()

    if not cs.c_modes or not cs.a_modes or not cs.b_modes:
        # scalar input/output: no matrix core exists — direct is the only
        # evaluation (and the planner would reject the spec).
        return [Candidate("direct", "xla")]

    plan_auto = make_plan(cs, dims)
    plan_noflat = make_plan(cs, dims, allow_flatten=False)

    out = [Candidate("auto", "xla")]
    if _plans_differ(plan_auto, plan_noflat):
        out.append(Candidate("batched", "xla"))
    out.append(Candidate("direct", "xla"))

    if "pallas" in backends:
        seen: set[tuple] = set()
        strat_plans = [("auto", plan_auto)]
        if _plans_differ(plan_auto, plan_noflat):
            strat_plans.append(("batched", plan_noflat))
        for strategy, plan in strat_plans:
            roles = plan_roles(plan)
            if roles is None:
                continue  # no single-kernel Pallas lowering for this plan
            for cfg in PALLAS_TILE_GRID:
                tiles = {**DEFAULT_TILES, **cfg}
                if plan.kind == CaseKind.EXCEPTIONAL:
                    # the brick depth contract() validates and runs with
                    tiles["b"] = EXT_BATCH_TILE
                eff = _effective_tiles(plan, roles, tiles)
                if (strategy, eff) in seen:
                    continue
                seen.add((strategy, eff))
                try:
                    # the same gate contract(tiles=...) applies — a
                    # candidate must never be rejected at execution time
                    validate_plan_tiles(plan, cfg, dtype)
                except ValueError:
                    continue
                out.append(
                    Candidate(strategy, "pallas", tuple(sorted(cfg.items())))
                )

        seen_native: set[tuple] = set()
        for grid_cfg in PALLAS_TILE_GRID:
            mode_tiles = native_mode_tiles(
                cs.a_modes, cs.b_modes, cs.c_modes, dims, grid_cfg
            )
            eff = tuple(sorted(
                (m, effective_tile(dims[m], t)) for m, t in mode_tiles.items()
            ))
            if eff in seen_native:
                continue
            seen_native.add(eff)
            try:
                # same gate as contract(strategy="native", tiles=...) — a
                # candidate must never be rejected at execution time
                validate_native_tiles(cs, dims, grid_cfg, dtype=dtype)
            except ValueError:
                continue
            out.append(Candidate("native", "pallas", tuple(sorted(grid_cfg.items()))))
    return out
