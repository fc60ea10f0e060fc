"""StridedBatchedGEMM as a Pallas TPU kernel — native-layout tile loads.

The paper's primitive (Listing 1)::

    C_p = alpha * opA(A + p*loa) @ opB(B + p*lob) + beta * C_p

On TPU the ``lda/loa`` stride walk becomes a ``BlockSpec.index_map`` that
reads HBM→VMEM tiles of each operand *in its native layout*.  This module
takes the idea to its fixed point (Matthews, arXiv:1607.00291 — the
block-scatter GEMM): :func:`native_gemm_pallas` gives the grid **one axis
per tensor mode** (output modes first, contracted modes innermost) and
each operand's index map simply selects the grid coordinates of the modes
it carries, in its own axis order (:mod:`repro.kernels.addressing`).  Any
mode ordering — a batch mode on any axis of any operand (or absent:
``lo = 0`` broadcast batching), "transposed" operands, the eight
exceptional Table II cases, the degenerate shared-batch layouts, multi-
mode contraction groups — lowers to this one kernel with no pre-permute
or copy.  "Transposition" happens on the MXU: the tile contraction is a
``jnp.einsum`` over VMEM tiles (→ ``dot_general`` with arbitrary
dimension numbers), the TPU analogue of GEMM's ``op`` flags.

The planner drives the same kernel through ``ops.sb_contract``, which
maps the classic ``u``/``v``/``k``/``b`` role tiles onto modes
(:func:`~repro.kernels.addressing.role_mode_tiles`).  The paper's
*extended transpose* (§III-E) falls out as the configuration
``tiles["b"] > 1`` — a 3D VMEM brick of the operand whose stride-1 axis
carries the batch ("3D tiling of B into cache") — see ``ext_gemm.py``.

Partial products accumulate in an f32 VMEM scratch tile and are emitted
on the last contracted step (MXU-friendly: tiles padded to multiples of
(8, 128) by ``ops.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.addressing import (
    DEFAULT_TILES,
    block_index_map,
    effective_tile,
)

__all__ = [
    "native_gemm_pallas", "DEFAULT_TILES",
    "VMEM_LIMIT_BYTES", "block_vmem_bytes", "interpret_mode",
]

#: scoped VMEM each kernel asks the compiler for: half of a TPU v5e
#: TensorCore's 128 MiB.  The compiler's default scope (16 MiB on v5e)
#: cannot hold the 128-deep brick an exceptional layout forces.
VMEM_LIMIT_BYTES = 64 * 2**20


def interpret_mode(*arrays) -> bool:
    """Whether Pallas kernels over ``arrays`` run in the interpreter.

    The one place this is decided: kernels compile on a TPU and are
    interpreted on any other platform.  A concrete array says where it
    lives; traced values run on the default backend.
    """
    for x in arrays:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return next(iter(x.devices())).platform != "tpu"
    return jax.default_backend() != "tpu"


def block_vmem_bytes(a_elems: int, b_elems: int, c_elems: int, in_dtype,
                     out_dtype, *, accumulate: bool) -> int:
    """VMEM one grid step of the native kernel holds.

    A, B and C blocks are double-buffered by the pipeline; the f32 tile
    product is a temporary, and a contracted grid axis adds the f32
    accumulator scratch.
    """
    f32_tiles = 2 if accumulate else 1
    return (2 * (a_elems + b_elems) * jnp.dtype(in_dtype).itemsize
            + 2 * c_elems * jnp.dtype(out_dtype).itemsize
            + f32_tiles * c_elems * 4)


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, tile_spec: str,
            k_axes: tuple[int, ...], out_dtype, upcast: bool):
    """One grid step: accumulate a tile contraction into VMEM scratch."""
    a, b = a_ref[...], b_ref[...]
    if upcast:  # interpret-on-CPU only: XLA:CPU lacks some bf16 dot thunks.
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    part = jnp.einsum(tile_spec, a, b, preferred_element_type=jnp.float32)

    if not k_axes:  # outer product: every C block is written exactly once
        o_ref[...] = part.astype(out_dtype)
        return

    first = functools.reduce(
        jnp.logical_and, [pl.program_id(ax) == 0 for ax in k_axes]
    )
    last = functools.reduce(
        jnp.logical_and,
        [pl.program_id(ax) == pl.num_programs(ax) - 1 for ax in k_axes],
    )

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += part

    @pl.when(last)
    def _emit():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def native_gemm_pallas(
    A,
    B,
    *,
    a_modes: str,
    b_modes: str,
    c_modes: str,
    mode_tiles: dict,
    out_dtype=None,
    interpret: bool | None = None,
):
    """Single-call contraction of ``A`` and ``B`` in their native layouts.

    ``mode_tiles`` maps every mode to its tile edge (see
    :func:`repro.kernels.addressing.native_mode_tiles`); tiles clamp to
    the mode dims, which must already be padded to multiples of the
    clamped tiles (``ops.py`` does this).  The grid is one axis per mode:
    output modes in C order (parallel), contracted modes innermost
    (arbitrary — they accumulate).  ``c_modes`` must be non-empty and
    both operands must have rank ≥ 1; ``ops.execute_native`` routes the
    scalar edge cases to the direct path instead.

    ``interpret`` defaults to :func:`interpret_mode` of the operands;
    pass ``False`` to compile for a TPU that is described, not attached.
    """
    if interpret is None:
        interpret = interpret_mode(A, B)
    out_dtype = out_dtype or jnp.result_type(A.dtype, B.dtype)
    dims: dict = {}
    for modes, x in ((a_modes, A), (b_modes, B)):
        for m, d in zip(modes, x.shape):
            dims[m] = d
    contracted = "".join(
        m for m in a_modes if m in b_modes and m not in c_modes
    )
    grid_modes = c_modes + contracted
    eff = {m: effective_tile(dims[m], mode_tiles[m]) for m in grid_modes}
    for m in grid_modes:
        assert dims[m] % eff[m] == 0, (m, dims[m], eff[m])
    grid = tuple(dims[m] // eff[m] for m in grid_modes)
    k_axes = tuple(range(len(c_modes), len(grid_modes)))

    def block(modes):
        shape = tuple(eff[m] for m in modes)
        return pl.BlockSpec(shape, block_index_map(modes, grid_modes)), shape

    a_spec, _ = block(a_modes)
    b_spec, _ = block(b_modes)
    c_spec, c_block = block(c_modes)
    out_shape = jax.ShapeDtypeStruct(tuple(dims[m] for m in c_modes), out_dtype)
    tile_spec = f"{a_modes},{b_modes}->{c_modes}"

    return pl.pallas_call(
        functools.partial(_kernel, tile_spec=tile_spec, k_axes=k_axes,
                          out_dtype=out_dtype,
                          upcast=interpret and A.dtype != jnp.float32),
        grid=grid,
        in_specs=[a_spec, b_spec],
        out_specs=c_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(c_block, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("parallel",) * len(c_modes) + ("arbitrary",) * len(k_axes)
            ),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(A, B)
