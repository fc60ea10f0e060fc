"""Extended-transpose kernel — the paper's exceptional-case primitive.

Eight of the 36 Table II contractions force the batch walk onto an
operand's stride-1 mode ("no-first-mode rule" violations, §III-E).  The
paper's fix is an extended ``op`` parameter whose implementation "performs
a 3D tiling of B into cache".  On TPU that is exactly a Pallas BlockSpec
that stages a 3D brick — ``(u_tile, k_tile, batch_tile)`` in the operand's
native axis order — in VMEM, contracts it slice-wise on the MXU against a
2D-tiled operand, and writes regular C tiles.

Mechanically this is :func:`repro.kernels.ops.sb_contract` with
``tiles["b"] > 1`` (the brick depth); this module provides the explicitly
named entry point and the brick-depth default used by ``ops.execute_plan``.

**Demoted to a reference entry point.**  Since the tile loaders grew
native-layout (block-scatter) addressing, the "extended transpose" is no
longer a separate kernel: :func:`~repro.kernels.sb_gemm.native_gemm_pallas`
handles every exceptional ordering as an ordinary per-mode tiling, and
``contract(..., strategy="native")`` reaches it for any spec.  This
wrapper remains as the paper-named configuration (planner-chosen β brick)
for the §III-E benchmarks and the differential tests that pin it.
"""

from __future__ import annotations

from repro.core.notation import CaseKind
from repro.core.planner import make_plan
from repro.kernels.ops import EXT_BATCH_TILE, sb_contract

__all__ = ["ext_gemm", "EXT_BATCH_TILE"]


def ext_gemm(spec: str, A, B, *, batch_tile: int = EXT_BATCH_TILE,
             out_dtype=None):
    """Evaluate an exceptional-case contraction with the 3D-brick kernel.

    ``spec`` must plan as exceptional (e.g. the row-major mirrors of
    Table II cases 3.4/3.6/4.4/4.6/5.4/5.6/6.4/6.6); other specs raise.
    """
    dims = {}
    a_modes, rest = spec.replace(" ", "").split(",")
    b_modes, c_modes = rest.split("->")
    for modes, x in ((a_modes, A), (b_modes, B)):
        for m, d in zip(modes, x.shape):
            dims[m] = d
    plan = make_plan(spec, dims, allow_flatten=False)
    if plan.kind != CaseKind.EXCEPTIONAL:
        raise ValueError(f"{spec} is not exceptional (planned as {plan.kind})")
    u, v, k = plan.gemm_modes
    roles = {k: "k", v: "v", plan.sb_batch: "b"}
    if u:
        roles[u] = "u"
    if plan.nested:
        raise NotImplementedError("nest ext_gemm via ops.execute_plan")
    return sb_contract(
        plan.fspec.a_modes, plan.fspec.b_modes, plan.fspec.c_modes, A, B,
        roles=roles, tiles={"b": batch_tile}, out_dtype=out_dtype,
    )
