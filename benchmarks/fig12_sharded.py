"""Fig. 12 (repo extension): sharded contraction execution over a mesh.

Times the shard-aware lowering (:mod:`repro.distributed.contract`) against
the single-device engine on a mesh over the devices that exist (axes
``x``/``y``; 2×4 on eight devices — simulate them on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), for the three
sharding regimes the planner distinguishes:

* **batch-sharded** — the strided-batch mode lives on a mesh axis; zero
  collectives, the embarrassingly-parallel regime;
* **contracted-sharded** — partial products + ``psum`` (and the
  ``reduce-scatter`` variant when the output stays sharded);
* **comm-aware path** — a 3-operand chain whose sharded path cost
  includes the collective term.

Simulated host devices share one CPU, so wall-clock *speedups* there are
not meaningful — what the numbers show is the collective overhead, and
the ``derived`` column carries the real payload: max |Δ| against the
single-device result (the differential guarantee) plus the collective
structure.  Run on real devices, the same code path is the scaling story.

Everything runs in this process: a process that holds an accelerator
holds it alone, so there is no child to hand the devices to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import time_fn
from repro.core.contract import contract, infer_dims
from repro.core.einsum import xeinsum
from repro.core.notation import parse_spec
from repro.distributed.contract import plan_sharded, sharded_contract
from repro.launch.mesh import make_mesh

__all__ = ["run"]


def _mesh():
    """x×y mesh over the largest power-of-two number of devices."""
    n = 1 << (len(jax.devices()).bit_length() - 1)
    x = 2 if n >= 4 else 1
    return make_mesh((x, n // x), ("x", "y"), devices=jax.devices()[:n])


def run(quick: bool = False):
    rows = []
    mesh = _mesh()
    n = 64 if quick else 256
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def row(name, spec, operands, in_specs, out_spec=None, strategy="auto"):
        single_us = time_fn(
            lambda *ops: contract(spec, *ops, strategy=strategy), *operands
        )
        sharded_us = time_fn(
            lambda *ops: sharded_contract(
                spec, *ops, mesh=mesh, in_specs=in_specs, out_spec=out_spec,
                strategy=strategy,
            ),
            *operands,
        )
        cs = parse_spec(spec)
        plan = plan_sharded(
            cs, infer_dims(cs, *operands), mesh=mesh, in_specs=in_specs,
            out_spec=out_spec,
        )
        ref = contract(spec, *operands, strategy=strategy)
        got = sharded_contract(
            spec, *operands, mesh=mesh, in_specs=in_specs, out_spec=out_spec,
            strategy=strategy,
        )
        err = float(jnp.max(jnp.abs(jnp.asarray(got) - ref)))
        coll = "+".join(
            (["scatter"] if plan.scatters else [])
            + (["psum"] if plan.psum_axes else [])
            + (["gather"] if plan.gathers else [])
        ) or "none"
        rows.append((name, sharded_us,
                     f"single_us={single_us:.1f};collectives={coll};"
                     f"maxerr={err:.1e}"))

    # batch-sharded strided-batched GEMM (paper case 1.3 regime): p on y
    row("fig12_batch_sharded", "mk,pkn->pmn",
        (arr(n, n), arr(mesh.shape["y"], n, n)),
        (P(None, None), P("y", None, None)))
    # contracted mode sharded in both operands -> psum
    row("fig12_contracted_psum", "mk,kn->mn",
        (arr(n, n), arr(n, n)),
        (P("x", "y"), P("y", None)))
    # same, output kept sharded -> reduce-scatter
    row("fig12_reduce_scatter", "mk,kn->mn",
        (arr(n, n), arr(n, n)),
        (P("x", "y"), P("y", None)), out_spec=P("x", "y"))
    # fully replicated (every shard computes the whole thing)
    row("fig12_replicated", "mk,kn->mn",
        (arr(n, n), arr(n, n)),
        (P(None, None), P(None, None)))

    # comm-aware n-ary path: chain with the contracted mode sharded
    A, B, C = arr(n, n), arr(n, n), arr(n, n)
    in_specs = (P(None, "y"), P("y", None), P(None, None))
    chain_single = time_fn(lambda a, b, c: xeinsum("ik,kn,nj->ij", a, b, c),
                           A, B, C)
    chain_sharded = time_fn(
        lambda a, b, c: xeinsum("ik,kn,nj->ij", a, b, c, mesh=mesh,
                                in_specs=in_specs),
        A, B, C,
    )
    ref = xeinsum("ik,kn,nj->ij", A, B, C)
    got = xeinsum("ik,kn,nj->ij", A, B, C, mesh=mesh, in_specs=in_specs)
    err = float(jnp.max(jnp.abs(got - ref)))
    rows.append(("fig12_chain_sharded", chain_sharded,
                 f"single_us={chain_single:.1f};maxerr={err:.1e}"))
    return rows
