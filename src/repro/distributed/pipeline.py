"""GPipe-style pipeline parallelism over a mesh axis via shard_map.

For runs deeper than TP×DP can feed (or to cut cross-pod traffic), stages
are laid over an axis (default ``pod``): each device group holds
``n_layers / n_stages`` layers and microbatches flow through a
``lax.ppermute`` ring.  The schedule below is the classic fill–steady–drain
loop: at tick t, stage s processes microbatch (t - s) — compute of stage s
overlaps the permute of stage s±1 (XLA schedules the ppermute async),
which is the compute/comm overlap story for PP.

This module is deliberately self-contained and tested on small host
meshes; the dry-run meshes use pure DP×TP (pjit), with PP available as a
launch-time option for deeper-than-memory models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward", "pipeline_loss"]


def pipeline_forward(
    mesh: Mesh,
    stage_fn,              # (stage_params, x, stage_idx) -> x
    stage_params,          # pytree whose leaves have leading axis n_stages
    x,                     # (n_micro, micro_batch, ...) microbatched input
    *,
    axis: str = "pod",
):
    """Run x through n_stages stage_fns laid out over ``axis``."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= n_stages, "need ≥ n_stages microbatches to fill the pipe"

    def per_stage(params, xs):
        # params: this stage's slice (leading axis squeezed);
        # xs: (n_micro, micro, ...) — only stage 0 reads real input.
        stage = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            mb = t - stage  # microbatch this stage handles at tick t
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), keepdims=False
            )
            inp = jnp.where(stage == 0, feed, buf)
            active = (mb >= 0) & (mb < n_micro)
            y = stage_fn(params, inp, stage)
            y = jnp.where(active, y, buf)
            # ship to next stage (ring; last stage's output falls off)
            shifted = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # last stage records finished microbatches
            outs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb, 0, n_micro - 1), 0
                ),
                lambda o: o,
                outs,
            )
            return shifted, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the last stage wrote real outputs; everyone else holds zeros —
        # psum broadcasts the finished microbatches to all stages.
        return jax.lax.psum(outs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_params, P()),       # x replicated; stages slice params
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def pipeline_loss(mesh, stage_fn, stage_params, x, targets, loss_fn, *, axis="pod"):
    """Convenience: pipeline forward + replicated loss."""
    y = pipeline_forward(mesh, stage_fn, stage_params, x, axis=axis)
    return loss_fn(y, targets)
