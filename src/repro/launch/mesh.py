"""Mesh construction: every mesh in the repo is built by :func:`make_mesh`.

Meshes are built by FUNCTIONS (not module constants) so importing this
module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before first jax init, and smoke
tests must keep seeing a single device.

Every axis is ``Auto``: shardings are propagated by the compiler from
the operands and from ``with_sharding_constraint``, which is what the
model zoo's logical-axis rules and ``shard_map`` lowering assume.
(``jax.make_mesh`` defaults to ``Explicit`` axes since JAX 0.7, under
which gathers over a sharded operand and sharding constraints fail to
type-check.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_mesh", "make_production_mesh", "make_host_mesh", "parse_mesh_shape",
]


def make_mesh(shape, axes, *, devices=None):
    """A device mesh of ``shape`` named ``axes`` with ``Auto`` axis types
    (over ``devices``, default: all devices of the default backend)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def parse_mesh_shape(arg: str) -> tuple[int, int]:
    """Parse a ``--mesh DATAxMODEL`` CLI argument, e.g. ``"2x4"`` → (2, 4).

    Simulate the devices on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set *before*
    the first jax import — the host device count locks at first init).
    """
    try:
        data, model = (int(p) for p in arg.lower().split("x"))
    except ValueError as e:
        raise ValueError(
            f"--mesh wants DATAxMODEL (e.g. '2x4'), got {arg!r}"
        ) from e
    if data < 1 or model < 1:
        raise ValueError(f"mesh sizes must be positive, got {arg!r}")
    return data, model


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256-chip pod; multi_pod=True adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data * model} devices, have {n}")
    return make_mesh((data, model), ("data", "model"))
