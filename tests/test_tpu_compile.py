"""The contraction kernels compile for a TPU v5e chip.

Nothing runs here: each test lowers a kernel at real width for one chip
of a ``v5e:2x2`` topology that is described, not attached, and checks
that the TPU compiler accepts it and emits the Mosaic kernel
(``tpu_custom_call``).  Interpret-mode tests cannot see what this
catches: blocks that break the TPU tiling rule and VMEM overruns.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.contract import contract
from repro.core.notation import CaseKind, parse_spec
from repro.core.planner import make_plan
from repro.core.table2 import CASES
from repro.kernels.addressing import native_mode_tiles, role_mode_tiles
from repro.kernels.ops import EXT_BATCH_TILE, plan_roles
from repro.kernels.sb_gemm import native_gemm_pallas

#: Table II exceptional cases at 512 per mode, and minicpm-2b's MLP up
#: projection (256 tokens x d_model 2304 -> d_ff 5760)
SPECS = {
    "3.4": (CASES["3.4"].row_major(), dict.fromkeys("mnpk", 512)),
    "5.6": (CASES["5.6"].row_major(), dict.fromkeys("mnpk", 512)),
    "mlp": ("se,ef->sf", {"s": 256, "e": 2304, "f": 5760}),
}

#: contractions through ``contract``: the ``Y_ijp`` step of Tucker HOOI at
#: n=512, rank 10 — a 3-D block whose lane mode is narrower than a lane
ROUTES = {
    "tucker-y3": ("npi,nj->ijp", {"n": 512, "p": 512, "i": 10, "j": 10},
                  {"strategy": "native"}),
    "3.4-auto": (*SPECS["3.4"], {"strategy": "auto", "backend": "pallas"}),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _operands(name, sharding):
    spec, dims = SPECS[name]
    return _avals(spec, dims, sharding)


def _avals(spec, dims, sharding):
    cs = parse_spec(spec)
    a, b = (jax.ShapeDtypeStruct(tuple(dims[m] for m in modes), jnp.bfloat16,
                                 sharding=sharding)
            for modes in (cs.a_modes, cs.b_modes))
    return cs, dims, a, b


def _assert_kernel(fn, a, b):
    text = jax.jit(fn).lower(a, b).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", sorted(SPECS))
def test_native_kernel_compiles_for_v5e(name, one_chip):
    cs, dims, a, b = _operands(name, one_chip)
    tiles = native_mode_tiles(cs.a_modes, cs.b_modes, cs.c_modes, dims)
    _assert_kernel(
        lambda A, B: native_gemm_pallas(
            A, B, a_modes=cs.a_modes, b_modes=cs.b_modes, c_modes=cs.c_modes,
            mode_tiles=tiles, interpret=False),
        a, b)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_batched_plan_kernel_compiles_for_v5e(name, one_chip):
    cs, dims, a, b = _operands(name, one_chip)
    plan = make_plan(cs, dims, allow_flatten=False)
    fs = plan.fspec
    assert (fs.a_modes, fs.b_modes) == (cs.a_modes, cs.b_modes)
    tiles = {"b": EXT_BATCH_TILE} if plan.kind == CaseKind.EXCEPTIONAL else None
    mode_tiles = role_mode_tiles(fs.a_modes, fs.b_modes, fs.c_modes, dims,
                                 plan_roles(plan), tiles)
    _assert_kernel(
        lambda A, B: native_gemm_pallas(
            A, B, a_modes=fs.a_modes, b_modes=fs.b_modes, c_modes=fs.c_modes,
            mode_tiles=mode_tiles, interpret=False),
        a, b)


@pytest.mark.parametrize("name", sorted(ROUTES))
def test_contract_route_compiles_for_v5e(name, one_chip, monkeypatch):
    # the engine decides interpret mode from the platform it runs on, and
    # here that is the CPU: compile as it would on the chip
    monkeypatch.setattr("repro.kernels.sb_gemm.interpret_mode",
                        lambda *arrays: False)
    spec, dims, kw = ROUTES[name]
    _, _, a, b = _avals(spec, dims, one_chip)
    _assert_kernel(lambda A, B: contract(spec, A, B, **kw), a, b)


#: the debug tables a compiled module's text lists (source files, lines)
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _strip_metadata(hlo: str) -> list[str]:
    """The module's instructions with their metadata and the debug
    tables taken out: what the chip runs."""
    out, table = [], False
    for line in re.sub(r", metadata=\{[^}]*\}", "", hlo).splitlines():
        if line in DEBUG_TABLES:
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            out.append(line)
    return out


def test_scoped_serve_decode_compiles_to_the_same_program(one_chip,
                                                         monkeypatch):
    """The paged decode at the minicpm-2b decode-batch cell's lattice
    point (2 slots, page bucket 32, pages of 16, a 1,792-row pool) at
    published widths, 2 layers of 40 (every layer carries the same
    scopes): the ``kv_view`` / ``decode_model`` / ``kv_write`` name
    scopes change the ops' metadata and nothing else."""
    from repro.configs import get_config
    from repro.models.transformer import decode_step, init_params
    from repro.runtime.pages import PagedKV

    monkeypatch.setattr("repro.kernels.sb_gemm.interpret_mode",
                        lambda *arrays: False)
    cfg = get_config("minicpm-2b", n_periods=2, dtype="bfloat16",
                     param_dtype="bfloat16")
    kv = PagedKV(cfg, n_pages=2 * (1792 // 16) + 1, page_size=16)
    bucket, P = 2, 32

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(aval, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.key(0)))
    args = (params, jax.tree.map(aval, kv.pool),
            aval(jnp.zeros((bucket, P), jnp.int32)),
            aval(jnp.zeros((bucket,), jnp.int32)),
            aval(jnp.zeros((bucket, 1, 1), jnp.int32)))
    vm = jax.vmap(lambda p, c, t: decode_step(cfg, p, c, t),
                  in_axes=(None, 0, 0))

    def compiled():
        return kv.build_decode(vm, bucket, P).lower(*args).compile().as_text()

    scoped = compiled()
    assert 'op_name="jit(serve_decode)/kv_view/' in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "kv_view" not in plain
    ops = _strip_metadata(scoped)
    assert len(ops) > 100 and ops == _strip_metadata(plain)
