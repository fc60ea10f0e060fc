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
import numpy as np
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


#: the minicpm-2b decode-batch cell's lattice point: 2 slots, page bucket
#: 32, pages of 16 rows, a 1,792-row pool per slot
SLOTS, PAGES, PAGE_ROWS, POOL_ROWS = 2, 32, 16, 1792


def _paged_steps(one_chip):
    """(PagedKV, abstract decode arguments, vmapped decode) for minicpm-2b
    at published widths, 2 layers of 40 (every layer is the same)."""
    from repro.configs import get_config
    from repro.models.transformer import decode_step, init_params
    from repro.runtime.pages import PagedKV

    cfg = get_config("minicpm-2b", n_periods=2, dtype="bfloat16",
                     param_dtype="bfloat16")
    kv = PagedKV(cfg, n_pages=SLOTS * (POOL_ROWS // PAGE_ROWS) + 1,
                 page_size=PAGE_ROWS)

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(aval, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.key(0)))
    args = (params, jax.tree.map(aval, kv.pool),
            aval(jnp.zeros((SLOTS, PAGES), jnp.int32)),
            aval(jnp.zeros((SLOTS,), jnp.int32)),
            aval(jnp.zeros((SLOTS, 1, 1), jnp.int32)))
    vm = jax.vmap(lambda p, c, t: decode_step(cfg, p, c, t),
                  in_axes=(None, 0, 0))
    return kv, args, vm


def test_scoped_serve_decode_compiles_to_the_same_program(one_chip,
                                                         monkeypatch):
    """The paged decode at the decode-batch cell's lattice point: the
    ``decode_model`` / ``kv_write`` name scopes change the ops' metadata
    and nothing else."""
    monkeypatch.setattr("repro.kernels.sb_gemm.interpret_mode",
                        lambda *arrays: False)
    kv, args, vm = _paged_steps(one_chip)

    def compiled():
        return kv.build_decode(vm, SLOTS, PAGES).lower(
            *args).compile().as_text()

    scoped = compiled()
    for scope in ("decode_model", "kv_write"):
        assert f'op_name="jit(serve_decode)/{scope}/' in scoped, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "decode_model" not in plain and "kv_write" not in plain
    ops = _strip_metadata(scoped)
    assert len(ops) > 100 and ops == _strip_metadata(plain)


#: an array-shaped HLO value: ``bf16[225,2,1,16,2304]{4,3,2,1,0:T(8,128)...}``
_ARRAY = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]\{([0-9,]*)[:}]")
#: one instruction: name, result array, layout and memory space, opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = [a-z]+[0-9]*\[([0-9,]*)\]"
                    r"\{([0-9,]*)(:[^}]*)?\} ([a-z-]+)\(", re.M)


def _dims(text: str) -> tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d)


def _row_major(dims, minor_to_major) -> bool:
    """Whether a layout stores ``dims`` in their logical order, the
    first dimension outermost (unit dimensions may sit anywhere)."""
    order = [d for d in minor_to_major if dims[d] != 1]
    return order == sorted(order, reverse=True)


def test_paged_pool_is_page_major_and_never_relaid_out(one_chip,
                                                      monkeypatch):
    """The pool's token leaves enter the compiled decode and commit
    page-major and lane-dense (the row's features, a multiple of 128, on
    the lanes), and neither program holds a copy of a whole pool leaf
    into another layout: every value of a pool leaf's size keeps its
    row-major layout, and each leaf is copied into device memory at
    most once, the out-of-place result."""
    monkeypatch.setattr("repro.kernels.sb_gemm.interpret_mode",
                        lambda *arrays: False)
    kv, args, vm = _paged_steps(one_chip)
    texts = _assert_pool_never_relaid_out(kv, args, vm, SLOTS, PAGES,
                                          one_chip)
    _assert_reads_pages_in_place(kv, texts["serve_decode"], SLOTS, PAGES)


def _assert_pool_never_relaid_out(kv, args, vm, slots, pages, one_chip):
    """What the test above checks, for any config's paged decode."""
    pool = args[1]
    leaves = jax.tree.leaves(pool)
    assert leaves and all(x.shape[0] == kv.n_pages and x.shape[-1] % 128 == 0
                          for x in leaves)
    sizes = {x.size for x in leaves}
    stage = jax.eval_shape(kv.build_view(pages), pool,
                           jnp.zeros((1, pages), jnp.int32),
                           jnp.zeros((1,), jnp.int32))
    stage = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), stage)
    programs = {
        "serve_decode": kv.build_decode(vm, slots, pages).lower(*args),
        "serve_page_commit": kv.build_commit(pages).lower(
            pool, stage, jax.ShapeDtypeStruct((pages,), jnp.int32,
                                              sharding=one_chip)),
    }
    texts = {}
    for name, lowered in programs.items():
        text = texts[name] = lowered.compile().as_text()
        entry = text.split("entry_computation_layout={", 1)[1].split("\n")[0]
        entered = [(_dims(d), _dims(m)) for d, m in _ARRAY.findall(entry)
                   if _dims(d) == leaves[0].shape]
        assert len(entered) >= len(leaves), (name, entry[:400])
        for dims, layout in entered:
            assert layout[-1] == 0 and _row_major(dims, layout), (name,
                                                                  layout)
        copies = 0
        for d, m, space, opcode in _INSTR.findall(text):
            dims, layout = _dims(d), _dims(m)
            if int(np.prod(dims)) not in sizes:
                continue
            assert _row_major(dims, layout), (name, opcode, dims, layout)
            # a copy into another memory space (S(n)) is not a result
            copies += opcode in ("copy", "copy-done") and "S(" not in space
        assert copies <= len(leaves), (name, copies)
    return texts


def _assert_reads_pages_in_place(kv, text, slots, pages):
    """A compiled paged decode calls the paged-attention kernel and
    holds no value of a view's size: no layer's K or V rows of a whole
    page bucket (``slots * pages * page_size`` rows of ``G * D``), alone
    or stacked over the layers of a pool leaf."""
    assert "paged_attention" in _kernels(text)
    view = {slots * pages * kv.page_size * x.shape[-1] * n
            for x in jax.tree.leaves(kv.pool) for n in (1, x.shape[1])}
    held = {int(np.prod(_dims(d))) for d, _, _, _ in _INSTR.findall(text)}
    assert not view & held, sorted(view & held)


# ------------------------------------------------------- held experts
#: mellum2-12b-a2.5b at published widths: 16 held experts of 2304 x 896
EXPERTS, D_MODEL, D_EXPERT = 16, 2304, 896
#: one layer's expert stack, as a scan would slice it out of its periods
LAYER_STACKS = (f"[{EXPERTS},{D_MODEL},{D_EXPERT}]",
                f"[{EXPERTS},{D_EXPERT},{D_MODEL}]")


@pytest.mark.parametrize("rows", [8, 64])
def test_expert_kernel_compiles_for_v5e(rows, one_chip):
    """The held-expert SwiGLU at published widths, for a decode batch of
    8 and a prefill chunk of 64 tokens, reading one period of a stack of
    two."""
    from repro.kernels.sb_gemm import expert_swiglu_pallas

    def aval(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda *a: expert_swiglu_pallas(*a, interpret=False)).lower(
        aval((rows, D_MODEL)), aval((rows, EXPERTS), jnp.float32),
        aval((2, EXPERTS, D_MODEL, D_EXPERT)),
        aval((2, EXPERTS, D_MODEL, D_EXPERT)),
        aval((2, EXPERTS, D_EXPERT, D_MODEL)),
        aval((), jnp.int32)).compile().as_text()
    assert _kernels(text) == ["expert_swiglu"]


def _kernels(text: str) -> list[str]:
    """Names of the Mosaic kernels a compiled module calls."""
    return sorted({re.search(r"%([\w-]+?)(?:\.\d+)? = ", line).group(1)
                   for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line})


def _sparse_serving(one_chip, monkeypatch, n_periods, slots=8, pages=32):
    """(config, abstract params, PagedKV, abstract decode arguments,
    vmapped decode) for mellum2-12b-a2.5b at published widths, 16 held
    experts, ``n_periods`` periods of its four layer kinds."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.transformer import decode_step, init_params
    from repro.runtime.pages import PagedKV

    monkeypatch.setattr("repro.kernels.sb_gemm.interpret_mode",
                        lambda *arrays: False)
    cfg = get_config("mellum2-12b-a2.5b", pad_vocab=False, n_periods=n_periods,
                     dtype="bfloat16", param_dtype="bfloat16")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=EXPERTS))
    kv = PagedKV(cfg, n_pages=slots * (3584 // PAGE_ROWS) + 1,
                 page_size=PAGE_ROWS)

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(aval, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.key(0)))
    args = (params, jax.tree.map(aval, kv.pool),
            aval(jnp.zeros((slots, pages), jnp.int32)),
            aval(jnp.zeros((slots,), jnp.int32)),
            aval(jnp.zeros((slots, 1, 1), jnp.int32)))
    vm = jax.vmap(lambda p, c, t: decode_step(cfg, p, c, t, stats=True),
                  in_axes=(None, 0, 0))
    return cfg, params, kv, args, vm


def test_sparse_serve_decode_holds_only_the_expert_kernel(one_chip,
                                                          monkeypatch):
    """mellum2-12b-a2.5b's paged decode at 8 slots (one period of its
    four layer kinds, published widths, 16 held experts): the expert
    kernel and the paged-attention kernel are the only Mosaic kernels in
    ``serve_decode``, no value of a view's size is held, and its GQA pool
    (4 x 128 = 512 lanes a row) is page-major and never relaid out."""
    slots, pages = 8, 32
    _, _, kv, args, vm = _sparse_serving(one_chip, monkeypatch, 1, slots,
                                         pages)
    assert {x.shape[-1] for x in jax.tree.leaves(kv.pool)} == {512}
    texts = _assert_pool_never_relaid_out(kv, args, vm, slots, pages,
                                          one_chip)
    assert _kernels(texts["serve_decode"]) == ["expert_swiglu",
                                               "paged_attention"]
    _assert_reads_pages_in_place(kv, texts["serve_decode"], slots, pages)


def test_sparse_serve_programs_read_expert_weights_in_place(one_chip,
                                                           monkeypatch):
    """mellum2-12b-a2.5b's paged ``serve_decode`` at 8 slots and its
    ``serve_prefill`` of a 64-token chunk, two periods so that a period
    scan exists: the expert kernel takes each weight as its whole
    ``(periods, experts, ...)`` stack, and no op of either program yields
    one layer's expert stack (the slice a scan would copy out for it)."""
    from repro.models.transformer import init_cache, prefill

    slots, pages = 8, 32
    cfg, params, kv, args, vm = _sparse_serving(one_chip, monkeypatch, 2,
                                                slots, pages)

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    stage = jax.tree.map(aval, jax.eval_shape(
        lambda: init_cache(cfg, 1, pages * PAGE_ROWS)))
    texts = {
        "serve_decode": kv.build_decode(vm, slots, pages).lower(*args),
        "serve_prefill": jax.jit(
            lambda p, t, c: prefill(cfg, p, {"tokens": t}, c, stats=True)
        ).lower(params, aval(jnp.zeros((1, 64), jnp.int32)), stage),
    }
    stacks = sorted(2 * [f"bf16[2,{EXPERTS},{D_MODEL},{D_EXPERT}]"]
                    + [f"bf16[2,{EXPERTS},{D_EXPERT},{D_MODEL}]"])
    for name, lowered in texts.items():
        text = lowered.compile().as_text()
        assert "expert_swiglu" in _kernels(text), name
        for shape in LAYER_STACKS:
            assert shape not in text, (name, shape)
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and re.search(r"%expert_swiglu(\.\d+)? = ", line)]
        # one launch per sparse layer of the scanned period
        assert len(calls) == len(cfg.pattern), (name, len(calls))
        for call in calls:
            operands = re.search(r"operand_layout_constraints=\{(.*?\})\}",
                                 call).group(1)
            weights = sorted(re.findall(r"bf16\[2,[0-9,]+\]", operands))
            assert weights == stacks, (name, operands)
