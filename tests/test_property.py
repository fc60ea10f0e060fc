"""Property-based tests (hypothesis) for the system's invariants.

Skipped wholesale when ``hypothesis`` is not installed (it lives in
requirements-dev.txt, not the runtime requirements).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.contract import contract
from repro.core.notation import CaseKind, parse_spec
from repro.core.planner import make_plan
from repro.distributed.compress import Int8Compressor

MODES = "mnpqk"


@st.composite
def contraction_specs(draw):
    """Random single-k pairwise contractions of order ≤ 3 each side."""
    k = "k"
    n_a_free = draw(st.integers(0, 2))
    n_b_free = draw(st.integers(max(0, 1 - n_a_free), 2))
    free = list("mnpq")[: n_a_free + n_b_free]
    a_free, b_free = free[:n_a_free], free[n_a_free:]
    a_modes = draw(st.permutations(a_free + [k]))
    b_modes = draw(st.permutations(b_free + [k]))
    c_modes = draw(st.permutations(free))
    dims = {m: draw(st.integers(1, 7)) for m in free + [k]}
    return "".join(a_modes), "".join(b_modes), "".join(c_modes), dims


@given(contraction_specs())
@settings(max_examples=60, deadline=None)
def test_contract_matches_einsum_for_any_layout(spec):
    a_m, b_m, c_m, dims = spec
    s = f"{a_m},{b_m}->{c_m}"
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal([dims[m] for m in a_m]), jnp.float32)
    B = jnp.asarray(rng.standard_normal([dims[m] for m in b_m]), jnp.float32)
    ref = jnp.einsum(s, A, B)
    for strategy in ("auto", "batched", "direct", "conventional"):
        got = contract(s, A, B, strategy=strategy)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=f"{s} {strategy}")


@given(contraction_specs())
@settings(max_examples=60, deadline=None)
def test_planner_invariants(spec):
    a_m, b_m, c_m, dims = spec
    s = f"{a_m},{b_m}->{c_m}"
    plan = make_plan(s, dims)
    fs = plan.fspec
    # every output mode is accounted for exactly once
    covered = set(plan.batch_modes)
    if plan.gemm_modes:
        u, v, _ = plan.gemm_modes
        covered |= {u, v} - {""}
    else:
        covered |= set(fs.c_modes)
    assert covered >= set(fs.c_modes), plan.describe()
    # no-last-mode rule: an sb batch mode never sits minor-most on an
    # order-≥3 tensor (exceptional plans are exempt — that's their point)
    if plan.kind in (CaseKind.SB_GEMM, CaseKind.NESTED) and plan.sb_batch:
        for modes in (fs.a_modes, fs.b_modes, fs.c_modes):
            if len(modes) >= 3:
                assert modes[-1] != plan.sb_batch, plan.describe()


@given(contraction_specs())
@settings(max_examples=30, deadline=None)
def test_pallas_backend_matches_einsum(spec):
    a_m, b_m, c_m, dims = spec
    s = f"{a_m},{b_m}->{c_m}"
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal([dims[m] for m in a_m]), jnp.float32)
    B = jnp.asarray(rng.standard_normal([dims[m] for m in b_m]), jnp.float32)
    ref = jnp.einsum(s, A, B)
    got = contract(s, A, B, strategy="batched", backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4, err_msg=s)


@given(
    st.integers(1, 500),  # length
    st.integers(8, 128),  # block
    st.floats(0.01, 100.0),  # scale of the gradient values
)
@settings(max_examples=40, deadline=None)
def test_int8_quantization_error_bounded_by_block_scale(n, block, scale):
    comp = Int8Compressor(block=block)
    rng = np.random.default_rng(n)
    g = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    q = comp._quant_dequant(g)
    # per-block max-abs / 127 bounds the elementwise error (±0.5 ulp)
    err = np.asarray(jnp.abs(q - g))
    bound = float(jnp.max(jnp.abs(g))) / 127.0 * 0.5 + 1e-6
    assert err.max() <= bound * 1.0001, (err.max(), bound)


# --------------------------------------------- native address computation
# The native-layout kernel never touches data to handle a layout — it is
# all address arithmetic in repro.kernels.addressing.  These properties
# pin that arithmetic in isolation: a wrong stride or tile origin here is
# exactly the class of bug the bit-identical differential tier would
# surface end-to-end, caught at the helper instead.

@given(
    st.lists(st.integers(1, 9), min_size=1, max_size=4),
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_flat_offset_roundtrip(shape, seed):
    import math

    from repro.kernels.addressing import (
        flat_offset, row_major_strides, unflatten_offset,
    )

    rng = np.random.default_rng(seed)
    strides = row_major_strides(shape)
    coords = tuple(int(rng.integers(0, d)) for d in shape)
    off = flat_offset(coords, strides)
    assert 0 <= off < math.prod(shape)
    assert unflatten_offset(off, shape) == coords
    # and the other direction: every flat offset names a unique coord
    off2 = int(rng.integers(0, math.prod(shape)))
    assert flat_offset(unflatten_offset(off2, shape), strides) == off2


@given(st.integers(1, 600), st.integers(1, 256))
@settings(max_examples=80, deadline=None)
def test_tile_clamp_and_coverage(dim, tile):
    from repro.kernels.addressing import (
        effective_tile, num_blocks, padded_extent, tile_origins,
    )

    padded, eff = padded_extent(dim, tile), effective_tile(dim, tile)
    assert dim <= padded < dim + tile       # pads, but never a full tile
    assert 1 <= eff <= tile and eff <= dim  # clamped to the mode
    assert padded % eff == 0                # blocks partition exactly
    origins = tile_origins(dim, tile)
    assert len(origins) == num_blocks(dim, tile) == padded // eff
    # origins tile [0, padded) with no gap and no overlap
    assert origins[0] == 0 and origins[-1] + eff == padded
    assert all(b - a == eff for a, b in zip(origins, origins[1:]))


@st.composite
def addressing_cases(draw):
    """Small native-kernel cases: ≤4 grid modes, dims ≤4, tiles ≤4 —
    exhaustively checkable grids."""
    n_b = draw(st.integers(0, 1))
    n_af = draw(st.integers(0, 1))
    n_bf = draw(st.integers(0, 1))
    k, b = ["k"], ["b"][:n_b]
    af, bf = ["m"][:n_af], ["n"][:n_bf]
    a_modes = "".join(draw(st.permutations(af + k + b)))
    b_modes = "".join(draw(st.permutations(bf + k + b)))
    c_modes = "".join(draw(st.permutations(af + bf + b)))
    dims = {m: draw(st.integers(1, 4)) for m in "k" + c_modes}
    grid_modes = c_modes + "k"
    tiles = {m: draw(st.integers(1, 4)) for m in grid_modes}
    return a_modes, b_modes, c_modes, dims, tiles, grid_modes


@given(addressing_cases())
@settings(max_examples=50, deadline=None)
def test_tile_loads_in_bounds_and_exhaustive(case):
    """Over the full grid, each operand's block-scatter loads (a) never
    address outside its padded extents — there is no out-of-bounds read
    to predicate away — and (b) touch every element of the padded
    operand exactly once per block-combination of the modes the operand
    does *not* carry."""
    import collections
    import itertools
    import math

    from repro.kernels.addressing import (
        num_blocks, padded_extent, tile_element_offsets,
    )

    a_modes, b_modes, c_modes, dims, tiles, grid_modes = case
    blocks = {m: num_blocks(dims[m], tiles[m]) for m in grid_modes}
    grid = list(itertools.product(*(range(blocks[m]) for m in grid_modes)))
    for operand in (a_modes, b_modes, c_modes):
        if not operand:
            continue
        padded = [padded_extent(dims[m], tiles[m]) for m in operand]
        n_elems = math.prod(padded)
        counts = collections.Counter()
        for coords in grid:
            offs = tile_element_offsets(operand, dims, tiles, coords,
                                        grid_modes)
            assert all(0 <= o < n_elems for o in offs), (operand, coords)
            counts.update(offs)
        repeats = math.prod(
            blocks[m] for m in grid_modes if m not in operand
        )
        assert set(counts) == set(range(n_elems)), operand
        assert set(counts.values()) == {repeats}, (operand, repeats)


@given(addressing_cases())
@settings(max_examples=50, deadline=None)
def test_native_mode_tiles_invariants(case):
    """The role→mode assignment covers every grid mode exactly once, puts
    the lane (v) tile on C's minor-most mode and the k tile on the
    largest contracted mode — each raised to the TPU block rule, which
    every operand's last two axes then obey — for any mode ordering."""
    from repro.kernels.addressing import LANE, SUBLANE, native_mode_tiles

    a_modes, b_modes, c_modes, dims, _, grid_modes = case
    role = {"u": 64, "v": 128, "k": 32, "b": 1}
    mt = native_mode_tiles(a_modes, b_modes, c_modes, dims, role)
    assert set(mt) == set(grid_modes)
    assert all(isinstance(t, int) and t >= 1 for t in mt.values())

    def raised(t):  # the role tile, or it rounded up to the block rule
        return {t, -(-t // SUBLANE) * SUBLANE, -(-t // LANE) * LANE}

    if c_modes:
        assert mt[c_modes[-1]] in raised(role["v"])
    contracted = [m for m in a_modes if m in b_modes and m not in c_modes]
    if contracted:
        k_prim = max(contracted, key=lambda m: dims[m])
        assert mt[k_prim] in raised(role["k"])
    for modes in (a_modes, b_modes, c_modes):
        if modes:
            t = mt[modes[-1]]
            assert t % LANE == 0 or t >= dims[modes[-1]], (modes, t)
        if len(modes) >= 2:
            t = mt[modes[-2]]
            assert t % SUBLANE == 0 or t >= dims[modes[-2]], (modes, t)


@given(st.lists(st.integers(1, 6), min_size=1, max_size=3), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_checkpoint_roundtrip_any_tree(shape, seed):
    import tempfile

    from repro.training.checkpoint import restore_checkpoint, save_checkpoint

    rng = np.random.default_rng(seed)
    tree = {
        "a": jnp.asarray(rng.standard_normal(shape), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 10, shape), jnp.int32)},
    }
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, tree)
        restored, _, _ = restore_checkpoint(d, None, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
