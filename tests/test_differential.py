"""Differential test harness: a seeded random-spec fuzzer.

Sharded lowering multiplies the ways a contraction can be silently wrong
(a dropped psum, a mis-ordered gather, a batch mode sliced on the wrong
axis all *run fine* and return numbers), so correctness is pinned
differentially: 200 seeded specs — 120 pairwise + 80 n-ary, operand
orders 2–5, small dims — are cross-checked against the ``jnp.einsum``
oracle across every ``contract()``/``xeinsum()`` strategy×backend:

* pairwise: ``auto`` / ``batched`` / ``direct`` / ``conventional`` on
  XLA for every spec; ``flatten`` where the plan admits it (and asserted
  to *raise* where it does not); the Pallas kernels (interpret mode on
  CPU — expensive, so sampled every 5th spec);
* n-ary: every path optimizer (``naive`` / ``greedy`` / ``auto``), with
  implicit-output and sum-only-mode specs in the mix;
* compiled programs: a slice of the same seeded specs also executes
  through :func:`repro.core.program.compile_program` and must match the
  ``jnp.einsum`` oracle — and be **bit-identical** to ``xeinsum`` (which
  routes through the same cached program);
* sharded: when ≥8 devices are visible (``REPRO_HOST_DEVICES=8``, see
  ``conftest.py``), the same specs run through ``xeinsum(...,
  mesh=...)`` with seeded mode shardings and must match their
  single-device result — the differential bar for the shard-aware path;
* layout fuzz: 100 seeded specs from :mod:`layoutfuzz` — permuted /
  exceptional / degenerate mode orders, size-1 extents, operands
  arriving through strided / reversed / transposed / broadcast storage —
  must be **bit-identical** (``np.array_equal``, not allclose; the
  operands are integer-valued f32 so every reduction order is exact)
  to ``jnp.einsum`` under every strategy, including the native-layout
  Pallas kernel, which may never permute or copy to get there.

No hypothesis dependency: plain ``numpy.random.default_rng`` with fixed
seeds, so every failure is a deterministic repro.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.contract import contract
from repro.core.einsum import xeinsum
from repro.core.notation import CaseKind, ContractionSpec
from repro.core.planner import make_plan
from repro.core.program import compile_program
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.slow  # the fuzzer is the multi-minute tier-1 tail

SEED = 20260801
N_PAIRWISE = 120
N_NARY = 80
N_LAYOUT = 100  # layout-fuzz tier (see layoutfuzz.py)
CHUNK = 10  # specs per pytest case: granular repro without 200 items
PALLAS_EVERY = 5
PROGRAM_EVERY = 2  # compiled-program slice of the seeded specs

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 simulated devices (REPRO_HOST_DEVICES=8)",
)


# ------------------------------------------------------------ generators
def gen_pairwise(rng) -> tuple[ContractionSpec, dict]:
    """One random valid pairwise spec with operand/output orders 2–5."""
    letters = "abcdefghij"
    while True:
        n_k = int(rng.integers(1, 3))    # contracted modes
        n_b = int(rng.integers(0, 3))    # shared batch modes
        n_af = int(rng.integers(1, 3))   # A's free modes
        n_bf = int(rng.integers(1, 3))   # B's free modes
        ra, rb = n_af + n_k + n_b, n_bf + n_k + n_b
        rc = n_af + n_bf + n_b
        if not (2 <= ra <= 5 and 2 <= rb <= 5 and 2 <= rc <= 5):
            continue
        ms = list(letters[: n_k + n_b + n_af + n_bf])
        k = ms[:n_k]
        b = ms[n_k:n_k + n_b]
        af = ms[n_k + n_b:n_k + n_b + n_af]
        bf = ms[n_k + n_b + n_af:]
        a_modes = "".join(rng.permutation(af + k + b))
        b_modes = "".join(rng.permutation(bf + k + b))
        c_modes = "".join(rng.permutation(af + bf + b))
        cs = ContractionSpec(a_modes, b_modes, c_modes)
        try:
            cs.validate()
        except ValueError:
            continue
        dims = {m: int(rng.integers(2, 6)) for m in ms}
        return cs, dims


def gen_nary(rng) -> tuple[str, dict]:
    """One random n-ary spec (3–4 operands, orders 1–4, dims 2–4).

    May include sum-only modes, outer products, contracted batch modes,
    and (one in five) an implicit output.
    """
    pool = "abcdefg"[: int(rng.integers(4, 8))]
    dims = {m: int(rng.integers(2, 5)) for m in pool}
    n_ops = int(rng.integers(3, 5))
    inputs = []
    for _ in range(n_ops):
        rank = int(rng.integers(1, 5))
        modes = rng.choice(list(pool), size=min(rank, len(pool)), replace=False)
        inputs.append("".join(modes))
    counts = collections.Counter(m for t in inputs for m in t)
    used = [m for m in pool if counts[m]]
    if rng.integers(0, 5) == 0:
        spec = ",".join(inputs)  # implicit output
    else:
        n_out = int(rng.integers(0, min(4, len(used)) + 1))
        out = "".join(rng.choice(used, size=n_out, replace=False))
        spec = ",".join(inputs) + "->" + out
    return spec, dims


def operands_for(mode_strings, dims, rng):
    return [
        jnp.asarray(
            rng.standard_normal([dims[m] for m in modes]), jnp.float32
        )
        for modes in mode_strings
    ]


def _chunks(n):
    return [
        pytest.param(c, id=f"specs{c * CHUNK}-{min((c + 1) * CHUNK, n) - 1}")
        for c in range((n + CHUNK - 1) // CHUNK)
    ]


# ----------------------------------------------------- pairwise vs oracle
@pytest.mark.parametrize("chunk", _chunks(N_PAIRWISE))
def test_pairwise_strategies_match_einsum(chunk):
    for i in range(chunk * CHUNK, min((chunk + 1) * CHUNK, N_PAIRWISE)):
        rng = np.random.default_rng([SEED, i])
        cs, dims = gen_pairwise(rng)
        A, B = operands_for((cs.a_modes, cs.b_modes), dims, rng)
        spec = cs.spec_str()
        ref = np.asarray(jnp.einsum(spec, A, B))

        for strategy in ("auto", "batched", "direct", "conventional"):
            got = contract(spec, A, B, strategy=strategy)
            np.testing.assert_allclose(
                np.asarray(got), ref, atol=1e-4, rtol=1e-4,
                err_msg=f"spec #{i} {spec} dims={dims} strategy={strategy}",
            )
        # flatten: exact where legal, a clean ValueError where not
        if make_plan(cs, dims).kind == CaseKind.FLAT_GEMM:
            got = contract(spec, A, B, strategy="flatten")
            np.testing.assert_allclose(
                np.asarray(got), ref, atol=1e-4, rtol=1e-4,
                err_msg=f"spec #{i} {spec} dims={dims} strategy=flatten",
            )
        else:
            with pytest.raises(ValueError):
                contract(spec, A, B, strategy="flatten")
        if i % PALLAS_EVERY == 0:  # interpret mode is slow — sample
            got = contract(spec, A, B, strategy="auto", backend="pallas")
            np.testing.assert_allclose(
                np.asarray(got), ref, atol=1e-4, rtol=1e-4,
                err_msg=f"spec #{i} {spec} dims={dims} backend=pallas",
            )


# -------------------------------------------------------- n-ary vs oracle
@pytest.mark.parametrize("chunk", _chunks(N_NARY))
def test_nary_optimizers_match_einsum(chunk):
    for i in range(chunk * CHUNK, min((chunk + 1) * CHUNK, N_NARY)):
        rng = np.random.default_rng([SEED, 10_000 + i])
        spec, dims = gen_nary(rng)
        inputs = spec.split("->")[0].split(",")
        ops = operands_for(inputs, dims, rng)
        ref = np.asarray(jnp.einsum(spec, *ops))
        for optimize in ("naive", "greedy", "auto"):
            got = xeinsum(spec, *ops, optimize=optimize)
            np.testing.assert_allclose(
                np.asarray(got), ref, atol=1e-4, rtol=1e-4,
                err_msg=f"spec #{i} {spec} dims={dims} optimize={optimize}",
            )
        if i % (2 * PALLAS_EVERY) == 0:
            got = xeinsum(spec, *ops, strategy="pallas")
            np.testing.assert_allclose(
                np.asarray(got), ref, atol=1e-4, rtol=1e-4,
                err_msg=f"spec #{i} {spec} dims={dims} strategy=pallas",
            )


# ------------------------------------------ compiled programs vs oracle
@pytest.mark.parametrize("chunk", _chunks(N_NARY // PROGRAM_EVERY))
def test_compiled_programs_match_oracle_and_eager(chunk):
    """Every other seeded n-ary spec (plus its pairwise sibling) through
    the compiled-program path: allclose to ``jnp.einsum``, bit-identical
    to ``xeinsum`` (same cached program, by construction)."""
    lo = chunk * CHUNK * PROGRAM_EVERY
    hi = min((chunk + 1) * CHUNK * PROGRAM_EVERY, N_NARY)
    for i in range(lo, hi, PROGRAM_EVERY):
        rng = np.random.default_rng([SEED, 10_000 + i])
        spec, dims = gen_nary(rng)
        inputs = spec.split("->")[0].split(",")
        ops = operands_for(inputs, dims, rng)
        ref = np.asarray(jnp.einsum(spec, *ops))
        prog = compile_program(spec, *ops)
        got = np.asarray(prog(*ops))
        np.testing.assert_allclose(
            got, ref, atol=1e-4, rtol=1e-4,
            err_msg=f"spec #{i} {spec} dims={dims} via compile_program",
        )
        assert np.array_equal(got, np.asarray(xeinsum(spec, *ops))), (
            f"spec #{i} {spec}: program and xeinsum results diverge"
        )
        # and a pairwise sibling from the same seed space
        rng2 = np.random.default_rng([SEED, i])
        cs, pdims = gen_pairwise(rng2)
        A, B = operands_for((cs.a_modes, cs.b_modes), pdims, rng2)
        pref = np.asarray(jnp.einsum(cs.spec_str(), A, B))
        pgot = np.asarray(compile_program(cs.spec_str(), A, B)(A, B))
        np.testing.assert_allclose(
            pgot, pref, atol=1e-4, rtol=1e-4,
            err_msg=f"pairwise #{i} {cs.spec_str()} via compile_program",
        )


# --------------------------------------- layout fuzz: bit-identical tier
@pytest.mark.parametrize("chunk", _chunks(N_LAYOUT))
def test_layout_fuzz_bit_identical(chunk):
    """Every strategy must be *bit-identical* to ``jnp.einsum`` on specs
    and storage layouts drawn from :mod:`layoutfuzz` — the operands are
    integer-valued f32, so there is no tolerance to hide a mis-addressed
    tile behind.  ``native`` (the transpose-free Pallas kernel) runs on
    every spec; the pallas ``auto`` route is sampled (interpret mode is
    slow)."""
    from layoutfuzz import gen_layout_case

    for i in range(chunk * CHUNK, min((chunk + 1) * CHUNK, N_LAYOUT)):
        cs, dims, A_np, B_np, treatments = gen_layout_case(i)
        spec = cs.spec_str()
        A, B = jnp.asarray(A_np), jnp.asarray(B_np)
        ref = np.asarray(jnp.einsum(spec, A, B))
        msg = f"spec #{i} {spec} dims={dims} layouts={treatments}"

        for strategy in ("auto", "batched", "direct", "conventional",
                         "native"):
            got = np.asarray(contract(spec, A, B, strategy=strategy))
            assert got.shape == ref.shape, f"{msg} strategy={strategy}"
            assert np.array_equal(got, ref), (
                f"{msg} strategy={strategy}: bits diverge "
                f"(max |Δ|={np.abs(got - ref).max()})"
            )
        if i % PALLAS_EVERY == 0:
            got = np.asarray(
                contract(spec, A, B, strategy="auto", backend="pallas")
            )
            assert np.array_equal(got, ref), (
                f"{msg} backend=pallas: bits diverge"
            )


# ------------------------------------------- sharded vs single-device
def _seeded_shardings(mode_strings, output, dims, mesh):
    """Shard up to one even-dim surviving mode per mesh axis (seeded by
    the spec itself, so the coverage is deterministic)."""
    counts = collections.Counter(m for t in mode_strings for m in t)
    surviving = [
        m for m in dict.fromkeys("".join(mode_strings))
        if (counts[m] > 1 or m in output)
    ]
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    shard = {}
    for ax, size in axis_sizes.items():
        for m in surviving:
            if m not in shard and dims[m] % size == 0:
                shard[m] = ax
                break
    from jax.sharding import PartitionSpec as P

    return shard, tuple(P(*[shard.get(m) for m in t]) for t in mode_strings)


@multidevice
@pytest.mark.parametrize("chunk", _chunks(N_PAIRWISE // 2))
def test_sharded_pairwise_matches_single_device(chunk):
    mesh = make_mesh((2, 2), ("x", "y"))
    for i in range(chunk * CHUNK, min((chunk + 1) * CHUNK, N_PAIRWISE // 2)):
        rng = np.random.default_rng([SEED, i])  # same specs as single-device
        cs, dims = gen_pairwise(rng)
        A, B = operands_for((cs.a_modes, cs.b_modes), dims, rng)
        spec = cs.spec_str()
        shard, in_specs = _seeded_shardings(
            (cs.a_modes, cs.b_modes), cs.c_modes, dims, mesh
        )
        single = np.asarray(xeinsum(spec, A, B))
        sharded = xeinsum(spec, A, B, mesh=mesh, in_specs=in_specs)
        np.testing.assert_allclose(
            np.asarray(sharded), single, atol=1e-4, rtol=1e-4,
            err_msg=f"spec #{i} {spec} dims={dims} shard={shard}",
        )


@multidevice
@pytest.mark.parametrize("chunk", _chunks(N_NARY // 2))
def test_sharded_nary_matches_single_device(chunk):
    mesh = make_mesh((2, 2), ("x", "y"))
    for i in range(chunk * CHUNK, min((chunk + 1) * CHUNK, N_NARY // 2)):
        rng = np.random.default_rng([SEED, 10_000 + i])
        spec, dims = gen_nary(rng)
        lhs = spec.split("->")[0].split(",")
        from repro.core.einsum import parse_nary

        _, output = parse_nary(spec)
        ops = operands_for(lhs, dims, rng)
        shard, in_specs = _seeded_shardings(lhs, output, dims, mesh)
        single = np.asarray(xeinsum(spec, *ops))
        sharded = xeinsum(spec, *ops, mesh=mesh, in_specs=in_specs)
        np.testing.assert_allclose(
            np.asarray(sharded), single, atol=1e-4, rtol=1e-4,
            err_msg=f"spec #{i} {spec} dims={dims} shard={shard}",
        )
