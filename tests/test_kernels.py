"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

On the CPU every kernel is interpreted (``interpret_mode``); the TPU
compile of the same kernels is ``test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.contract import contract
from repro.core.planner import make_plan
from repro.core.table2 import CASES
from repro.kernels.ext_gemm import ext_gemm
from repro.kernels.ops import sb_contract
from repro.kernels.ref import ref_contract

jax.config.update("jax_enable_x64", False)


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


SHAPE_SWEEP = [
    {"m": 1, "n": 1, "p": 1, "k": 1},        # degenerate
    {"m": 5, "n": 7, "p": 3, "k": 4},        # small odd
    {"m": 16, "n": 8, "p": 2, "k": 32},      # small aligned
    {"m": 130, "n": 65, "p": 9, "k": 200},   # >tile, ragged
    {"m": 256, "n": 128, "p": 4, "k": 128},  # tile multiples
]


@pytest.mark.parametrize("dims", SHAPE_SWEEP, ids=lambda d: "x".join(map(str, d.values())))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("label", ["1.1", "1.3", "2.4", "4.1", "5.3"])
def test_sb_gemm_vs_oracle(dims, dtype, label):
    rng = np.random.default_rng(0)
    rm = CASES[label].row_major()
    a_modes, rest = rm.split(",")
    b_modes, _ = rest.split("->")
    A = _rand(rng, [dims[m] for m in a_modes], dtype)
    B = _rand(rng, [dims[m] for m in b_modes], dtype)
    ref = ref_contract(rm, A, B, out_dtype=jnp.float32)
    got = contract(rm, A, B, strategy="batched", backend="pallas",
                   out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **_tol(dtype))


@pytest.mark.parametrize("label", sorted(CASES))
def test_all_36_cases_pallas(label):
    """Every Table II case evaluates correctly through the Pallas backend."""
    rng = np.random.default_rng(1)
    dims = {"m": 6, "n": 10, "p": 3, "k": 5}
    rm = CASES[label].row_major()
    a_modes, rest = rm.split(",")
    b_modes, _ = rest.split("->")
    A = _rand(rng, [dims[m] for m in a_modes], jnp.float32)
    B = _rand(rng, [dims[m] for m in b_modes], jnp.float32)
    ref = ref_contract(rm, A, B)
    got = contract(rm, A, B, strategy="batched", backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("label", sorted(l for l, c in CASES.items() if c.exceptional))
def test_ext_gemm_all_exceptional_cases(label, dtype):
    rng = np.random.default_rng(2)
    dims = {"m": 34, "n": 18, "p": 5, "k": 40}
    rm = CASES[label].row_major()
    a_modes, rest = rm.split(",")
    b_modes, _ = rest.split("->")
    A = _rand(rng, [dims[m] for m in a_modes], dtype)
    B = _rand(rng, [dims[m] for m in b_modes], dtype)
    ref = ref_contract(rm, A, B, out_dtype=jnp.float32)
    got = ext_gemm(rm, A, B, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **_tol(dtype))


def test_ext_gemm_rejects_regular_cases():
    rm = CASES["1.1"].row_major()
    A = jnp.zeros((4, 6))
    B = jnp.zeros((3, 10, 4))
    with pytest.raises(ValueError):
        ext_gemm(rm, A, B)


def test_broadcast_batching():
    """loa=0 broadcast: A reused across the batch (paper Listing 1)."""
    rng = np.random.default_rng(3)
    A = _rand(rng, (16, 8), jnp.float32)          # km
    B = _rand(rng, (4, 16, 12), jnp.float32)      # pkn... modes: p k n
    ref = jnp.einsum("km,pkn->pnm", A, B)
    got = sb_contract("km", "pkn", "pnm", A, B,
                      roles={"k": "k", "m": "v", "n": "u", "p": "b"})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_shared_batch_mode():
    """Both operands strided over the same batch mode (attention-style)."""
    rng = np.random.default_rng(4)
    A = _rand(rng, (6, 9, 17), jnp.float32)   # b q d -> modes "bqd"
    B = _rand(rng, (6, 13, 17), jnp.float32)  # b t d
    ref = jnp.einsum("bqd,btd->bqt", A, B)
    got = sb_contract("bqd", "btd", "bqt", A, B,
                      roles={"b": "b", "q": "u", "t": "v", "d": "k"})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_under_jit_and_grad():
    rng = np.random.default_rng(5)
    rm = CASES["1.3"].row_major()  # km,pkn->pnm
    A = _rand(rng, (12, 20), jnp.float32)
    B = _rand(rng, (3, 12, 8), jnp.float32)

    @jax.jit
    def loss(a, b):
        return jnp.sum(contract(rm, a, b, strategy="batched", backend="pallas") ** 2)

    # pallas kernels are forward-only primitives here; grads flow via the
    # XLA path in models.  This test pins the jit path only.
    val = loss(A, B)
    ref = jnp.sum(jnp.einsum(rm, A, B) ** 2)
    np.testing.assert_allclose(float(val), float(ref), rtol=1e-4)


def test_native_kernel_grad_matches_einsum():
    """The native kernel defines a custom VJP whose backward passes are
    themselves native contractions (the einsum-transpose specs are always
    legal because free modes must reach the output)."""
    rng = np.random.default_rng(6)
    specs = [
        ("pk,mkn->nmp", (5, 7), (4, 7, 3)),   # exceptional layout
        ("mk,kn->mn", (6, 4), (4, 5)),        # plain GEMM
        ("k,k->", (9,), (9,)),                # scalar output (direct route)
        ("bmk,bkn->bnm", (2, 3, 4), (2, 4, 5)),
        ("mq,qn->qnm", (3, 4), (4, 5)),       # batch-minor output
    ]
    for spec, sa, sb in specs:
        A = _rand(rng, sa, jnp.float32)
        B = _rand(rng, sb, jnp.float32)
        ga, gb = jax.grad(
            lambda a, b: jnp.sum(contract(spec, a, b, strategy="native") ** 2),
            (0, 1))(A, B)
        ra, rb = jax.grad(
            lambda a, b: jnp.sum(jnp.einsum(spec, a, b) ** 2), (0, 1))(A, B)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                                   rtol=1e-4, atol=1e-4, err_msg=spec)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                                   rtol=1e-4, atol=1e-4, err_msg=spec)
    # jit composes, and second order works (the backward is differentiable)
    A = _rand(rng, (5, 7), jnp.float32)
    B = _rand(rng, (4, 7, 3), jnp.float32)
    f = lambda a: jnp.sum(contract("pk,mkn->nmp", a, B, strategy="native"))
    r = lambda a: jnp.sum(jnp.einsum("pk,mkn->nmp", a, B))
    np.testing.assert_allclose(np.asarray(jax.jit(jax.grad(f))(A)),
                               np.asarray(jax.grad(r)(A)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.grad(lambda a: jnp.sum(jax.grad(f)(a) ** 2))(A)),
        np.asarray(jax.grad(lambda a: jnp.sum(jax.grad(r)(a) ** 2))(A)),
        rtol=1e-4, atol=1e-4)


def test_unknown_strategy_and_backend_rejected():
    A = jnp.ones((2, 2))
    with pytest.raises(ValueError, match="unknown strategy"):
        contract("mk,kn->mn", A, A, strategy="nativ")
    with pytest.raises(ValueError, match="unknown backend"):
        contract("mk,kn->mn", A, A, backend="cuda")
