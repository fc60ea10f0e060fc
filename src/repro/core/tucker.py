"""Tucker decomposition via HOOI — the paper's application study (§II-C, Fig 9).

Algorithm 1 of the paper, for a third-order tensor ``T ∈ R^{m×n×p}``::

    T_mnp ≈ G_ijk A_mi B_nj C_pk

The multi-operand expressions (Y-updates, core computation and
reconstruction) go through :func:`repro.core.einsum.xeinsum`, which plans
the pairwise order and lowers each step through the engine — with
``strategy="auto"`` (flatten/strided-batch, no copies) for our method, or
``strategy="conventional"`` for the matricization baseline the paper
benchmarks against (TensorToolbox / BTAS / Cyclops all transpose+copy).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.contract import contract
from repro.core.einsum import xeinsum

__all__ = ["TuckerResult", "hooi", "tucker_reconstruct", "init_hosvd"]


@dataclasses.dataclass
class TuckerResult:
    core: jax.Array          # G (i, j, k)
    factors: tuple           # A (m,i), B (n,j), C (p,k)
    rel_error: jax.Array     # ||T - reconstruction|| / ||T||


def _leading_left_sv(mat, r: int):
    """r leading left singular vectors.  For tall unfoldings we take the
    eigendecomposition of the (small) Gram matrix — same subspace, much
    cheaper than full SVD, and jit-friendly."""
    g = mat @ mat.T
    _, vecs = jnp.linalg.eigh(g)          # ascending eigenvalues
    return vecs[:, ::-1][:, :r]


def init_hosvd(T, ranks, strategy: str = "auto", backend: str = "xla"):
    """HOSVD init: factor r = leading left SVs of each unfolding (Alg 1 l.2)."""
    m, n, p = T.shape
    i, j, k = ranks
    A = _leading_left_sv(T.reshape(m, n * p), i)
    # mode-2 / mode-3 unfoldings need the mode first; build gram matrices via
    # contractions instead of transposing T (transpose-free init).
    g2 = contract("mnp,mqp->nq", T, T, strategy="direct")
    _, v2 = jnp.linalg.eigh(g2)
    B = v2[:, ::-1][:, :j]
    g3 = contract("mnp,mnq->pq", T, T, strategy="direct")
    _, v3 = jnp.linalg.eigh(g3)
    C = v3[:, ::-1][:, :k]
    return A, B, C


def hooi(
    T,
    ranks: tuple[int, int, int],
    *,
    n_iter: int = 10,
    strategy: Literal["auto", "batched", "conventional", "direct"] = "auto",
    backend: Literal["xla", "pallas"] = "xla",
    jit: bool = True,
) -> TuckerResult:
    """Higher-order orthogonal iteration (paper Algorithm 1).

    The body's recurring contraction working set is compiled **once** as
    three :mod:`repro.core.program` contraction programs (the split
    follows the data dependencies — each factor update consumes the
    eigendecomposition of the previous one) and executed per iteration
    from the program cache; with ``jit=False`` every iteration still runs
    the same jitted executables rather than re-planning step by step.
    """
    i, j, k = ranks
    xctr = functools.partial(xeinsum, strategy=strategy, backend=backend)
    from repro.core.program import build_program, compile_program

    def _factor_from_gram(g, r):
        _, vecs = jnp.linalg.eigh(g)
        return vecs[:, ::-1][:, :r]

    A, B, C = init_hosvd(T, ranks, strategy, backend)
    kw = dict(strategy=strategy, backend=backend)
    # Y_mjk = T_mnp B_nj C_pk (Alg 1 l.4), its gram Y_(1)·Y_(1)ᵀ (leading
    # left SVs = top eigvecs — no unfolding transpose is ever
    # materialized), and the dominant T·C stage staged explicitly so the
    # Y_(1) and Y_(2) updates share it: one program, two outputs.
    p1 = compile_program(build_program(
        {"T": T, "C": C, "B": B},
        [("t1", "mnp,pk->mnk", ("T", "C")),
         ("y1", "mnk,nj->mjk", ("t1", "B")),
         ("g1", "mjk,qjk->mq", ("y1", "y1"), {"strategy": "direct"})],
        outputs=("g1", "t1")), **kw)
    # Y_ink = T_mnp A_mi C_pk (l.6), via the shared t1
    t1_aval = jax.ShapeDtypeStruct((T.shape[0], T.shape[1], k), T.dtype)
    p2 = compile_program(build_program(
        {"t1": t1_aval, "A": A},
        [("y2", "mnk,mi->ink", ("t1", "A")),
         ("g2", "ink,iqk->nq", ("y2", "y2"), {"strategy": "direct"})]), **kw)
    # Y_ijp = T_mnp A_mi B_nj (l.8) — no shared stage; path-planned
    p3 = compile_program(build_program(
        {"T": T, "A": A, "B": B},
        [("y3", "mnp,mi,nj->ijp", ("T", "A", "B")),
         ("g3", "ijp,ijq->pq", ("y3", "y3"), {"strategy": "direct"})]), **kw)

    # T is an argument, not a closure: a jitted closure would embed the
    # whole tensor in the executable as a constant
    def body(T, fac):
        A, B, C = fac
        g1, t1 = p1(T, C, B)
        A = _factor_from_gram(g1, i)
        B = _factor_from_gram(p2(t1, A), j)
        C = _factor_from_gram(p3(T, A, B), k)
        return A, B, C

    step = jax.jit(body) if jit else body
    fac = (A, B, C)
    for _ in range(n_iter):
        fac = step(T, fac)
    A, B, C = fac

    # G_ijk = T ×1 Aᵀ ×2 Bᵀ ×3 Cᵀ — one four-operand expression
    G = xctr("mnp,mi,nj,pk->ijk", T, A, B, C)

    recon = tucker_reconstruct(G, (A, B, C), strategy=strategy, backend=backend)
    rel = jnp.linalg.norm(T - recon) / jnp.linalg.norm(T)
    return TuckerResult(core=G, factors=(A, B, C), rel_error=rel)


def tucker_reconstruct(G, factors, *, strategy="auto", backend="xla"):
    """``T ≈ G ×1 A ×2 B ×3 C`` as one path-planned n-ary contraction."""
    A, B, C = factors
    return xeinsum(
        "ijk,mi,nj,pk->mnp", G, A, B, C, strategy=strategy, backend=backend
    )
