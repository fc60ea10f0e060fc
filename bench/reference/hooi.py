"""Plain HOOI (Tucker decomposition of a third-order tensor), from the
paper's Algorithm 1.  Imports nothing of the program.

HOSVD init: each factor is the leading eigenvectors of its mode's Gram
matrix.  Each sweep replaces A by the leading eigenvectors of the Gram
of T x2 B x3 C, then B from T x1 A x3 C, then C from T x1 A x2 B.  The
core is T x1 A x2 B x3 C, and the relative error is
||T - G x1 A x2 B x3 C|| / ||T||, summed over slabs of T so that the
reconstruction is never whole.

``precision`` is that of every contraction: ``"highest"`` (float32) for
the reference, ``"high"`` (XLA's three bf16 passes on a TPU) for the
control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["hooi", "make_tensor"]


def make_tensor(n: int, rank, noise_to_tail: float, key):
    """G x1 A x2 B x3 C with standard normal G (``rank``) and factors
    (n x r), plus standard normal noise scaled so that its unfoldings'
    largest singular value, about sd (n + sqrt(n)), is ``noise_to_tail``
    times the signal's smallest mode singular value.  Noise at that
    level turns the HOSVD's subspaces well away from HOOI's, so the
    sweeps do measurable work.  Float32, made on the device in one
    call."""
    return _make_tensor(key, n=n, rank=tuple(rank),
                        noise_to_tail=float(noise_to_tail))


@functools.partial(jax.jit, static_argnames=("n", "rank", "noise_to_tail"))
def _make_tensor(key, *, n, rank, noise_to_tail):
    kg, ka, kb, kc, kn = jax.random.split(key, 5)
    hi = jax.lax.Precision.HIGHEST
    G = jax.random.normal(kg, rank)
    A, B, C = (jax.random.normal(k, (n, r)) for k, r in
               zip((ka, kb, kc), rank))
    T = jnp.einsum("ijk,mi,nj,pk->mnp", G, A, B, C, precision=hi)
    # the signal's mode singular values are those of its core in
    # orthonormal bases of the factors' column spaces
    Ra, Rb, Rc = (jnp.linalg.qr(F, mode="r") for F in (A, B, C))
    S = jnp.einsum("ijk,ai,bj,ck->abc", G, Ra, Rb, Rc, precision=hi)
    tail = jnp.min(jnp.stack([
        jnp.linalg.svd(jnp.moveaxis(S, m, 0).reshape(S.shape[m], -1),
                       compute_uv=False).min() for m in range(3)]))
    sd = noise_to_tail * tail / (n + np.sqrt(n))
    return T + sd * jax.random.normal(kn, (n, n, n))


def _pair(spec, a, b, precision):
    """One pairwise contraction at ``precision``."""
    p = {"highest": jax.lax.Precision.HIGHEST,
         "high": jax.lax.Precision.HIGH}[precision]
    return jnp.einsum(spec, a, b, precision=p)


def _lead(g, r):
    """The ``r`` leading eigenvectors of the symmetric ``g``."""
    _, v = jnp.linalg.eigh(g)
    return v[:, ::-1][:, :r]


@functools.partial(jax.jit, static_argnames=("ranks", "n_iter", "precision",
                                             "slab"))
def hooi(T, *, ranks, n_iter, precision, slab: int = 64):
    """Returns (core, (A, B, C), relative error)."""
    i, j, k = ranks
    e = functools.partial(_pair, precision=precision)
    A = _lead(e("mnp,qnp->mq", T, T), i)
    B = _lead(e("mnp,mqp->nq", T, T), j)
    C = _lead(e("mnp,mnq->pq", T, T), k)

    def sweep(_, fac):
        A, B, C = fac
        Y = e("mnk,nj->mjk", e("mnp,pk->mnk", T, C), B)
        A = _lead(e("mjk,qjk->mq", Y, Y), i)
        Y = e("mnk,mi->ink", e("mnp,pk->mnk", T, C), A)
        B = _lead(e("ink,iqk->nq", Y, Y), j)
        Y = e("inp,nj->ijp", e("mnp,mi->inp", T, A), B)
        C = _lead(e("ijp,ijq->pq", Y, Y), k)
        return A, B, C

    A, B, C = jax.lax.fori_loop(0, n_iter, sweep, (A, B, C))
    G = e("ijp,pk->ijk", e("inp,nj->ijp", e("mnp,mi->inp", T, A), B), C)
    n = T.shape[0]
    blocks = T.reshape(n // slab, slab, *T.shape[1:])
    A_blocks = A.reshape(n // slab, slab, i)

    def slab_err(args):
        t, a = args
        d = t - e("inp,mi->mnp", e("ijp,nj->inp", e("ijk,pk->ijp", G, C),
                                           B), a)
        return jnp.sum(d * d)

    err2 = jnp.sum(jax.lax.map(slab_err, (blocks, A_blocks)))
    return G, (A, B, C), jnp.sqrt(err2) / jnp.sqrt(jnp.sum(T * T))
