"""Flash-attention Pallas kernel vs the dense softmax oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_paged_attention import paged_oracle

from repro.kernels.flash_attn import flash_attention


def dense_oracle(q, k, v, causal=True):
    D = q.shape[-1]
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * D**-0.5
    if causal:
        S, T = s.shape[-2:]
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask, s, -2.0**30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32)).astype(q.dtype)


SHAPES = [
    (2, 64, 64, 64, 16),     # aligned, S == T
    (1, 96, 96, 32, 16),     # ragged blocks
    (3, 128, 256, 64, 32),   # cross attention T > S
    (2, 200, 200, 48, 64),   # odd sizes
]


@pytest.mark.parametrize("bh,s,t,bq,d", SHAPES)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_flash_vs_dense(bh, s, t, bq, d, causal, dtype):
    if causal and t != s:
        pytest.skip("causal requires S == T here")
    rng = np.random.default_rng(bh * 100 + s)
    q = jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((bh, t, d)), dtype)
    v = jnp.asarray(rng.standard_normal((bh, t, d)), dtype)
    ref = dense_oracle(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, blocks={"q": bq, "k": bq})
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_under_jit():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 32)), jnp.float32)
    got = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    ref = dense_oracle(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_wrapper_pattern():
    """GQA: fold (B, G, R) into BH with broadcast KV — the model-side use."""
    rng = np.random.default_rng(1)
    B, G, R, S, D = 2, 2, 3, 64, 16
    q = jnp.asarray(rng.standard_normal((B, G, R, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
    qf = q.reshape(B * G * R, S, D)
    kf = jnp.broadcast_to(k[:, :, None], (B, G, R, S, D)).reshape(B * G * R, S, D)
    vf = jnp.broadcast_to(v[:, :, None], (B, G, R, S, D)).reshape(B * G * R, S, D)
    got = flash_attention(qf, kf, vf).reshape(B, G, R, S, D)
    ref = dense_oracle(
        q.reshape(B * G * R, S, D), kf, vf
    ).reshape(B, G, R, S, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ paged decode
PAGE = 16


#: lengths 1, 16k - 1, 16k and 16k + 1, then request 0 again: the batch
#: padding the runtime adds
LENGTHS = [1, 2 * PAGE - 1, 3 * PAGE, 4 * PAGE + 1, 1]


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window24"])
@pytest.mark.parametrize("heads", [(36, 1, 64), (4, 8, 128)],
                         ids=["mha64", "gqa128"])
def test_paged_attention_vs_dense(heads, window):
    """minicpm-2b's 36 heads of 64 and mellum2-12b-a2.5b's 4 kv heads of
    128 with 8 queries each, in one lane-dense row per token: the paged
    kernel equals the dense masked softmax over the request's pages,
    scattered through the pool and null-padded, window cut included."""
    from repro.kernels.flash_attn import paged_attention

    G, R, D = heads
    rng = np.random.default_rng(G)
    n_pages, layers, P = 24, 2, 6
    k_pages, v_pages = (
        jnp.asarray(rng.standard_normal((n_pages, layers, 1, PAGE, G * D)),
                    jnp.float32) for _ in range(2))
    lengths = np.asarray(LENGTHS, np.int32)
    B = len(lengths)
    free = list(rng.permutation(np.arange(1, n_pages)))
    tables = np.zeros((B, P), np.int32)               # the null page pads
    for b, n in enumerate(lengths[:-1]):
        k = n // PAGE + 1
        tables[b, :k] = [free.pop() for _ in range(k)]
    tables[-1] = tables[0]
    pad = lambda x: jnp.concatenate([x[:-1], x[:1]])     # noqa: E731
    q = pad(jnp.asarray(rng.standard_normal((B, G, R, D)), jnp.float32))
    k_new, v_new = (pad(jnp.asarray(rng.standard_normal((B, G, D)),
                                    jnp.float32)) for _ in range(2))
    got = paged_attention(q, k_new, v_new, k_pages, v_pages,
                          jnp.asarray(tables), jnp.asarray(lengths), 1,
                          window=window)
    want = paged_oracle(q, k_new, v_new, k_pages, v_pages, tables, lengths,
                        1, window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[-1], np.asarray(got)[0])


@pytest.mark.parametrize("window", [None, 1, 17, 100])
def test_page_span_covers_the_attended_rows(window):
    """The pages a request reads hold exactly the rows its window
    reaches, on the host (NumPy) as in the kernel."""
    from repro.kernels.flash_attn import page_span

    lengths = np.arange(0, 70)
    spans = (np.broadcast_to(x, lengths.shape)
             for x in page_span(lengths, window, PAGE, np))
    for n, s, f, e in zip(lengths, *spans):
        rows = [t for t in range(n) if window is None or n - t < window]
        assert s == (rows[0] if rows else s)
        assert (f, e) == ((rows[0] // PAGE, (rows[-1] // PAGE) + 1)
                          if rows else (f, f))
