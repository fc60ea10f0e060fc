"""Paged decode attention and the page pool, against brute force.

``paged_attention`` (``kernels/flash_attn.py``) against a float32 dense
oracle over each request's pages; ``PagedKV.reads`` and ``page_span``
against a count of the rows ``layers._attn_mask`` attends; ``PagePool``'s
bookkeeping under random admit/grow/evict/release/register sequences.
"""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels.flash_attn import page_span, paged_attention
from repro.models.layers import _attn_mask
from repro.runtime.pages import NULL_PAGE, PagedKV, PagePool, PoolExhausted
from repro.runtime.scheduler import Request, RequestState

#: minicpm-2b's 36 heads of 64 (one kv head per query head),
#: mellum2-12b-a2.5b's 4 kv heads of 128 with 8 queries each, and a
#: narrow GQA row of 2 kv heads of 64
MINICPM, MELLUM, NARROW = (36, 1, 64), (4, 8, 128), (2, 4, 64)
HEADS = {"minicpm": MINICPM, "mellum": MELLUM, "narrow": NARROW}
P = 6        # table width: pages a request may hold
LAYERS = 3


def paged_oracle(q, k_new, v_new, k_pages, v_pages, tables, lengths, layer,
                 window, softcap=None):
    """Dense masked softmax in float32, request by request: the cached
    rows laid end to end from the request's pages, the new row last, at
    position ``lengths[b]``; scores scaled by ``D**-0.5``, then capped
    at ``softcap * tanh(s / softcap)`` when ``softcap`` is set."""
    B, G, R, D = q.shape
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))  # noqa: E731
    out = []
    for b in range(B):
        n = int(lengths[b])
        rows = lambda pages: np.concatenate(  # noqa: E731
            [f32(pages[p, layer, 0]) for p in tables[b]]
        )[:n].reshape(n, G, D)
        k = np.concatenate([rows(k_pages), f32(k_new[b])[None]])
        v = np.concatenate([rows(v_pages), f32(v_new[b])[None]])
        s = np.einsum("grd,tgd->grt", f32(q[b]), k) * D**-0.5
        if softcap is not None:
            s = softcap * np.tanh(s / softcap)
        rel = n - np.arange(n + 1)
        s = np.where(rel < (window or n + 1), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append(np.einsum("grt,tgd->grd", p / p.sum(-1, keepdims=True), v))
    return np.stack(out)


def _run(lengths, *, ps, heads, window=None, softcap=None, layer=1,
         dtype=jnp.float32, seed=0):
    """Scatter random K/V for ``lengths`` through a shuffled pool (tables
    null-padded to ``P``), run the kernel and the oracle."""
    G, R, D = heads
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    B = len(lengths)
    held = [min(int(n) // ps + 1, P) for n in lengths]
    n_pages = sum(held) + 2
    k_pages, v_pages = (
        jnp.asarray(rng.standard_normal((n_pages, LAYERS, 1, ps, G * D)),
                    dtype) for _ in range(2))
    free = list(rng.permutation(np.arange(1, n_pages)))
    tables = np.full((B, P), NULL_PAGE, np.int32)
    for b, k in enumerate(held):
        tables[b, :k] = [free.pop() for _ in range(k)]
    # scores of a few units, so a softcap of 50 bends the largest
    q = jnp.asarray(4 * rng.standard_normal((B, G, R, D)), dtype)
    k_new, v_new = (jnp.asarray(rng.standard_normal((B, G, D)), dtype)
                    for _ in range(2))
    got = paged_attention(q, k_new, v_new, k_pages, v_pages,
                          jnp.asarray(tables), jnp.asarray(lengths), layer,
                          window=window, softcap=softcap)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = paged_oracle(q, k_new, v_new, k_pages, v_pages, tables, lengths,
                        layer, window, softcap)
    return np.asarray(got, np.float32), want


# ------------------------------------------------------------- the kernel
@pytest.mark.parametrize("softcap", [None, 50.0], ids=["nocap", "cap50"])
@pytest.mark.parametrize("heads", list(HEADS.values()), ids=list(HEADS))
@pytest.mark.parametrize("window", [None, 1, 17, 40],
                         ids=["full", "w1", "w17", "w40"])
@pytest.mark.parametrize("ps", [8, 16], ids=["ps8", "ps16"])
def test_paged_attention_matrix(ps, window, heads, softcap):
    """Six requests at lengths 1, ps-1, ps, ps+1, 2ps+3 and P*ps-1 (the
    last page's last row): the kernel equals the dense oracle over the
    request's scattered pages, window cut and softcap included."""
    lengths = [1, ps - 1, ps, ps + 1, 2 * ps + 3, P * ps - 1]
    got, want = _run(lengths, ps=ps, heads=heads, window=window,
                     softcap=softcap, seed=ps + (window or 0))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


PS = 8


@pytest.mark.parametrize("length,window", [
    (1, None), (PS - 1, None), (PS, None), (PS + 1, None),
    (P * PS - 1, None), (P * PS - 1, P * PS - 1),
], ids=["1", "ps-1", "ps", "ps+1", "full-table", "window-eq-length"])
def test_paged_attention_lengths(length, window):
    """One request at each page-boundary length, and a window equal to
    the length (its first cached row falls out)."""
    got, want = _run([length], ps=PS, heads=NARROW, window=window,
                     seed=length)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, LAYERS - 1], ids=["first", "last"])
def test_paged_attention_layer_index(layer):
    """The kernel reads the asked layer's rows of each page, at both ends
    of the page's layer axis."""
    got, want = _run([3, 2 * PS, 4 * PS + 1], ps=PS, heads=MELLUM,
                     window=17, layer=layer, seed=layer)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads,window", [(MINICPM, None), (MELLUM, 17)],
                         ids=["minicpm", "mellum"])
def test_paged_attention_bf16_pool(heads, window):
    """A bf16 pool and queries, as both configurations serve, against the
    float32 oracle over the same bf16 values."""
    got, want = _run([1, PS + 1, 3 * PS, P * PS - 1], ps=PS, heads=heads,
                     window=window, dtype=jnp.bfloat16, seed=7)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# ------------------------------------------------------- what a launch reads
def _attended(n: int, window) -> np.ndarray:
    """Cached rows ``[0, n)`` the new row at position ``n`` attends,
    read off the model's own mask."""
    ok = _attn_mask(jnp.asarray([n]), jnp.arange(n), causal=True,
                    window=window)
    return np.flatnonzero(np.asarray(ok)[0])


@pytest.mark.parametrize("ps", [8, 16], ids=["ps8", "ps16"])
@pytest.mark.parametrize("window", [None, 1, 15, 16, 17, 1024],
                         ids=["full", "w1", "w15", "w16", "w17", "w1024"])
def test_reads_counts_the_pages_the_mask_touches(window, ps):
    """``page_span`` bounds exactly the attended rows and their pages, and
    ``PagedKV.reads`` counts, over a batch, the rows of the pages a full
    layer reads plus each new row, and the pages every attention layer
    reads."""
    pattern = (LayerSpec(window=window), LayerSpec(), LayerSpec(window=window))
    cfg = ModelConfig(arch_id="reads", d_model=32, n_heads=2, n_kv_heads=1,
                      d_ff=64, vocab_size=64, pattern=pattern, dtype="float32")
    kv = PagedKV(cfg, 4, ps)
    assert kv.inplace
    edges = [1, 2, ps - 1, ps, ps + 1, 3 * ps + 5, 1030, 1100]
    if window is not None:
        edges += [window - 1, window, window + 1, window + ps]
    lengths = sorted({n for n in edges if n >= 1})

    rows = pages = 0
    for n in lengths:
        for spec in pattern:
            att = _attended(n, spec.window)
            start, first, end = (int(x) for x in
                                 page_span(n, spec.window, ps, np))
            touched = sorted({int(t) // ps for t in att})
            if len(att):
                assert start == att[0]
                assert (first, end) == (touched[0], touched[-1] + 1)
            else:
                assert first == end
            pages += len(touched)
        rows += len({int(t) // ps for t in _attended(n, None)}) * ps + 1
    assert kv.reads(lengths, len(lengths), 8) == (rows, pages)


# ----------------------------------------------------------- pool invariants
def _check_pool(pool: PagePool, live: list) -> None:
    holders = collections.Counter(p for s in live for p in s.pages)
    for s in live:
        assert len(set(s.pages)) == len(s.pages)
    free = pool._free
    assert len(set(free)) == len(free)
    assert NULL_PAGE not in holders and NULL_PAGE not in free
    assert not set(free) & set(holders)
    assert pool.n_free + len(holders) == pool.usable
    assert pool.refcount == dict(holders)
    for h, p in pool._hash_to_page.items():
        assert p in holders and pool._page_hash[p] == h
    assert set(pool._page_hash) <= set(holders)
    if not pool.prefix_sharing:
        assert not pool._hash_to_page and not pool._page_hash


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("sharing", [True, False], ids=["share", "noshare"])
@pytest.mark.parametrize("ps", [4, 16], ids=["ps4", "ps16"])
def test_page_pool_invariants(ps, sharing, seed):
    """Random admit/grow/evict/release/register sequences over prompts that
    share prefixes: after every operation the free pages and the distinct
    held pages make up the pool, refcounts equal holders, the null page is
    never handed out and the prefix index points only at held pages; a
    drained pool is all free."""
    rng = np.random.default_rng(seed)
    max_rows = 6 * ps
    pool = PagePool(20, ps, max_rows=max_rows, prefix_sharing=sharing)
    prefixes = [rng.integers(0, 50, 3 * ps) for _ in range(3)]
    waiting, live = [], []
    rid = 0

    def new_request():
        nonlocal rid
        head = prefixes[rng.integers(len(prefixes))][:rng.integers(1, 3 * ps)]
        tail = rng.integers(0, 50, rng.integers(0, 2 * ps))
        prompt = np.concatenate([head, tail]).astype(np.int32)
        rid += 1
        return RequestState(Request(rid=rid, prompt=prompt), seed=0)

    def drop(state):
        pool.release(state.pages, rid=state.rid)
        state.pages, state.page_hashes = [], []
        live.remove(state)

    for _ in range(300):
        op = rng.choice(["admit", "grow", "evict", "release", "register"],
                        p=[0.3, 0.3, 0.1, 0.15, 0.15])
        if op == "admit":
            state = waiting.pop(0) if waiting and rng.random() < 0.5 \
                else new_request()
            if pool.try_admit(state):
                live.append(state)
            else:
                assert not state.pages
                waiting.append(state)
        elif live and op == "grow":
            state = live[rng.integers(len(live))]
            if len(state.pages) < pool.pages_for(max_rows):
                try:
                    state.pages += pool.alloc(1, rid=state.rid)
                except PoolExhausted:      # the youngest is evicted
                    victim = max(live, key=lambda s: s.rid)
                    drop(victim)
                    waiting.append(victim)
        elif live and op == "evict":
            victim = live[rng.integers(len(live))]
            drop(victim)
            waiting.append(victim)
        elif live and op == "release":
            drop(live[rng.integers(len(live))])
        elif live and op == "register":
            pool.register(live[rng.integers(len(live))])
        _check_pool(pool, live)

    assert pool.page_allocs > pool.usable      # the pool turned over
    if sharing:
        assert pool.prefix_hits > 0
    while live:
        drop(live[-1])
        _check_pool(pool, live)
    assert pool.n_free == pool.usable and not pool.refcount
    assert not pool._hash_to_page and not pool._page_hash
