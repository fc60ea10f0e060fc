"""Paged KV-cache: pool bookkeeping, prefix sharing, and the paged
runtime's differential guarantees (token identity vs the unpaged
runtime, the page-leak invariant, eviction/cancellation under memory
pressure)."""

import warnings

import jax
import numpy as np
import pytest

from repro.runtime.buckets import BucketLattice
from repro.runtime.pages import NULL_PAGE, PagePool, PoolExhausted
from repro.runtime.scheduler import Request, RequestState


# ----------------------------------------------------------------- helpers
def _state(rid, prompt, max_new=4):
    return RequestState(Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                                max_new_tokens=max_new))


# ---------------------------------------------------------------- PagePool
class TestPagePool:
    def test_geometry_and_null_page_reserved(self):
        pool = PagePool(9, 4)
        assert pool.usable == 8 and pool.n_free == 8
        assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
        assert pool.pages_for(5) == 2
        # prompt + first decode row, capped at max_rows
        assert pool.required_pages(3) == 1        # 4 rows
        assert pool.required_pages(4) == 2        # 5 rows
        pages = pool.alloc(8)
        assert NULL_PAGE not in pages             # never handed out
        assert pool.n_free == 0

    def test_alloc_exhaustion_is_atomic(self):
        pool = PagePool(4, 2)
        pool.alloc(2)
        with pytest.raises(PoolExhausted):
            pool.alloc(2)
        assert pool.n_free == 1                   # nothing half-allocated

    def test_release_refcounts_and_free_list(self):
        pool = PagePool(5, 2)
        pages = pool.alloc(3)
        pool.refcount[pages[0]] += 1              # simulate one sharer
        freed = pool.release(pages)
        assert freed == 2                         # shared page survives
        assert pool.refcount == {pages[0]: 1}
        assert pool.release([pages[0]]) == 1
        assert pool.n_free == pool.usable and not pool.refcount

    def test_chain_hashes_prefix_property(self):
        pool = PagePool(9, 4)
        a = pool._chain_hashes(np.arange(12, dtype=np.int32))
        b = pool._chain_hashes(np.r_[np.arange(8), 99, 1, 2, 3].astype(np.int32))
        assert a[:2] == b[:2] and a[2] != b[2]    # chain digest covers prefix
        assert len(pool._chain_hashes(np.arange(7, dtype=np.int32))) == 1

    def test_admit_register_share_release_cycle(self):
        pool = PagePool(17, 4)
        prompt = np.arange(13, dtype=np.int32)    # 3 full pages + 1 row
        s0 = _state(0, prompt)
        assert pool.try_admit(s0)
        assert len(s0.pages) == 4 and s0.shared_tokens == 0
        pool.register(s0)
        assert pool.stats()["prefix_index_size"] == 3

        s1 = _state(1, prompt)                    # identical prompt
        assert pool.try_admit(s1)
        assert s1.pages[:3] == s0.pages[:3]       # mapped, not recomputed
        assert s1.shared_tokens == 12
        assert pool.prefix_hits == 1 and pool.prefix_shared_tokens == 12
        assert [pool.refcount[p] for p in s0.pages[:3]] == [2, 2, 2]

        pool.release(s0.pages, rid=0)             # owner leaves first
        assert all(p in pool.refcount for p in s1.pages)
        pool.release(s1.pages, rid=1)
        assert pool.n_free == pool.usable and not pool.refcount
        assert pool.stats()["prefix_index_size"] == 0

    def test_share_capped_to_leave_one_prefill_token(self):
        """A fully-resident prompt still prefills its last page — the
        first token's logits must come from a real prefill."""
        pool = PagePool(17, 4)
        prompt = np.arange(12, dtype=np.int32)    # exactly 3 pages
        s0 = _state(0, prompt)
        pool.try_admit(s0)
        pool.register(s0)
        s1 = _state(1, prompt)
        pool.try_admit(s1)
        assert s1.shared_tokens == 8              # (12-1)//4 = 2 pages

    def test_register_repoints_duplicate_prefix(self):
        """Two requests admitted together prefill the same prefix into
        private pages; the index must survive the first one's release by
        re-pointing at the newer copy (latest-registrant-wins)."""
        pool = PagePool(17, 4)
        prompt = np.arange(13, dtype=np.int32)
        s0, s1 = _state(0, prompt), _state(1, prompt)
        pool.try_admit(s0)
        pool.try_admit(s1)                        # index empty: no sharing
        assert s1.shared_tokens == 0
        pool.register(s0)
        pool.register(s1)                         # re-points to s1's pages
        pool.release(s0.pages, rid=0)
        assert pool.stats()["prefix_index_size"] == 3
        s2 = _state(2, prompt)
        pool.try_admit(s2)
        assert s2.pages[:3] == s1.pages[:3]

    def test_can_admit_is_pure(self):
        pool = PagePool(3, 4, max_rows=64)        # 2 usable pages
        assert pool.can_admit(np.arange(7, dtype=np.int32))
        assert not pool.can_admit(np.arange(12, dtype=np.int32))
        assert pool.n_free == 2 and pool.admission_blocks == 0

    def test_try_admit_blocks_without_allocating(self):
        pool = PagePool(3, 4, max_rows=64)
        s = _state(0, np.arange(12, dtype=np.int32))  # needs 4 pages
        assert not pool.try_admit(s)
        assert s.pages == [] and pool.n_free == 2
        assert pool.admission_blocks == 1

    def test_prefix_sharing_off(self):
        pool = PagePool(17, 4, prefix_sharing=False)
        prompt = np.arange(13, dtype=np.int32)
        s0 = _state(0, prompt)
        pool.try_admit(s0)
        pool.register(s0)
        s1 = _state(1, prompt)
        pool.try_admit(s1)
        assert s1.shared_tokens == 0 and pool.prefix_hits == 0


# ------------------------------------------------------------ page lattice
class TestPageLattice:
    def test_page_buckets(self):
        lat = BucketLattice(4, max_chunk=8, max_pages=16)
        assert lat.page_buckets == (1, 2, 4, 8, 16)
        assert lat.page_bucket(3) == 4 and lat.page_bucket(16) == 16
        with pytest.raises(ValueError):
            lat.page_bucket(17)
        unpaged = BucketLattice(4, max_chunk=8)
        assert unpaged.page_buckets == ()
        with pytest.raises(ValueError):
            unpaged.page_bucket(1)

    def test_tuple_bucket_keys(self):
        from repro.runtime.buckets import BucketTable

        t = BucketTable()
        assert t.key("decode", (4, np.int64(8)), None) == ("decode", (4, 8), None)
        assert t.key("prefill", 4, None) == ("prefill", 4, None)


# ------------------------------------------------- paged runtime (w/ model)
@pytest.fixture(scope="module")
def served():
    from repro.configs import get_config
    from repro.models.transformer import Model

    cfg = get_config("minicpm-2b", smoke=True).with_(n_periods=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _requests(cfg, lens, max_new=4, prefix=None):
    out = []
    for i, ln in enumerate(lens):
        rng = np.random.default_rng(1000 + i)
        tail = rng.integers(0, cfg.vocab_size, size=ln).astype(np.int32)
        prompt = tail if prefix is None else np.concatenate([prefix, tail])
        out.append(Request(rid=i, prompt=prompt,
                           max_new_tokens=max_new[i]
                           if isinstance(max_new, list) else max_new))
    return out


def _assert_drained(rt):
    """The page-leak invariant: after serve() drains, every page is back
    on the free list and no refcount survives."""
    assert rt.pool.n_free == rt.pool.usable
    assert not rt.pool.refcount


def test_paged_token_identity_and_leak_invariant(served):
    """The tentpole oracle: paged gather/scatter over page tables (with
    null-page padding and page-count bucketing) is greedy
    token-identical to the unpaged runtime on ragged traffic."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    lens = [3, 11, 7, 19, 2, 13]
    ref = _requests(cfg, lens)
    ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                   precompile=False).serve(ref)

    got = _requests(cfg, lens)
    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        precompile=False, paged=True, page_size=4)
    rt.serve(got)
    for a, b in zip(ref, got):
        assert b.done and b.output == a.output, (a.rid, a.output, b.output)
    _assert_drained(rt)
    # paged bucket keys are lattice tuples; unpaged stay ints
    kinds = {k[0] for k in rt.buckets.keys()}
    assert kinds <= {"decode", "prefill", "page_view", "page_commit"}
    assert all(isinstance(k[1], tuple) for k in rt.buckets.keys()
               if k[0] in ("decode", "prefill"))


def test_prefix_sharing_differential(served):
    """Shared system prompt: the sharing runtime must emit exactly the
    tokens the non-sharing one does — a shared page is bit-identical to
    what prefill would recompute — while actually sharing."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rng = np.random.default_rng(7)
    sysp = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
    lens = [3, 5, 4, 6, 2]
    stagger = [4, 7, 10, 13, 16]      # lifetimes overlap → sharing chains

    ref = _requests(cfg, lens, max_new=stagger, prefix=sysp)
    unshared = ServingRuntime(cfg, params, slots=2, max_len=64,
                              prefill_chunk=8, precompile=False,
                              paged=True, page_size=4, prefix_sharing=False)
    unshared.serve(ref)
    assert unshared.pool.prefix_hits == 0
    _assert_drained(unshared)

    got = _requests(cfg, lens, max_new=stagger, prefix=sysp)
    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        precompile=False, paged=True, page_size=4)
    rt.serve(got)
    for a, b in zip(ref, got):
        assert b.done and b.output == a.output, (a.rid, a.output, b.output)
    assert rt.pool.prefix_hits > 0
    assert rt.metrics.prefix_shared_tokens > 0
    _assert_drained(rt)


def test_pool_exhaustion_preempts_and_drains(served):
    """A pool too small for the offered load: admission blocks, decode
    growth preempts (youngest evicted, marked not dropped), and the pool
    still drains clean."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=4, max_len=64, prefill_chunk=8,
                        precompile=False, paged=True, page_size=4, pages=9)
    reqs = _requests(cfg, [14, 15, 13, 14], max_new=12)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rt.serve(reqs, max_steps=200)
    assert all(r.status in ("done", "evicted") for r in reqs)
    assert any(r.status == "done" for r in reqs)
    assert rt.pool.admission_blocks > 0 or rt.metrics.evictions > 0
    _assert_drained(rt)


def test_cancel_while_queued(served):
    """The S1 regression: a rid still in the queue (no slot, no pages)
    is cancellable — previously a KeyError."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=1, max_len=64, prefill_chunk=8,
                        precompile=False, paged=True, page_size=4)
    a, b = _requests(cfg, [5, 7])
    rt.submit(a)
    rt.submit(b)                      # b queued behind a's slot
    req = rt.evict(b.rid)
    assert req is b
    assert b.status == "evicted" and not b.done
    assert rt.metrics.evictions == 1
    rt.serve([])                      # drain a
    assert a.done
    _assert_drained(rt)
    with pytest.raises(KeyError, match="neither active nor queued"):
        rt.scheduler.evict(99)


def test_evict_while_prefilling_releases_pages(served):
    """Evicting mid-prefill (slot bound, pages held, prompt not yet
    committed) releases the slot and every page."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=1, max_len=64, prefill_chunk=4,
                        precompile=False, paged=True, page_size=4)
    (a,) = _requests(cfg, [19])       # several chunks of prefill
    rt.submit(a)
    rt.tick()                         # admit + first chunk only
    assert a.status == "prefill" and rt.pool.n_free < rt.pool.usable
    rt.evict(a.rid)
    assert a.status == "evicted" and not a.done
    _assert_drained(rt)
    assert not rt.scheduler.has_work()


def test_serve_rejects_offender_and_serves_rest(served):
    """The S2 regression: an over-long prompt mid-list must not abandon
    the half-submitted batch — it is marked rejected and the rest are
    served to completion."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=16, prefill_chunk=8,
                        precompile=False)
    good1, good2 = _requests(cfg, [5, 6], max_new=3)
    bad = Request(rid=99, prompt=np.zeros(17, np.int32), max_new_tokens=3)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rt.serve([good1, bad, good2])
    assert bad.status == "rejected" and not bad.done and bad.output == []
    assert good1.done and good2.done
    assert rt.metrics.rejections == 1
    assert rt.metrics.snapshot()["rejections"] == 1
    assert any("rejected" in str(x.message) for x in w)
    # direct submit of an unservable request still raises
    with pytest.raises(ValueError, match="exceeds max_len"):
        rt.submit(Request(rid=100, prompt=np.zeros(17, np.int32)))


def test_paged_rejects_prompt_too_big_for_pool(served):
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        precompile=False, paged=True, page_size=4, pages=5)
    with pytest.raises(ValueError, match="pool holds"):
        rt.submit(Request(rid=0, prompt=np.zeros(30, np.int32)))


def test_precompile_buckets_pins_compile_set(served):
    """After precompile_buckets(), a served trace creates no new bucket
    entries — the zero-recompile steady state is deterministic, not
    warm-up dependent."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=32, prefill_chunk=8,
                        precompile=False, paged=True, page_size=4)
    n = rt.precompile_buckets()
    assert n == rt.buckets.compiles > 0
    reqs = _requests(cfg, [3, 9, 14], max_new=4)
    rt.serve(reqs)
    assert all(r.done for r in reqs)
    assert rt.buckets.compiles == n
    _assert_drained(rt)


def test_paged_guardrails(served):
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    with pytest.raises(NotImplementedError, match="sharded"):
        ServingRuntime(cfg, params, slots=2, max_len=32, paged=True,
                       precompile=False, mesh=object())


# ------------------------------------------- PagedKV gather / commit / write
KV_ROWS = 4                                    # rows per page
KV_LATTICE = BucketLattice(2, max_chunk=8, max_pages=8).page_buckets


@pytest.fixture(scope="module", params=["bf16", "kv_quant"])
def paged_kv(request):
    """A pool of 2 x 8 pages + the null page, every row of every leaf
    filled with distinct small integers (exact in bf16 and int8)."""
    from repro.configs import get_config
    from repro.runtime.pages import PagedKV

    cfg = get_config("minicpm-2b", smoke=True).with_(
        n_periods=2, kv_quant=request.param == "kv_quant")
    kv = PagedKV(cfg, n_pages=2 * max(KV_LATTICE) + 1, page_size=KV_ROWS)
    rng = np.random.default_rng(3)
    kv.pool = jax.tree.map(
        lambda x: jax.numpy.asarray(rng.integers(-99, 99, x.shape), x.dtype),
        kv.pool)
    return kv


def _tables(kv, P, lengths, seed=0):
    """(B, P) page tables covering each length's rows with distinct
    pages, padded with the null page."""
    rng = np.random.default_rng(seed)
    free = list(rng.permutation(np.arange(1, kv.n_pages)))
    out = np.full((len(lengths), P), NULL_PAGE, np.int32)
    for b, n in enumerate(lengths):
        k = n // kv.page_size + 1
        out[b, :k] = [free.pop() for _ in range(k)]
    return out


def _dense_view(kv, pool, tables, lengths):
    """The reference view, page by page in NumPy: each request's pages
    laid end to end along the token axis, rows unflattened."""
    def view(ax, spec, leaf):
        B = len(tables)
        if ax < 0:
            return np.broadcast_to(
                np.reshape(lengths, (B,) + (1,) * spec.ndim),
                (B,) + spec.shape).astype(spec.dtype)
        leaf = np.asarray(leaf)
        return np.stack([
            np.concatenate([leaf[p] for p in row], axis=ax).reshape(
                spec.shape[:ax] + (-1,) + spec.shape[ax + 1:])
            for row in tables])

    return jax.tree.map(view, kv.axes, kv.shapes, pool)


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("P", KV_LATTICE)
def test_gather_matches_dense_reference(paged_kv, P):
    """The page-major pool's views equal a dense view built row by row
    from the same pages, null-padded tables included."""
    kv = paged_kv
    lengths = np.asarray([P * kv.page_size - 1, (P * kv.page_size) // 3],
                         np.int32)
    tables = _tables(kv, P, lengths)
    got = jax.jit(kv._gather)(kv.pool, jax.numpy.asarray(tables),
                              jax.numpy.asarray(lengths))
    _assert_trees_equal(got, _dense_view(kv, kv.pool, tables, lengths))


@pytest.mark.parametrize("P", KV_LATTICE)
def test_one_gather_matches_dense_reference(paged_kv, P, monkeypatch):
    """Past ``MAX_UNROLLED_PAGES`` slices the view is one gather: the
    same views as the unrolled slices."""
    from repro.runtime import pages

    monkeypatch.setattr(pages, "MAX_UNROLLED_PAGES", 0)
    kv = paged_kv
    lengths = np.asarray([P * kv.page_size - 1, (P * kv.page_size) // 3],
                         np.int32)
    tables = _tables(kv, P, lengths, seed=1)
    got = jax.jit(kv._gather)(kv.pool, jax.numpy.asarray(tables),
                              jax.numpy.asarray(lengths))
    _assert_trees_equal(got, _dense_view(kv, kv.pool, tables, lengths))


@pytest.mark.parametrize("P", KV_LATTICE)
def test_commit_then_gather_round_trips(paged_kv, P):
    """A staged batch-1 cache committed into its pages reads back
    unchanged through the view; pages outside the table are untouched."""
    kv = paged_kv
    n = max(1, P - 1)                  # real pages; the rest null-padded
    pages = _tables(kv, P, [n * kv.page_size - 1], seed=P)[0]
    rng = np.random.default_rng(P)

    def staged(ax, spec):
        shape = spec.shape if ax < 0 else (
            spec.shape[:ax] + (P * kv.page_size,) + spec.shape[ax + 1:])
        return jax.numpy.asarray(rng.integers(-99, 99, shape), spec.dtype)

    stage = jax.tree.map(staged, kv.axes, kv.shapes)
    pool = kv.build_commit(P)(kv.pool, stage, jax.numpy.asarray(pages))
    length = np.asarray([7], np.int32)
    view = kv.build_view(P)(pool, jax.numpy.asarray(pages[None]),
                            jax.numpy.asarray(length))
    rows = n * kv.page_size

    def check(ax, spec, got, want, before, after):
        if ax < 0:
            np.testing.assert_array_equal(np.asarray(got), 7)
            return
        keep = (slice(None),) * ax + (slice(0, rows),)
        np.testing.assert_array_equal(np.asarray(got)[keep],
                                      np.asarray(want)[keep])
        untouched = np.setdiff1d(np.arange(kv.n_pages), pages)
        np.testing.assert_array_equal(np.asarray(after)[untouched],
                                      np.asarray(before)[untouched])

    jax.tree.map(check, kv.axes, kv.shapes, view, stage, kv.pool, pool)


def _marking_step(kv):
    """A stand-in for the vmapped model step: writes ``token + 1`` into
    row ``length`` of every token leaf and returns the token as logits."""
    from jax import lax

    def step(params, cache, tok):
        def w(ax, leaf):
            if ax < 0:
                return leaf
            row = jax.numpy.full(leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:],
                                 tok[0, 0] + 1, leaf.dtype)
            return lax.dynamic_update_slice_in_dim(leaf, row, cache["length"],
                                                   axis=ax)

        return tok[:, 0].astype(np.float32), jax.tree.map(w, kv.axes, cache)

    return jax.vmap(step, in_axes=(None, 0, 0))


def _marking_paged_step(kv):
    """The same stand-in for the in-place step: each leaf's new row, in
    the cache's layout with the batch on its batch axis, is ``token +
    1``."""
    def step(cfg, params, pool, tables, lengths, tokens, stats=False):
        B = tokens.shape[0]

        def w(ax, spec):
            if ax < 0:
                return lengths + 1
            shape = spec.shape[:ax - 1] + (B, 1) + spec.shape[ax + 1:]
            mark = (tokens[:, 0] + 1).astype(spec.dtype)
            return jax.numpy.broadcast_to(
                mark.reshape((1,) * (ax - 1) + (B,) + (1,) * (len(shape) - ax)),
                shape)

        return tokens[:, 0].astype(np.float32), jax.tree.map(
            w, kv.axes, kv.shapes)

    return step


@pytest.mark.parametrize("P", KV_LATTICE)
def test_decode_writes_one_row_per_request(paged_kv, P, monkeypatch):
    """The decode's row write lands at ``(tables[b, len // ps], len %
    ps)`` and nowhere else, with the batch padded by a duplicate of
    request 0 as the engine pads it, whether the step reads the pool in
    place (bf16) or through views (``kv_quant``)."""
    kv = paged_kv
    lengths = np.asarray([P * kv.page_size - 2, (P * kv.page_size) // 2],
                         np.int32)
    tables = _tables(kv, P, lengths, seed=7)
    toks = np.asarray([[[5]], [[9]]], np.int32)
    pad = lambda a: np.concatenate([a, a[:1]])            # noqa: E731
    assert kv.inplace == (not kv.cfg.kv_quant)
    monkeypatch.setattr("repro.models.transformer.decode_step_paged",
                        _marking_paged_step(kv))
    step = kv.build_decode(_marking_step(kv), 3, P)
    logits, pool = step(None, kv.pool, *map(jax.numpy.asarray, (
        pad(tables), pad(lengths), pad(toks))))
    np.testing.assert_array_equal(np.asarray(logits)[:, 0], [5, 9, 5])

    def check(ax, before, after):
        if ax < 0:
            assert before is None and after is None
            return
        want = np.array(before)
        for b, n in enumerate(lengths):
            page, row = tables[b, n // kv.page_size], n % kv.page_size
            want[(page,) + (slice(None),) * ax + (row,)] = toks[b, 0, 0] + 1
        np.testing.assert_array_equal(np.asarray(after), want)

    jax.tree.map(check, kv.axes, kv.pool, pool)


# ------------------------------------------- decode reading pages in place
def _step_case(arch, kv_quant=False):
    """A tiny bf16 model of ``arch``'s attention shape, a pool of 4-row
    pages filled with small random K/V, and a decode batch over it:
    lengths 1, 4k - 1, 4k, 4k + 1 and more, scattered tables padded with
    the null page, request 0 duplicated as the engine pads."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.transformer import Model, decode_step
    from repro.runtime.pages import PagedKV

    cfg = get_config(arch, smoke=True, dtype="bfloat16",
                     param_dtype="bfloat16", kv_quant=kv_quant)
    if arch == "mellum2-12b-a2.5b":   # GQA, windows shorter than the rows
        cfg = cfg.with_(n_kv_heads=2, pattern=tuple(
            dataclasses.replace(s, window=6) if s.window else s
            for s in cfg.pattern))
    params = Model(cfg).init(jax.random.PRNGKey(1))
    kv = PagedKV(cfg, n_pages=24, page_size=KV_ROWS)
    rng = np.random.default_rng(5)
    kv.pool = jax.tree.map(lambda x: jax.numpy.asarray(
        rng.integers(-99, 99, x.shape) / (99 if x.dtype != np.int8 else 1),
        x.dtype), kv.pool)
    lengths = np.asarray([1, 7, 8, 9, 18, 1], np.int32)
    tables = _tables(kv, 6, lengths[:-1], seed=11)
    tables = np.concatenate([tables, tables[:1]])
    toks = rng.integers(0, cfg.vocab_size, (len(lengths), 1, 1)).astype(
        np.int32)
    toks[-1] = toks[0]
    moe = any(s.ff == "moe" for s in cfg.layers)
    vm = jax.vmap(lambda p, c, t: decode_step(cfg, p, c, t, stats=moe),
                  in_axes=(None, 0, 0))
    args = (params, kv.pool) + tuple(
        jax.numpy.asarray(a) for a in (tables, lengths, toks))
    return kv, vm, args


def _as_f32(tree):
    return jax.tree.map(lambda x: x.astype(np.float32)
                        if jax.numpy.issubdtype(x.dtype, jax.numpy.floating)
                        else x, tree)


@pytest.mark.parametrize("arch", ["minicpm-2b", "mellum2-12b-a2.5b"])
def test_in_place_decode_matches_the_view_path(arch):
    """The bf16 decode reading pages in place gives the logits of the
    view path run in float32 on the same weights and pool, within bf16
    rounding (the bf16 view path reads 1.0% and 1.7% of the largest
    logit away), for MHA (minicpm-2b) and GQA with window layers
    (mellum2-12b-a2.5b); and writes each request's new row of every leaf
    into the same page and offset, every other pool row untouched."""
    from repro.models.transformer import decode_step
    from repro.runtime.pages import PagedKV

    kv, _, args = _step_case(arch)
    tables, lengths = np.asarray(args[2]), np.asarray(args[3])
    got = kv.build_decode(None, len(lengths), tables.shape[1])(*args)
    cfg = kv.cfg.with_(dtype="float32", param_dtype="float32")
    ref = PagedKV(cfg, kv.n_pages, kv.page_size)
    ref.inplace = False
    moe = any(s.ff == "moe" for s in cfg.layers)
    vm = jax.vmap(lambda p, c, t: decode_step(cfg, p, c, t, stats=moe),
                  in_axes=(None, 0, 0))
    want = ref.build_decode(vm, len(lengths), tables.shape[1])(
        *_as_f32(args[:2]), *args[2:])
    assert len(got) == len(want)
    scale = float(np.abs(np.asarray(want[0])).max())
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0]), atol=3e-2 * scale,
                               rtol=0)
    written = {(tables[b, n // KV_ROWS], n % KV_ROWS)
               for b, n in enumerate(lengths)}

    def check(ax, before, new, old):
        if ax < 0:
            return
        before, new, old = (np.asarray(x, np.float32)
                            for x in (before, new, old))
        for page in range(kv.n_pages):
            for r in range(KV_ROWS):
                at = (page,) + (slice(None),) * ax + (r,)
                if (page, r) in written:
                    np.testing.assert_allclose(
                        new[at], old[at], rtol=0,
                        atol=3e-2 * np.abs(old[at]).max())
                else:
                    np.testing.assert_array_equal(new[at], before[at])

    jax.tree.map(check, kv.axes, args[1], got[1], want[1])


def _all_eqns(closed):
    """Every equation of a closed jaxpr, those of its sub-jaxprs too."""
    out, stack = [], [closed.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for e in jaxpr.eqns:
            out.append(e)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        stack.append(getattr(sub.jaxpr, "jaxpr", sub.jaxpr))
                    elif hasattr(sub, "eqns"):
                        stack.append(sub)
    return out


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "kv_quant"])
def test_kv_quant_pool_decodes_through_views(kv_quant):
    """A pool of float K/V decodes through the paged-attention kernel; a
    pool of int8 K/V with scales keeps the gathered view and gives what
    the vmapped step gives over that view."""
    kv, vm, args = _step_case("minicpm-2b", kv_quant=kv_quant)
    step = kv.build_decode(vm, len(args[3]), args[2].shape[1])
    kernels = {str(e.params["name"])
               for e in _all_eqns(jax.make_jaxpr(step)(*args))
               if e.primitive.name == "pallas_call"}
    assert kv.inplace == (not kv_quant)
    assert kernels == (set() if kv_quant else {"paged_attention"})
    if kv_quant:
        view = kv._gather(*args[1:4])
        np.testing.assert_array_equal(np.asarray(step(*args)[0]),
                                      np.asarray(vm(args[0], view,
                                                    args[4])[0]))
