"""Paged KV-cache: a block-pool allocator with content-hash prefix reuse.

The runtime used to give every slot a contiguous ``max_len`` cache, so
device memory — not compute — capped concurrency.  This module replaces
slot caches with a **page pool**: KV state lives in fixed-size pages of
``page_size`` token rows, requests hold *page tables* (lists of page
ids), and the engine's existing gather/scatter becomes page-table
indexed.  This is exactly the source paper's pointer-interface case —
decode over scattered pages is a batch of contractions at
non-contiguous strided addresses, the situation the extended
StridedBatchedGEMM interface is designed to absorb — and the
page-count *bucket lattice* (Peise-style shape classes in
:mod:`repro.runtime.buckets`) keeps the paged compile set bounded.

Two halves, mirroring the scheduler/engine split:

* :class:`PagePool` — pure host-side bookkeeping: free list, per-page
  refcounts, and the **prefix index**: a chain hash per *full* prompt
  page (digest of all tokens up to and including that page), mapping to
  the resident page holding those rows.  A new prompt whose leading
  full pages hash-match maps them into its page table with a refcount
  bump — no prefill recompute; common system prompts prefill once and
  fork.  Shared pages are *full* pages: writes only ever happen at the
  growing tail, so a full page is immutable and sharing needs no
  copy-on-write fault path.  Eviction is page release — refcounts drop,
  pages return to the free list at zero (and leave the prefix index).
* :class:`PagedKV` — the device half: one pooled cache tree (every
  token leaf stored page-major, ``(n_pages, ..., page_size, row
  features)``) plus the jitted gather/commit/decode builders the engine
  caches per bucket lattice point.

**Page 0 is the null page.**  Page tables pad to the lattice width
with it; decode reading pages in place never copies it, and a gathered
view reads it only through positions the attention mask zeroes exactly,
so the padding is value-safe without per-request branches.

Correctness invariant (pinned by the differential tests): greedy
output is token-identical to the unpaged runtime.  The gathered view
is shorter than ``max_len`` but masked positions carry exactly-zero
probabilities, and a shared prefix page holds bit-identical KV to what
prefill would recompute (same tokens, same absolute positions, same
params).
"""

from __future__ import annotations

import hashlib
from collections import Counter

import numpy as np

from repro.obs import trace as _trace

__all__ = ["NULL_PAGE", "PagePool", "PagedKV", "PoolExhausted"]

#: reserved scratch page: pads page tables up to the lattice width.
NULL_PAGE = 0

#: most whole-page slices a view unrolls (batch rows x pages x pooled
#: leaves).  Compiling unrolled slices for v5e takes ~9 ms a slice
#: (mellum2-12b-a2.5b's 8 x 224 x 8 view: 128 s on a CPU host), one XLA
#: gather under a second; minicpm-2b's views unroll at most 2 x 112 x 2
#: = 448 slices and keep the form measured fastest for it (PERF.md).
MAX_UNROLLED_PAGES = 512


class PoolExhausted(RuntimeError):
    """No free page — the caller decides who to preempt."""


# =========================================================== host bookkeeping
class PagePool:
    """Free list + refcounts + prefix index for a pool of KV pages.

    ``n_pages`` counts the whole pool including the reserved null page,
    so ``usable == n_pages - 1``.  ``max_rows`` caps how many cache rows
    one request may ever hold (the engine passes ``max_len``).
    ``metrics``, when given, is a
    :class:`repro.runtime.metrics.ServingMetrics` that receives
    ``on_page_alloc`` / ``on_page_release`` / ``on_prefix_hit`` events.
    """

    def __init__(self, n_pages: int, page_size: int, *,
                 max_rows: int | None = None, prefix_sharing: bool = True,
                 metrics=None):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if n_pages < 2:
            raise ValueError(
                f"pool needs the null page plus at least one usable page, "
                f"got n_pages={n_pages}"
            )
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.max_rows = (int(max_rows) if max_rows is not None
                         else (self.n_pages - 1) * self.page_size)
        self.prefix_sharing = bool(prefix_sharing)
        self.metrics = metrics
        # LIFO free list (recently-released pages are cache-warm); the
        # null page is never in it
        self._free = list(range(self.n_pages - 1, NULL_PAGE, -1))
        self.refcount: dict[int, int] = {}     # allocated pages only
        self._hash_to_page: dict[str, int] = {}
        self._page_hash: dict[int, str] = {}
        # counters (also surfaced via stats())
        self.page_allocs = 0
        self.page_releases = 0
        self.prefix_hits = 0
        self.prefix_shared_pages = 0
        self.prefix_shared_tokens = 0
        self.admission_blocks = 0

    # ------------------------------------------------------------- geometry
    @property
    def usable(self) -> int:
        return self.n_pages - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    def pages_for(self, rows: int) -> int:
        """Pages needed to hold ``rows`` cache rows."""
        return -(-int(rows) // self.page_size)

    def required_pages(self, prompt_len: int) -> int:
        """Pages a request must hold at admission: the prompt plus the
        first decode row (capped at ``max_rows`` — a prompt of exactly
        ``max_rows`` is legal, the cache-length cap evicts before any
        out-of-range write)."""
        return self.pages_for(min(int(prompt_len) + 1, self.max_rows))

    # ------------------------------------------------------------ alloc/free
    def alloc(self, n: int, *, rid: int | None = None) -> list[int]:
        """Pop ``n`` fresh pages (refcount 1 each), or raise
        :class:`PoolExhausted` without allocating any."""
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} page(s), {len(self._free)} free "
                f"(pool of {self.usable})"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refcount[p] = 1
        self.page_allocs += n
        if self.metrics is not None:
            self.metrics.on_page_alloc(n)
        if n and _trace.enabled():
            _trace.instant("page_alloc", "pages", rid=rid, n=n,
                           free=len(self._free))
        return pages

    def release(self, pages: list[int], *, rid: int | None = None) -> int:
        """Drop one reference per page; pages hitting refcount zero
        return to the free list and leave the prefix index.  Returns the
        number of pages actually freed."""
        freed = 0
        for p in pages:
            rc = self.refcount[p] - 1
            if rc:
                self.refcount[p] = rc
                continue
            del self.refcount[p]
            h = self._page_hash.pop(p, None)
            if h is not None and self._hash_to_page.get(h) == p:
                del self._hash_to_page[h]
            self._free.append(p)
            freed += 1
        self.page_releases += freed
        if self.metrics is not None and freed:
            self.metrics.on_page_release(freed)
        if pages and _trace.enabled():
            _trace.instant("page_release", "pages", rid=rid, n=len(pages),
                           freed=freed, free=len(self._free))
        return freed

    # --------------------------------------------------------- prefix index
    def _chain_hashes(self, prompt) -> list[str]:
        """One digest per *full* prompt page; digest ``i`` covers every
        token up to and including page ``i`` (chain hashing), so a hash
        match implies the whole leading prefix matches."""
        arr = np.ascontiguousarray(np.asarray(prompt, np.int32))
        h = hashlib.sha256()
        out = []
        for i in range(len(arr) // self.page_size):
            h.update(arr[i * self.page_size:(i + 1) * self.page_size].tobytes())
            out.append(h.hexdigest())
        return out

    def match_prefix(self, hashes: list[str], prompt_len: int) -> list[int]:
        """Resident pages matching the prompt's leading full pages.

        Capped so at least one prompt token is left to prefill: the
        first token's logits come from the prefill of the un-shared
        remainder, so a fully-resident prompt still prefills its last
        page."""
        if not self.prefix_sharing:
            return []
        shareable = (int(prompt_len) - 1) // self.page_size
        pages = []
        for h in hashes[:shareable]:
            p = self._hash_to_page.get(h)
            if p is None:
                break
            pages.append(p)
        return pages

    def can_admit(self, prompt) -> bool:
        """Would :meth:`try_admit` succeed right now?  Pure inspection —
        nothing is allocated and no counter moves (``admit_now`` uses it
        to refuse before enqueueing)."""
        plen = len(prompt)
        hashes = self._chain_hashes(prompt) if self.prefix_sharing else []
        shared = self.match_prefix(hashes, plen)
        return self.required_pages(plen) - len(shared) <= self.n_free

    def try_admit(self, state) -> bool:
        """Reserve pages for a request at admission, sharing what it can.

        Maps hash-matching resident prefix pages into ``state.pages``
        (refcount bump, zero recompute), allocates private pages for the
        remainder of ``prompt_len + 1`` rows, and records the chain
        hashes for :meth:`register` at prefill commit.  Returns False —
        allocating nothing — when the pool lacks the private pages."""
        prompt = state.request.prompt
        plen = len(prompt)
        hashes = self._chain_hashes(prompt) if self.prefix_sharing else []
        shared = self.match_prefix(hashes, plen)
        need = self.required_pages(plen) - len(shared)
        if need > self.n_free:
            self.admission_blocks += 1
            return False
        for p in shared:
            self.refcount[p] += 1
        state.pages = shared + self.alloc(need, rid=state.rid)
        state.shared_tokens = len(shared) * self.page_size
        state.page_hashes = hashes
        if shared:
            self.prefix_hits += 1
            self.prefix_shared_pages += len(shared)
            self.prefix_shared_tokens += state.shared_tokens
            if self.metrics is not None:
                self.metrics.on_prefix_hit(len(shared), state.shared_tokens)
            if _trace.enabled():
                _trace.instant("page_share", "pages", rid=state.rid,
                               n=len(shared), tokens=state.shared_tokens)
        return True

    def register(self, state) -> None:
        """Publish a request's *full prompt pages* into the prefix index
        (called once, when its prefill commits).  Full prompt pages are
        immutable — decode writes land at row ``prompt_len`` and beyond
        — so later prompts may map them directly.

        A hash already indexed is *re-pointed* at the newer copy: two
        requests admitted in the same tick prefill the same prefix into
        private pages (neither could share — the index fills at commit,
        after both were admitted), and if the older copy kept the index
        entry, its release would empty the index while the newer copy
        sat resident and unfindable.  Latest-registrant-wins keeps the
        entry on the page most likely to outlive it; the release guard
        (`_hash_to_page.get(h) == p`) makes the de-indexed older copy's
        retirement a no-op on the index."""
        if not self.prefix_sharing:
            return
        n_full = len(state.request.prompt) // self.page_size
        for h, p in zip(state.page_hashes[:n_full], state.pages):
            old = self._hash_to_page.get(h)
            if old == p:
                continue
            self._hash_to_page[h] = p
            self._page_hash[p] = h
            if old is not None and self._page_hash.get(old) == h:
                del self._page_hash[old]

    # -------------------------------------------------------------- summary
    def stats(self) -> dict:
        """Flat counters for the metrics registry (``pages`` source)."""
        used = self.usable - self.n_free
        return {
            "page_size": self.page_size,
            "pages_total": self.usable,
            "pages_free": self.n_free,
            "pages_in_use": used,
            "pool_occupancy": used / self.usable if self.usable else 0.0,
            "page_allocs": self.page_allocs,
            "page_releases": self.page_releases,
            "prefix_hits": self.prefix_hits,
            "prefix_shared_pages": self.prefix_shared_pages,
            "prefix_shared_tokens": self.prefix_shared_tokens,
            "prefix_index_size": len(self._hash_to_page),
            "admission_blocks": self.admission_blocks,
        }


# ============================================================== device half
def _leaf_token_axis(a, b):
    """Token axis of one cache leaf, found by differencing the shapes of
    two ``init_cache`` widths; ``-1`` marks a length leaf (no token
    axis).  Raises for state that cannot be paged (SSM recurrent state
    has no token axis and is not a length scalar)."""
    diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
    if not diffs:
        import jax.numpy as jnp

        if jnp.issubdtype(a.dtype, jnp.integer) and a.ndim <= 1:
            return -1  # per-layer / top-level "length" scalar
        raise ValueError(
            f"cache leaf of shape {a.shape} has no token axis — "
            f"SSM/hybrid recurrent state cannot be paged"
        )
    if len(diffs) != 1:
        raise ValueError(f"ambiguous token axis for leaf {a.shape}/{b.shape}")
    return diffs[0]


class PagedKV:
    """The pooled device cache and its jitted gather/commit/decode ops.

    ``pool`` holds, for every token leaf of ``init_cache(cfg, 1,
    page_size)``, the pages of that leaf stacked page-major, with each
    row's features flattened into one trailing axis: a leaf of batch-1
    shape ``pre + (page_size,) + post`` is pooled as ``(n_pages,) + pre
    + (page_size, prod(post))``.  Each page is a ``page_size``-row slice
    of every layer's KV at once, so one page table describes a request's
    whole cache; a page is contiguous in memory, and the row's features
    (``G * D`` for K and V) are the minor, lane axis whatever the head
    size.  Length leaves are not pooled (``None``): the views take them
    from the request lengths.  The builders return jitted callables the
    engine caches in its :class:`~repro.runtime.buckets.BucketTable`
    keyed by the page-count lattice point ``P`` (views are ``P *
    page_size`` rows wide), keeping the compile set bounded.
    """

    def __init__(self, cfg, n_pages: int, page_size: int, dtype=None):
        import jax
        import jax.numpy as jnp

        from repro.models.transformer import init_cache

        self.cfg = cfg
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        one = init_cache(cfg, 1, page_size, dtype)
        two = init_cache(cfg, 1, 2 * page_size, dtype)
        #: token-axis per leaf (in batch-1 leaf coordinates), -1 = length
        self.axes = jax.tree.map(_leaf_token_axis, one, two)
        #: batch-1 leaf shapes and dtypes, the views' own layout
        self.shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), one)

        def pages(ax, x):
            if ax < 0:
                return None
            row = int(np.prod(x.shape[ax + 1:]))
            return jnp.zeros((self.n_pages,) + x.shape[:ax]
                             + (self.page_size, row), x.dtype)

        self.pool = jax.tree.map(pages, self.axes, one)
        #: whether decode reads the pool in place (float K/V); a pool of
        #: int8 K/V with scales is gathered into views
        self.inplace = all(jnp.issubdtype(x.dtype, jnp.floating)
                           for x in jax.tree.leaves(self.pool))
        #: attention layers by window (None: full)
        self.windows = Counter(s.window for s in cfg.layers
                               if s.mixer == "attn")

    def reads(self, lengths, bucket: int, P: int) -> tuple[int, int]:
        """(rows, pages) of cached K/V one decode launch reads for the
        requests with ``lengths`` rows cached, in a ``bucket`` x ``P``
        lattice point.  In place: the rows of the pages a full layer reads
        plus the new row, and the pages every attention layer reads, a
        window layer only those its window reaches (:func:`page_span`).
        Through views: every row and page of every view."""
        from repro.kernels.flash_attn import page_span

        ps = self.page_size
        if not self.inplace:
            return bucket * P * ps, bucket * P * sum(self.windows.values())
        lengths = np.asarray(lengths)
        rows = int(np.sum(-(-lengths // ps))) * ps + len(lengths)
        pages = 0
        for window, layers in self.windows.items():
            _, first, end = page_span(lengths, window, ps, np)
            pages += layers * int(np.sum(end - first))
        return rows, pages

    # -------------------------------------------------------------- weights
    def _gather(self, pool, tables, lengths):
        """Materialize per-request cache views from page tables.

        ``tables``: (B, P) page ids (null-padded); ``lengths``: (B,)
        cache lengths.  Returns a cache tree of batch views whose token
        axes are ``P * page_size`` wide; length leaves broadcast
        ``lengths``.

        Each table entry is one dynamic slice of a whole, contiguous
        page, unrolled: the TPU compiler's own gather (``leaf[tables]``)
        first cuts the pool into 384-lane slices, a pass over the whole
        pool, and a loop of page copies runs slower at wide page
        buckets.  The pages are stacked with the layer axis ahead of the
        batch axis, the order in which the model's scan over layers
        reads them, so the view needs no transpose of its own.  Past
        :data:`MAX_UNROLLED_PAGES` slices, whose compile would take
        minutes, one gather with the same output order takes their
        place."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        B, P = tables.shape
        ids = [list(row) for row in tables]
        leaves = sum(ax >= 0 for ax in jax.tree.leaves(self.axes))
        unroll = B * P * leaves <= MAX_UNROLLED_PAGES

        def g(ax, spec, leaf):
            if ax < 0:
                return jnp.broadcast_to(
                    lengths.reshape((B,) + (1,) * spec.ndim),
                    (B,) + spec.shape).astype(spec.dtype)
            if unroll:
                rows = [jnp.stack([lax.dynamic_index_in_dim(
                    leaf, i, keepdims=False, allow_negative_indices=False)
                    for i in row], axis=ax) for row in ids]  # pre+(P,ps,F)
                x = jnp.moveaxis(jnp.stack(rows, axis=1), 1, 0)
            else:   # the same pages, one gather
                x = jnp.moveaxis(leaf[tables], 1, 1 + ax)
            return x.reshape((B,) + spec.shape[:ax]
                             + (P * self.page_size,) + spec.shape[ax + 1:])

        return jax.tree.map(g, self.axes, self.shapes, pool)

    def build_view(self, P: int):
        """Jitted batch-1 view builder (prefill staging): maps a
        request's pages (+ shared-prefix length) into a dense cache of
        ``P * page_size`` rows, squeezed to the batch-1 tree
        ``prefill`` expects."""
        import jax

        def serve_page_view(pool, table, length):
            view = self._gather(pool, table, length)
            return jax.tree.map(lambda x: x[0], view)

        return jax.jit(serve_page_view)

    def build_commit(self, P: int):
        """Jitted prefill commit: re-cut a staged batch-1 cache of
        ``P * page_size`` rows into ``P`` pages and scatter them whole
        into the pool at ``pages`` (null-padded to P).  Writing a shared
        page is bit-idempotent — the staged rows were gathered from
        it."""
        import jax
        import jax.numpy as jnp

        def serve_page_commit(pool, stage, pages):
            def c(ax, pool_leaf, stage_leaf):
                if ax < 0:
                    return pool_leaf          # lengths live host-side
                pre = stage_leaf.shape[:ax]
                x = stage_leaf.reshape(pre + (P,) + pool_leaf.shape[-2:])
                x = jnp.moveaxis(x, ax, 0)    # (P,) + pre + (ps, F)
                return pool_leaf.at[pages].set(x.astype(pool_leaf.dtype))

            return jax.tree.map(c, self.axes, pool, stage)

        return jax.jit(serve_page_commit)

    def build_decode(self, decode_vmapped, bucket: int, P: int):
        """Jitted paged decode for one ``(slot-bucket, page-bucket)``
        lattice point: run the step, write the single new row of every
        leaf into its page, in place, one ``dynamic_update_slice`` per
        batch row.  Returns ``(logits, new_pool)`` and whatever the step
        returns after its cache (a sparse model's routing counts).

        A pool of float K/V is read in place:
        :func:`~repro.models.transformer.decode_step_paged` runs the
        batch, each attention layer reading every request's pages through
        its table (:func:`~repro.kernels.flash_attn.paged_attention`), and
        returns only the new rows.  A pool of int8 K/V with scales
        (``kv_quant``) is gathered into views of ``P * page_size`` rows
        that ``decode_vmapped`` steps.

        The engine pads the batch to ``bucket`` by duplicating an
        active request's (table, length, token) row — duplicates
        compute identical updates, so the row writes are
        value-deterministic (same rule as the unpaged slot scatter).

        The gather (views only), the model step and the row writes run
        under the name scopes ``kv_view``, ``decode_model`` and
        ``kv_write``, which reach each op's ``op_name`` metadata and change
        nothing else in the compiled program.  So JAX's persistent
        compilation cache, whose key leaves metadata out unless
        ``jax_compilation_cache_include_metadata_in_key`` is set, may
        load an executable carrying the metadata of an older compile."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from repro.models.transformer import decode_step_paged

        ps = self.page_size
        inplace = self.inplace
        moe = any(s.ff == "moe" for s in self.cfg.layers)

        def step(params, pool, tables, lengths, toks):
            """(logits (B, 1, V), each leaf's new rows, stats, and where
            request b's row sits on a leaf's token axis)."""
            if not inplace:
                with jax.named_scope("kv_view"):
                    view = self._gather(pool, tables, lengths)
                with jax.named_scope("decode_model"):
                    return (*decode_vmapped(params, view, toks), lengths)
            with jax.named_scope("decode_model"):
                logits, rows, *aux = decode_step_paged(
                    self.cfg, params, pool, tables, lengths, toks[:, 0],
                    stats=moe)
            # the routing counts as the vmapped step lays them out
            aux = [{k: jnp.moveaxis(h, 1, 0)[:, :, None]
                    for k, h in a.items()} for a in aux]
            # rows: the batch on the leaf's own batch axis, one token
            rows = jax.tree.map(
                lambda ax, x: x if ax < 0 else jnp.expand_dims(
                    jnp.moveaxis(x, ax - 1, 0), ax),
                self.axes, rows)
            return (logits[:, None], rows, *aux, jnp.zeros_like(lengths))

        def serve_decode(params, pool, tables, lengths, toks):
            logits, new_view, *stats, at = step(params, pool, tables,
                                                lengths, toks)

            def s(ax, pool_leaf, new_leaf):
                if ax < 0:
                    return pool_leaf
                pre, post = new_leaf.shape[1:1 + ax], new_leaf.shape[2 + ax:]
                for b in range(new_leaf.shape[0]):
                    n = lengths[b]
                    row = lax.dynamic_slice(
                        new_leaf,
                        (b,) + (0,) * ax + (at[b],) + (0,) * len(post),
                        (1,) + pre + (1,) + post)
                    pool_leaf = lax.dynamic_update_slice(
                        pool_leaf,
                        row.reshape((1,) + pre + (1, -1)).astype(
                            pool_leaf.dtype),
                        (tables[b, n // ps],) + (0,) * ax + (n % ps, 0))
                return pool_leaf

            with jax.named_scope("kv_write"):
                new_pool = jax.tree.map(s, self.axes, pool, new_view)
            return (logits, new_pool, *stats)

        return jax.jit(serve_decode)
