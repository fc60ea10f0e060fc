"""The continuous-batching serving runtime.

:class:`ServingRuntime` drives the tick loop over the three layers this
package separates:

* the :class:`~repro.runtime.scheduler.Scheduler` decides *what* runs —
  admissions, one prefill chunk per prefilling request, the decode
  batch;
* the :class:`~repro.runtime.buckets.BucketLattice` decides *at which
  shape* it runs — active-slot counts snap up to a power-of-two decode
  bucket, prompts decompose into exact power-of-two chunks — and the
  :class:`~repro.runtime.buckets.BucketTable` guarantees each lattice
  point compiles once (every ``xeinsum`` inside the traced step lands in
  the process program cache via
  :func:`repro.core.program.compile_program`);
* the kernels execute: decode gathers the bucket's slots out of the
  stacked cache, runs the vmapped step, and scatters back (bucket ==
  slot count skips the gather entirely — the legacy step-locked graph,
  bit-identical to the old ``ServeEngine``).

``paged=True`` swaps the per-slot contiguous caches for the block-pool
allocator in :mod:`repro.runtime.pages`: KV lives in fixed-size pages,
requests hold page tables, admission is keyed on free *pages* (a
:class:`~repro.runtime.pages.PagePool` attached to the scheduler), and
prompts whose leading chunks hash-match a resident prefix map those
pages by refcount instead of recomputing them.  The decode/prefill
graphs become page-table-indexed gather/scatter over one pooled cache
tree, bucketed on a (slot-count × page-count) lattice.

Correctness invariants the tests pin:

* **greedy token identity** — chunked prefill slices the prompt exactly
  (never pads), threads absolute positions, and cached attention always
  contracts against the full cache width with exact-zero masked
  probabilities, so every request's token stream is bit-identical to
  the legacy engine's whatever the batch composition;
* **value-deterministic scatter** — a decode bucket pads its index
  vector by duplicating an active slot; duplicates compute identical
  updates, so the scatter cannot race on conflicting values;
* **bounded compile set** — after warm-up every live shape is a bucket
  hit (``BucketTable.compiles`` frozen), which
  ``benchmarks/fig14_runtime.py`` asserts as *zero recompiles* on a
  ragged Poisson trace.

Chunked prefill is auto-disabled for SSM/hybrid and frontend
architectures: the recurrent decode path folds a multi-token chunk into
its last token, so only whole-prompt prefill matches the legacy oracle
there.
"""

from __future__ import annotations

import contextlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import decode_step, init_cache, prefill
from repro.obs import trace as _trace
from repro.runtime.buckets import BucketLattice, BucketTable, tuning_key_component
from repro.runtime.metrics import ServingMetrics
from repro.runtime.pages import NULL_PAGE, PagePool, PagedKV, PoolExhausted
from repro.runtime.scheduler import (
    EVICTED, PREFILL, REJECTED, UNFINISHED, Request, RequestState, Scheduler,
)

__all__ = ["ServingRuntime", "supports_chunked_prefill"]


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill is exact only for pure-attention decoder stacks.

    SSM/hybrid blocks run their cached prefill through the recurrent
    decode step, which folds a multi-token chunk into its last token;
    frontend models prepend non-token features.  Both must prefill the
    whole prompt in one shot to match the legacy engine.
    """
    specs = tuple(cfg.prefix) + tuple(cfg.pattern)
    return cfg.frontend is None and all(s.mixer == "attn" for s in specs)


class ServingRuntime:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 1024, greedy: bool = True,
                 prefill_chunk: int = 64, chunked_prefill: bool | None = None,
                 bucketed_decode: bool = True,
                 paged: bool = False, page_size: int = 16,
                 pages: int | None = None, prefix_sharing: bool = True,
                 pretune: bool = False, tuner=None, tuning_cache=None,
                 tune_policy: str | None = None,
                 pretune_prompt_lens: tuple[int, ...] = (8, 16, 32),
                 precompile: bool = True,
                 mesh=None, sharding_rules=None, clock=None):
        """``chunked_prefill=None`` auto-detects
        (:func:`supports_chunked_prefill`); ``bucketed_decode=False`` +
        ``chunked_prefill=False`` is the legacy step-locked engine.

        ``paged=True`` serves off a page pool of ``pages`` pages of
        ``page_size`` token rows each (default: the null page plus
        enough pages to match the unpaged runtime's ``slots × max_len``
        rows).  Memory then caps concurrency by *pages held*, not slots:
        ``slots`` may exceed what contiguous caches could hold, and
        ``prefix_sharing`` maps hash-matching resident prompt prefixes
        instead of recomputing them.  Requires a pure-attention stack
        (the pool pages the token axis; SSM state has none) and is
        single-device for now.

        ``mesh`` (a ``jax.sharding.Mesh``) serves *sharded*: params and
        the slot-stacked decode cache are partitioned by the model zoo's
        logical-axis rules (size-aware — nondivisible axes fall back to
        replicated) and every prefill/decode step runs under the mesh +
        rules context.  ``sharding_rules`` overrides the defaults.
        """
        if cfg.encoder_only:
            raise ValueError(f"{cfg.arch_id} is encoder-only; nothing to serve")
        self.cfg, self.params = cfg, params
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        self.mesh = mesh
        self._rules = None
        if chunked_prefill is None:
            chunked_prefill = supports_chunked_prefill(cfg)
        elif chunked_prefill and not supports_chunked_prefill(cfg):
            raise ValueError(
                f"{cfg.arch_id} has SSM/frontend layers: chunked prefill "
                f"would not match whole-prompt prefill (pass "
                f"chunked_prefill=False)"
            )
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.pool: PagePool | None = None
        self.kv: PagedKV | None = None
        max_pages = None
        if self.paged:
            if mesh is not None:
                raise NotImplementedError(
                    "paged KV-cache does not serve sharded yet"
                )
            if not supports_chunked_prefill(cfg):
                raise ValueError(
                    f"{cfg.arch_id} has SSM/frontend layers: recurrent "
                    f"state has no token axis and cannot be paged"
                )
            max_pages = -(-max_len // self.page_size)  # pages per request
            if pages is None:
                # null page + the unpaged runtime's slots*max_len rows
                pages = slots * max_pages + 1
        self.lattice = BucketLattice(
            slots, max_chunk=prefill_chunk, chunked=chunked_prefill,
            bucketed_decode=bucketed_decode, max_pages=max_pages,
        )
        self.buckets = BucketTable()
        self.metrics = ServingMetrics(slots, **({"clock": clock} if clock else {}))
        #: optional callable fed each decode step's logits (the numerics
        #: probe installs here — see repro.obs.health.NumericsProbe);
        #: ``None`` keeps the decode path at a single branch.
        self.logits_probe = None
        if self.paged:
            self.pool = PagePool(
                pages, self.page_size, max_rows=max_len,
                prefix_sharing=prefix_sharing, metrics=self.metrics,
            )
            self.kv = PagedKV(cfg, pages, self.page_size)
        self.scheduler = Scheduler(slots, self.lattice, pool=self.pool)

        if mesh is not None:
            from repro.distributed.sharding import ShardingRules
            from repro.launch.shardings import param_logical_axes, tree_shardings

            self._rules = sharding_rules or ShardingRules(mesh)
            p_spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
            )
            p_sh = tree_shardings(self._rules, param_logical_axes(p_spec), p_spec)
            # a no-op for params already made in these shardings
            # (repro.launch.serve.init_serving_params)
            self.params = jax.device_put(params, p_sh)
        if self.paged:
            self.cache = None        # KV lives in self.kv.pool
        else:
            self.cache = self._slot_cache()
        self._tokens = np.zeros((slots, 1, 1), np.int32)
        self._decode_vmapped = jax.vmap(
            lambda p, c, t: decode_step(cfg, p, c, t), in_axes=(None, 0, 0)
        )
        self.tuner = tuner
        self.pretune_stats: dict | None = None
        self.program_stats: dict | None = None
        # pretune BEFORE precompile: warming the tuning cache bumps its
        # fingerprint, which would invalidate every tuned program (and
        # every bucket key) precompile just built
        if pretune:
            self.pretune_stats = self.warmup_tuning(
                tuner=tuner, tuning_cache=tuning_cache,
                tune_policy=tune_policy,
                prompt_lens=pretune_prompt_lens,
            )
        if precompile:
            self.program_stats = self.precompile_programs(
                prompt_lens=pretune_prompt_lens
            )
        # the warm-up's dispatcher traffic is bookkept under
        # pretune_stats; the serve phase then starts its hit/miss/
        # measurement counters from a deterministic zero
        if pretune and self.tuner is not None:
            self.pretune_stats["dispatcher"] = dict(self.tuner.stats)
            if hasattr(self.tuner, "reset_counters"):
                self.tuner.reset_counters()

    # --------------------------------------------------------------- helpers
    def _slot_cache(self):
        """The slot-stacked cache: every leaf gains a leading (slots,)
        axis, so each slot keeps an independent length/KV state.  Made
        under ``jit`` directly in its sharding on a mesh."""
        cfg, slots, max_len = self.cfg, self.slots, self.max_len

        def make():
            one = init_cache(cfg, 1, max_len)
            return jax.tree.map(
                lambda x: jnp.zeros((slots,) + x.shape, x.dtype), one)

        shardings = None
        if self.mesh is not None:
            from repro.launch.shardings import cache_logical_axes, tree_shardings

            c_spec = jax.eval_shape(make)
            shardings = tree_shardings(
                self._rules, cache_logical_axes(c_spec), c_spec)
        return jax.jit(make, out_shardings=shardings)()

    @contextlib.contextmanager
    def _mesh_ctx(self):
        """Mesh + logical-sharding-rules context for model steps (no-op
        single-device)."""
        if self.mesh is None:
            yield
            return
        from repro.distributed.sharding import use_rules

        with self.mesh, use_rules(self._rules):
            yield

    def _fingerprint(self):
        return tuning_key_component(self.cfg.contract_strategy)

    # ----------------------------------------------------------- autotuning
    def _trace_working_set(self, recorder, prompt_lens) -> list:
        """Abstractly trace every decode bucket + every prefill length
        under ``recorder`` (``record_contractions`` / ``record_programs``)
        and return the recording.

        ``jax.eval_shape`` runs no FLOPs, so this is cheap even for large
        models.  The traces go through fresh lambda wrappers: eval_shape
        caches jaxprs by function identity, and a cached trace would
        bypass the model code the recorder needs to observe.
        """
        one = init_cache(self.cfg, 1, self.max_len)
        decode = lambda p, c, t: self._decode_vmapped(p, c, t)  # noqa: E731
        prefill_ = lambda p, t, c: prefill(  # noqa: E731
            self.cfg, p, {"tokens": t}, c
        )
        with self._mesh_ctx(), recorder() as rec:
            for b in self.lattice.slot_buckets:
                step = jnp.zeros((b, 1, 1), jnp.int32)
                if self.paged:
                    # paged decode runs on gathered views of every
                    # page-lattice width, not on max_len slot rows
                    for P in self.lattice.page_buckets:
                        view = init_cache(self.cfg, 1, P * self.page_size)
                        sub = jax.tree.map(
                            lambda x: jax.ShapeDtypeStruct(
                                (b,) + x.shape, x.dtype),
                            view,
                        )
                        jax.eval_shape(decode, self.params, sub, step)
                else:
                    sub = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            (b,) + x.shape[1:], x.dtype),
                        self.cache,
                    )
                    jax.eval_shape(decode, self.params, sub, step)
            for plen in dict.fromkeys(min(p, self.max_len) for p in prompt_lens):
                toks = jnp.zeros((1, plen), jnp.int32)
                jax.eval_shape(prefill_, self.params, toks, one)
        return rec

    def _prefill_lens(self, prompt_lens) -> tuple[int, ...]:
        """The prefill lengths worth pre-tracing: the chunk lattice when
        chunking is on (the steady-state compile set), the caller's
        prompt-length buckets otherwise."""
        if self.lattice.chunked:
            return self.lattice.chunk_buckets
        return tuple(prompt_lens)

    def contraction_working_set(
        self, prompt_lens: tuple[int, ...] = (8, 16, 32)
    ) -> list[tuple]:
        """The ``(spec, dims, dtype)`` set of every decode bucket + every
        steady-state prefill length (see :meth:`_trace_working_set`)."""
        from repro.core.contract import record_contractions

        return self._trace_working_set(
            record_contractions, self._prefill_lens(prompt_lens)
        )

    def precompile_programs(
        self, prompt_lens: tuple[int, ...] = (8, 16, 32)
    ) -> dict:
        """Compile the contraction-program working set up front.

        Traces every decode bucket and every steady-state prefill length
        abstractly (``jax.eval_shape`` — no FLOPs run) under
        :func:`repro.core.program.record_programs`, so every ``xeinsum``
        the forward passes issue lands in the process program cache:
        parsed, path-planned, pass-pipelined and lowered exactly once.
        Returns ``{"programs": unique, "calls": recorded, "steps": total}``.
        """
        from repro.core.program import record_programs

        rec = self._trace_working_set(
            record_programs, self._prefill_lens(prompt_lens)
        )
        unique = {p.signature for p in rec}
        return {
            "programs": len(unique),
            "calls": len(rec),
            "steps": sum(len(p.program.steps) for p in rec),
        }

    def precompile_buckets(self) -> int:
        """Create every bucket-table entry on the lattice up front.

        Entries hold lazily-jitted callables (tracing happens on first
        call), so this is cheap; what it pins is the *compile set*: after
        it runs, ``BucketTable.compiles`` is frozen at the lattice size
        and every serve-time lookup is a hit — the deterministic
        zero-recompile steady state the multi-tenant benchmark asserts.
        Returns the entry count."""
        fp = self._fingerprint()
        bk, bg = self.buckets.key, self.buckets.get
        chunks = self.lattice.chunk_buckets if self.lattice.chunked else ()
        if self.paged:
            for P in self.lattice.page_buckets:
                bg(bk("page_view", P, fp), lambda P=P: self.kv.build_view(P))
                bg(bk("page_commit", P, fp),
                   lambda P=P: self.kv.build_commit(P))
                for c in chunks:
                    bg(bk("prefill", (c, P), fp), self._build_prefill)
                for b in self.lattice.slot_buckets:
                    bg(bk("decode", (b, P), fp),
                       lambda b=b, P=P: self.kv.build_decode(
                           self._decode_vmapped, b, P))
        else:
            for b in self.lattice.slot_buckets:
                bg(bk("decode", b, fp), lambda b=b: self._build_decode(b))
            for c in chunks:
                bg(bk("prefill", c, fp), self._build_prefill)
        return self.buckets.compiles

    def warmup_tuning(self, *, tuner=None, tuning_cache=None,
                      tune_policy: str | None = None,
                      prompt_lens: tuple[int, ...] = (8, 16, 32)) -> dict:
        """Pre-tune the runtime's contraction working set before serving.

        Measures (and persists, when the dispatcher's cache has a path)
        the fastest execution mode for every distinct contraction the
        model issues at serving shapes.  With ``tune_policy="predict"``
        the warm-up is *predict-first*: keys the cost model (fitted on
        the cache — e.g. one imported from the fleet, see
        :mod:`repro.tuning.federate`) is confident about skip their
        measurement sweep entirely, so warm-up wall-clock drops by the
        predictor's coverage.  Returns the pretune stats dict; the
        dispatcher is kept on ``self.tuner``.
        """
        if tuner is None:
            from repro.tuning.dispatch import Dispatcher, get_dispatcher

            tuner = (
                Dispatcher(tuning_cache) if tuning_cache is not None
                else get_dispatcher()
            )
        if tune_policy is not None:
            tuner.policy = tune_policy
        self.tuner = tuner
        return tuner.pretune(self.contraction_working_set(prompt_lens))

    # --------------------------------------------------------- bucket builds
    def _build_decode(self, bucket: int):
        """The jitted decode executable for one slot-count bucket.

        ``bucket == slots`` runs on the stacked cache directly (the
        legacy graph — no gather, logits row == slot id).  Smaller
        buckets gather the indexed slots, decode, and scatter back;
        logits rows align with the index vector.
        """
        vm = self._decode_vmapped
        if bucket == self.slots:
            def fn(p, cache, toks, idx):
                del idx  # full batch: row == slot id
                return vm(p, cache, toks)
        else:
            def fn(p, cache, toks, idx):
                sub = jax.tree.map(lambda x: x[idx], cache)
                logits, new_sub = vm(p, sub, toks[idx])
                cache = jax.tree.map(
                    lambda full, ns: full.at[idx].set(ns), cache, new_sub
                )
                return logits, cache
        return jax.jit(fn)

    def _build_prefill(self):
        cfg = self.cfg

        def fn(p, toks, c):
            return prefill(cfg, p, {"tokens": toks}, c)

        return jax.jit(fn)

    # ------------------------------------------------------------ lifecycle
    def _reject_reason(self, request: Request) -> str | None:
        """Why a request could *never* be served, or ``None``.

        One rule, two callers: :meth:`submit` raises on it (programming
        error at the API), :meth:`serve` marks the offender ``rejected``
        and serves the rest of the batch (operational input)."""
        plen = len(request.prompt)
        if plen > self.max_len:
            return (
                f"prompt of {plen} tokens exceeds max_len={self.max_len} "
                f"(the KV cache cannot hold it)"
            )
        if self.pool is not None:
            need = self.pool.required_pages(plen)
            if need > self.pool.usable:
                return (
                    f"prompt needs {need} page(s) but the pool holds "
                    f"only {self.pool.usable}"
                )
        return None

    def submit(self, request: Request) -> RequestState:
        """Queue a request (admitted when a slot frees up).

        Prompts longer than ``max_len`` are rejected here: the prefill
        writes one cache row per prompt token, and an over-long prompt
        would have its writes clamped by ``dynamic_update_slice`` —
        silently overwriting earlier KV rows and emitting a first token
        from corrupted state.  (A prompt of exactly ``max_len`` is fine:
        the first token comes from the prefill logits, and the decode
        cache-length cap evicts before any out-of-range write.)  The
        paged runtime also rejects prompts whose page table could never
        fit the pool."""
        reason = self._reject_reason(request)
        if reason is not None:
            raise ValueError(f"request {request.rid}: {reason}")
        state = self.scheduler.submit(request)
        self.metrics.on_submit(request.rid)
        if _trace.enabled():
            _trace.instant("submit", "runtime", rid=request.rid,
                           prompt_len=len(request.prompt),
                           max_new=request.max_new_tokens)
        return state

    def evict(self, rid: int) -> Request:
        """Reclaim a live request's slot; the request is marked
        ``"evicted"`` (``done`` stays False) and its slot is reusable
        immediately."""
        state = self.scheduler.evict(rid)
        self.metrics.on_evict(rid)
        if _trace.enabled():
            _trace.instant("evict", "runtime", rid=rid, reason="explicit")
        return state.request

    # ------------------------------------------------------------ metrics
    def register_metrics(self, registry=None):
        """Wire this runtime's counters into a
        :class:`repro.obs.registry.MetricsRegistry` (default: the
        process-wide one) under the conventional source names:
        ``serving`` (request/token/latency metrics), ``buckets``
        (compile-once table), ``programs`` (process program cache),
        ``pages`` (page-pool occupancy, paged runtime only) and — when a
        tuner is attached — ``dispatcher``.  Returns the registry.

        Explicit, not automatic: constructing a runtime must not mutate
        process-global state behind a test's back."""
        from repro.core.program import program_cache_stats
        from repro.obs.registry import get_registry

        reg = registry if registry is not None else get_registry()
        reg.register("serving", self.metrics.snapshot)
        reg.register("buckets", self.buckets.stats)
        reg.register("programs", program_cache_stats)
        if self.pool is not None:
            reg.register("pages", self.pool.stats)
        if self.tuner is not None:
            reg.register("dispatcher", lambda: self.tuner.stats)
        return reg

    # ------------------------------------------------------------- execution
    def _sample(self, state: RequestState, logits_row) -> int:
        """One token off a (V,) logits row — argmax or the request's own
        PRNG stream (the legacy engine sampled only the first token and
        silently argmaxed every decode step)."""
        if self.greedy:
            return int(jnp.argmax(logits_row))
        return int(jax.random.categorical(state.next_key(), logits_row))

    def _run_prefill_chunk(self, state: RequestState, chunk: int) -> None:
        with _trace.span("prefill_chunk", "runtime") as sp:
            if sp:
                sp.set(rid=state.rid, chunk=chunk, pos=state.pos,
                       slot=state.slot)
            self._run_prefill_chunk_impl(state, chunk)

    def _run_prefill_chunk_impl(self, state: RequestState, chunk: int) -> None:
        if state.cache is None:
            if self.paged:
                # gather the request's pages into a dense staging cache;
                # a shared prefix arrives pre-filled and prefill resumes
                # after it (state.pos started at shared_tokens)
                state.cache = self._page_stage(state)
            else:
                state.cache = init_cache(self.cfg, 1, self.max_len)
        toks = jnp.asarray(
            np.asarray(state.request.prompt[state.pos:state.pos + chunk],
                       np.int32)[None]
        )
        # paged staging caches come in page-lattice widths, so the
        # compiled prefill is keyed on (chunk, width) lattice points
        size = (chunk, self._page_width(state)) if self.paged else chunk
        key = self.buckets.key("prefill", size, self._fingerprint())
        fn = self.buckets.get(key, self._build_prefill)
        with self._mesh_ctx():
            logits, state.cache = fn(self.params, toks, state.cache)
        state.pos += chunk
        self.metrics.on_prefill_chunk(chunk)
        if state.remaining_prompt == 0:
            first = self._sample(state, logits[0])
            state.request.output.append(first)
            self._tokens[state.slot, 0, 0] = first
            if self.paged:
                self._page_commit(state)
            else:
                with self._mesh_ctx():
                    self.cache = _write_slot(
                        self.cache, state.cache, state.slot
                    )
            self.scheduler.prefill_done(state)
            self.metrics.on_first_token(state.rid)
            if _trace.enabled():
                _trace.instant("first_token", "runtime", rid=state.rid)
            self._maybe_finish(state)

    # ------------------------------------------------------- paged plumbing
    def _page_width(self, state: RequestState) -> int:
        """The page-lattice point covering ``state``'s page table."""
        return self.lattice.page_bucket(len(state.pages))

    def _page_table(self, state: RequestState, P: int) -> np.ndarray:
        """``state``'s page table padded to lattice width ``P`` with the
        null page (whose rows only flow through exactly-zero masked
        attention probabilities)."""
        t = np.full((P,), NULL_PAGE, np.int32)
        t[:len(state.pages)] = state.pages
        return t

    def _page_stage(self, state: RequestState):
        """Batch-1 prefill staging cache: the request's pages gathered
        dense (``P * page_size`` rows), cache length = shared prefix."""
        P = self._page_width(state)
        key = self.buckets.key("page_view", P, self._fingerprint())
        fn = self.buckets.get(key, lambda: self.kv.build_view(P))
        table = jnp.asarray(self._page_table(state, P)[None])
        length = jnp.full((1,), state.shared_tokens, jnp.int32)
        return fn(self.kv.pool, table, length)

    def _page_commit(self, state: RequestState) -> None:
        """Scatter a finished prefill's staging cache back into its
        pages and publish the full prompt pages to the prefix index.
        Re-writing a shared page is bit-idempotent: its staged rows were
        gathered from that very page and prefill never touched them."""
        P = self._page_width(state)
        key = self.buckets.key("page_commit", P, self._fingerprint())
        fn = self.buckets.get(key, lambda: self.kv.build_commit(P))
        pages = jnp.asarray(self._page_table(state, P))
        self.kv.pool = fn(self.kv.pool, state.cache, pages)
        self.pool.register(state)

    def _ensure_decode_capacity(self, decodes: list[RequestState]) -> None:
        """Grow page tables for this decode step, preempting on pressure.

        The step for request ``s`` writes cache row ``prompt_len +
        n_generated - 1``, so its table must cover ``prompt_len +
        n_generated`` rows.  When the pool is dry the *youngest* other
        decoding request (highest rid) is evicted — marked, its pages
        released — and the allocation retried; a request alone in the
        batch evicts itself."""
        for state in list(decodes):
            if state not in decodes:
                continue         # already preempted as a victim below
            need = self.pool.pages_for(state.prompt_len + state.n_generated)
            while len(state.pages) < need:
                try:
                    state.pages += self.pool.alloc(
                        need - len(state.pages), rid=state.rid
                    )
                except PoolExhausted:
                    others = [s for s in decodes if s is not state]
                    victim = (max(others, key=lambda s: s.rid) if others
                              else state)
                    self.scheduler.finish(victim, EVICTED)
                    self.metrics.on_evict(victim.rid)
                    if _trace.enabled():
                        _trace.instant("evict", "runtime", rid=victim.rid,
                                       reason="pool_exhausted")
                    decodes.remove(victim)
                    if victim is state:
                        break

    def _maybe_finish(self, state: RequestState) -> None:
        if state.n_generated >= state.request.max_new_tokens:
            self.scheduler.finish(state)
            self.metrics.on_finish(state.rid)
            if _trace.enabled():
                _trace.instant("finish", "runtime", rid=state.rid,
                               n_generated=state.n_generated)

    def _run_decode(self, decodes: list[RequestState]) -> None:
        # cache-length cap: a slot whose next token would fall off the
        # cache is evicted (marked, not silently corrupted)
        for state in list(decodes):
            if state.prompt_len + state.n_generated - 1 >= self.max_len:
                self.scheduler.finish(state, EVICTED)
                self.metrics.on_evict(state.rid)
                if _trace.enabled():
                    _trace.instant("evict", "runtime", rid=state.rid,
                                   reason="cache_cap")
                decodes.remove(state)
        if not decodes:
            return
        with _trace.span("decode_batch", "runtime") as sp:
            if sp:
                sp.set(n_active=len(decodes),
                       bucket=self.lattice.decode_bucket(len(decodes)),
                       rids=[s.rid for s in decodes])
            self._run_decode_impl(decodes)

    def _run_decode_impl(self, decodes: list[RequestState]) -> None:
        if self.paged:
            self._ensure_decode_capacity(decodes)
            if decodes:
                self._run_decode_paged(decodes)
            return
        n = len(decodes)
        bucket = self.lattice.decode_bucket(n)
        key = self.buckets.key("decode", bucket, self._fingerprint())
        fn = self.buckets.get(key, lambda: self._build_decode(bucket))
        if bucket == self.slots:
            idx = np.arange(self.slots)
            rows = [s.slot for s in decodes]
        else:
            slot_ids = [s.slot for s in decodes]
            # pad with a duplicate of an active slot: duplicates compute
            # identical updates, so the scatter is value-deterministic
            idx = np.asarray(slot_ids + [slot_ids[0]] * (bucket - n))
            rows = list(range(n))
        with self._mesh_ctx():
            logits, self.cache = fn(
                self.params, self.cache, jnp.asarray(self._tokens),
                jnp.asarray(idx),
            )
        if self.logits_probe is not None:
            self.logits_probe(logits)
        self.metrics.on_decode(n, bucket)
        if self.greedy:
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            toks = [int(nxt[r]) for r in rows]
        else:
            toks = [self._sample(s, logits[r, 0])
                    for s, r in zip(decodes, rows)]
        for state, tok in zip(decodes, toks):
            state.request.output.append(tok)
            self._tokens[state.slot, 0, 0] = tok
            self.metrics.on_token()
            self._maybe_finish(state)

    def _run_decode_paged(self, decodes: list[RequestState]) -> None:
        """One decode step over page tables: gather each request's pages
        into a view, step, scatter the one written KV row back.  The
        executable is keyed on the (slot-bucket, page-bucket) lattice
        point; the batch pads with full duplicates of request 0's
        (table, length, token) row, so the padded rows compute — and
        scatter — identical values."""
        n = len(decodes)
        bucket = self.lattice.decode_bucket(n)
        P = self.lattice.page_bucket(max(len(s.pages) for s in decodes))
        key = self.buckets.key("decode", (bucket, P), self._fingerprint())
        fn = self.buckets.get(
            key,
            lambda: self.kv.build_decode(self._decode_vmapped, bucket, P),
        )
        tables = np.stack([self._page_table(s, P) for s in decodes])
        lengths = np.asarray(
            [s.prompt_len + s.n_generated - 1 for s in decodes], np.int32
        )
        toks = self._tokens[[s.slot for s in decodes]]
        if bucket > n:
            pad = bucket - n
            tables = np.concatenate([tables, np.repeat(tables[:1], pad, 0)])
            lengths = np.concatenate([lengths, np.repeat(lengths[:1], pad)])
            toks = np.concatenate([toks, np.repeat(toks[:1], pad, 0)])
        logits, self.kv.pool = fn(
            self.params, self.kv.pool, jnp.asarray(tables),
            jnp.asarray(lengths), jnp.asarray(toks),
        )
        if self.logits_probe is not None:
            self.logits_probe(logits)
        self.metrics.on_decode(n, bucket)
        if self.greedy:
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            out = [int(nxt[r]) for r in range(n)]
        else:
            out = [self._sample(s, logits[r, 0])
                   for r, s in enumerate(decodes)]
        for state, tok in zip(decodes, out):
            state.request.output.append(tok)
            self._tokens[state.slot, 0, 0] = tok
            self.metrics.on_token()
            self._maybe_finish(state)

    def tick(self) -> None:
        """One scheduler round: admissions → prefill chunks → decode.

        The decode batch is collected *after* the prefills ran: a
        request whose prompt completes this tick takes its first decode
        step this tick (matching the legacy admit-then-step order).
        This is load-bearing for correctness, not just latency — the
        full-slot decode launch updates every slot's cache row, so a
        just-prefilled slot left out of the batch would have its cache
        advanced by a *discarded* decode and its first token would be
        fed again next tick."""
        with _trace.span("tick", "runtime") as sp:
            plan = self.scheduler.schedule()
            engaged = {s.rid for s, _ in plan.prefills}
            for state, chunk in plan.prefills:
                self._run_prefill_chunk(state, chunk)
            batch = self.scheduler.decode_batch()
            self._run_decode(batch)
            # occupancy counts slots that did work this tick: _run_decode
            # drops cap-evicted states from `batch` in place (they launched
            # nothing), and the count is taken before finish() released the
            # requests that completed, so a full-throughput stream of short
            # requests reads as busy
            engaged.update(s.rid for s in batch)
            self.metrics.on_tick(len(engaged))
            if self.pool is not None:
                self.metrics.on_pool_gauge(self.pool.n_free, self.pool.usable)
            if sp:
                sp.set(n_prefills=len(plan.prefills), n_decode=len(batch),
                       engaged=sorted(engaged))

    def admit_now(self, request: Request) -> bool:
        """Legacy-style admission: bind a slot and run the *whole*
        prompt's prefill immediately (all chunks back to back).  Returns
        False when no slot is free — the old ``ServeEngine.admit``
        contract."""
        if self.scheduler.n_free == 0 or self.scheduler.queue:
            return False
        if self.pool is not None and not self.pool.can_admit(request.prompt):
            return False         # paged: pool cannot hold the prompt now
        self.submit(request)
        state = self.scheduler.admit_next()
        while state.request.status == PREFILL:
            self._run_prefill_chunk(
                state, self.lattice.next_chunk(state.remaining_prompt)
            )
        return True

    def serve(self, requests: list[Request], max_steps: int = 10_000,
              tick_callback=None):
        """Run to completion with continuous batching.

        Requests still live when ``max_steps`` runs out are marked
        ``status="unfinished"`` (``done`` stays False) and a
        ``RuntimeWarning`` is emitted — never silently returned as if
        complete.  ``tick_callback``, when given, is invoked as
        ``tick_callback(step)`` after every tick (the launcher's
        periodic metrics printout hangs off it).

        The whole batch is validated *before* anything queues: an
        unservable request (over-long prompt) is marked
        ``status="rejected"`` with a ``RuntimeWarning`` and the rest of
        the list is served — submitting one at a time used to abandon
        the half-submitted batch when a mid-list prompt raised."""
        for r in requests:
            reason = self._reject_reason(r)
            if reason is None:
                continue
            r.status = REJECTED
            r.done = False
            self.metrics.on_reject(r.rid)
            if _trace.enabled():
                _trace.instant("reject", "runtime", rid=r.rid)
            warnings.warn(
                f"request {r.rid} rejected (not served): {reason}",
                RuntimeWarning,
                stacklevel=2,
            )
        for r in requests:
            if r.status != REJECTED:
                self.submit(r)
        self.metrics.start()
        steps = 0
        while self.scheduler.has_work() and steps < max_steps:
            self.tick()
            steps += 1
            if tick_callback is not None:
                tick_callback(steps)
        self.metrics.stop()
        if self.scheduler.has_work():
            leftover = [s for s in list(self.scheduler.queue)
                        + list(self.scheduler.active.values())]
            for state in leftover:
                if state.slot is not None:
                    self.scheduler.finish(state, UNFINISHED)
                else:
                    state.request.status = UNFINISHED
                self.metrics.on_unfinished(state.rid)
            self.scheduler.queue.clear()
            warnings.warn(
                f"serve() exhausted max_steps={max_steps} with "
                f"{len(leftover)} unfinished request(s): "
                f"{sorted(s.rid for s in leftover)} (marked "
                f"status='unfinished', done=False)",
                RuntimeWarning,
                stacklevel=2,
            )
        return requests


def _write_slot(cache, one, slot: int):
    """Copy a batch-1 cache tree into slot ``slot`` of the stacked cache."""

    def write(dst, src):
        src = src.astype(dst.dtype)[None]
        return jax.lax.dynamic_update_slice(
            dst, src, (slot,) + (0,) * (dst.ndim - 1)
        )

    return jax.tree.map(write, cache, one)
