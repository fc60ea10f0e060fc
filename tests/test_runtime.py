"""Continuous-batching runtime: buckets, scheduler, grouped kernel,
and the token-identity differential against the legacy engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.buckets import (
    BucketLattice, BucketTable, chunk_schedule, pow2_buckets,
    tuning_key_component,
)
from repro.runtime.metrics import ServingMetrics
from repro.runtime.scheduler import Request, Scheduler


# ----------------------------------------------------------- grouped kernel
class TestGroupedGemm:
    def _rand_groups(self, shapes, dtype=np.float32, seed=0):
        rng = np.random.default_rng(seed)
        As = [jnp.asarray(rng.standard_normal((m, k)), dtype)
              for m, n, k in shapes]
        Bs = [jnp.asarray(rng.standard_normal((k, n)), dtype)
              for m, n, k in shapes]
        return As, Bs

    @pytest.mark.parametrize("shapes", [
        [(5, 17, 9), (12, 3, 33), (1, 1, 1), (40, 20, 8)],
        [(8, 8, 8)],
        [(3, 3, 3), (3, 3, 3), (3, 3, 3)],
        [(33, 7, 65), (2, 31, 4)],
    ])
    def test_matches_reference(self, shapes):
        from repro.kernels.grouped_gemm import grouped_gemm_ref
        from repro.kernels.ops import grouped_matmul

        As, Bs = self._rand_groups(shapes)
        outs = grouped_matmul(As, Bs, tiles={"u": 8, "v": 8, "k": 8})
        for o, r, (m, n, k) in zip(outs, grouped_gemm_ref(As, Bs), shapes):
            assert o.shape == (m, n)
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       atol=1e-5, rtol=1e-5)

    def test_default_tiles_and_bf16(self):
        from repro.kernels.grouped_gemm import grouped_gemm_ref
        from repro.kernels.ops import grouped_matmul

        shapes = [(5, 130, 9), (20, 4, 140)]
        As, Bs = self._rand_groups(shapes, jnp.bfloat16)
        outs = grouped_matmul(As, Bs)  # GROUPED_DEFAULT_TILES
        for o, r in zip(outs, grouped_gemm_ref(As, Bs)):
            assert o.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(o, np.float32), np.asarray(r, np.float32),
                atol=2e-1, rtol=2e-1,
            )

    def test_padding_is_per_group_not_worst_case(self):
        """The packed A buffer pads each group to its own tile multiple —
        a (1,·,·) group costs 8 rows, not the largest group's 256."""
        from repro.kernels.grouped_gemm import DESC_FIELDS, pack_groups

        shapes = [(256, 8, 8), (1, 8, 8)]
        As, Bs = self._rand_groups(shapes)
        A_flat, _, descs, _ = pack_groups(As, Bs, {"u": 8, "v": 8, "k": 8})
        assert A_flat.shape[0] == 256 + 8            # not 2 × 256
        assert descs.shape == (2, len(DESC_FIELDS))
        assert descs[1, 0] == 8                       # padded m of group 2
        assert np.all(np.asarray(descs[:, 6:]) == 0)  # plain layouts

    def test_rejects_bad_groups_and_tiles(self):
        from repro.kernels.ops import grouped_matmul

        A = jnp.zeros((4, 4))
        with pytest.raises(ValueError):
            grouped_matmul([A], [jnp.zeros((5, 4))])   # k mismatch
        with pytest.raises(ValueError):
            grouped_matmul([], [])                     # no groups
        with pytest.raises(ValueError):
            grouped_matmul([A], [A], tiles={"u": 7})   # not a multiple of 8
        with pytest.raises(ValueError):
            grouped_matmul([A], [A], tiles={"b": 8})   # unknown role
        with pytest.raises(ValueError):                # per-group flags must
            grouped_matmul([A], [A], trans_a=[True, False])  # match arity

    # ---------------------------- descriptor-table edge cases (vs ref.py)
    def test_single_group(self):
        """G=1 is still a table-driven launch, not a special case."""
        from repro.kernels.ops import grouped_matmul
        from repro.kernels.ref import ref_grouped_gemm

        As, Bs = self._rand_groups([(13, 29, 7)])
        (out,) = grouped_matmul(As, Bs, tiles={"u": 8, "v": 8, "k": 8})
        (ref,) = ref_grouped_gemm(As, Bs)
        assert out.shape == (13, 29)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_all_sub_tile_group(self):
        """Every dim below every tile: one clamped block per axis."""
        from repro.kernels.ops import grouped_matmul
        from repro.kernels.ref import ref_grouped_gemm

        As, Bs = self._rand_groups([(3, 5, 2), (1, 1, 1)])
        outs = grouped_matmul(As, Bs)  # default tiles (8, 128, 128) ≫ dims
        for o, r in zip(outs, ref_grouped_gemm(As, Bs)):
            assert o.shape == r.shape
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       atol=1e-5, rtol=1e-5)

    def test_empty_groups(self):
        """Zero-size groups: k=0 emits exact zeros, m=0/n=0 emit empty
        results — mixed freely with normal groups in one launch."""
        from repro.kernels.ops import grouped_matmul
        from repro.kernels.ref import ref_grouped_gemm

        rng = np.random.default_rng(3)
        def r(*s):
            return jnp.asarray(rng.standard_normal(s), jnp.float32)

        As = [r(4, 0), r(0, 6), r(4, 6), r(4, 6)]
        Bs = [r(0, 5), r(6, 5), r(6, 0), r(6, 5)]
        outs = grouped_matmul(As, Bs, tiles={"u": 8, "v": 8, "k": 8})
        refs = ref_grouped_gemm(As, Bs)
        assert [tuple(o.shape) for o in outs] == [(4, 5), (0, 5), (4, 0),
                                                  (4, 5)]
        assert np.all(np.asarray(outs[0]) == 0.0)  # k=0 → exact zeros
        for o, ref in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)
        # degenerate extreme: a batch that is nothing but one empty group
        (empty,) = grouped_matmul([r(0, 0)], [r(0, 0)])
        assert empty.shape == (0, 0)

    def test_native_layout_trans_flags(self):
        """Per-group trans_a/trans_b: transposed-stored operands are
        consumed in place via the descriptor table — the grouped
        counterpart of the native tile loaders."""
        from repro.kernels.grouped_gemm import DESC_FIELDS, pack_groups
        from repro.kernels.ops import grouped_matmul
        from repro.kernels.ref import ref_grouped_gemm

        rng = np.random.default_rng(7)
        def r(*s):
            return jnp.asarray(
                rng.integers(-3, 4, s).astype(np.float32))

        # group 0 plain; group 1 both stored transposed; group 2 A only
        As = [r(5, 7), r(7, 6), r(9, 12)]        # 1: (k,m); 2: (k,m)
        Bs = [r(7, 9), r(4, 7), r(9, 130)]       # 1: (n,k)
        ta, tb = [False, True, True], [False, True, False]
        outs = grouped_matmul(As, Bs, trans_a=ta, trans_b=tb)
        refs = ref_grouped_gemm(As, Bs, trans_a=ta, trans_b=tb)
        assert [tuple(o.shape) for o in outs] == [(5, 9), (6, 4), (12, 130)]
        for g, (o, ref) in enumerate(zip(outs, refs)):
            assert np.array_equal(np.asarray(o), np.asarray(ref)), g
        # the flags land in the descriptor table, not in a data permute
        _, _, descs, _ = pack_groups(As, Bs, trans_a=ta, trans_b=tb)
        i_ta, i_tb = DESC_FIELDS.index("trans_a"), DESC_FIELDS.index("trans_b")
        assert list(np.asarray(descs[:, i_ta])) == [0, 1, 1]
        assert list(np.asarray(descs[:, i_tb])) == [0, 1, 0]

    def test_candidate_enumeration(self):
        from repro.tuning.candidates import (
            Candidate, VMEM_BUDGET_BYTES, enumerate_grouped_candidates,
            estimate_grouped_vmem_bytes,
        )

        cands = enumerate_grouped_candidates([(5, 17, 9), (40, 20, 8)])
        keys = [c.key() for c in cands]
        assert keys[0] == "xla:grouped"
        assert len(set(keys)) == len(keys)            # deduped
        assert any(k.startswith("pallas:grouped") for k in keys)
        for c in cands:                               # stable roundtrip
            assert Candidate.from_key(c.key()) == c
        for c in cands:
            if c.backend == "pallas":
                assert estimate_grouped_vmem_bytes(
                    c.tiles_dict, jnp.float32) <= VMEM_BUDGET_BYTES
        # the grouped kernel pads every group UP to its tiles (no
        # clamping), so every distinct grid config is a genuinely
        # different kernel and stays in the candidate set even for tiny
        # groups
        from repro.tuning.candidates import GROUPED_TILE_GRID

        tiny = enumerate_grouped_candidates([(1, 1, 1)])
        assert len(tiny) == 1 + len(GROUPED_TILE_GRID)


# ----------------------------------------------------------------- buckets
class TestBuckets:
    def test_pow2_buckets(self):
        assert pow2_buckets(1) == (1,)
        assert pow2_buckets(4) == (1, 2, 4)
        assert pow2_buckets(6) == (1, 2, 4, 6)        # cap included
        with pytest.raises(ValueError):
            pow2_buckets(0)

    def test_chunk_schedule_covers_exactly(self):
        chunks = pow2_buckets(8)
        for n in range(1, 40):
            sched = chunk_schedule(n, chunks)
            assert sum(sched) == n
            assert all(c in chunks for c in sched)
            assert sched == sorted(sched, reverse=True)   # largest-first

    def test_lattice_modes(self):
        lat = BucketLattice(4, max_chunk=8)
        assert lat.decode_bucket(3) == 4
        assert lat.decode_bucket(1) == 1
        assert lat.next_chunk(13) == 8
        with pytest.raises(ValueError):
            lat.decode_bucket(5)
        legacy = BucketLattice(4, max_chunk=8, chunked=False,
                               bucketed_decode=False)
        assert legacy.slot_buckets == (4,)
        assert legacy.next_chunk(13) == 13             # exact single shot

    def test_bucket_table_compiles_once(self):
        table = BucketTable()
        builds = []
        key = table.key("decode", 2, None)
        for _ in range(3):
            table.get(key, lambda: builds.append(1) or "entry")
        assert builds == [1]
        assert table.compiles == 1 and table.hits == 2
        assert table.stats()["bucket_hit_rate"] == pytest.approx(2 / 3)

    def test_tuning_fingerprint_only_for_tuned(self):
        assert tuning_key_component("auto") is None
        fp = tuning_key_component("tuned")
        assert fp is not None and len(fp) == 2


# --------------------------------------------------------------- scheduler
class TestScheduler:
    def _sched(self, slots=2, chunk=4):
        return Scheduler(slots, BucketLattice(slots, max_chunk=chunk))

    def _req(self, rid, plen=5, max_new=3):
        return Request(rid=rid, prompt=np.arange(plen, dtype=np.int32),
                       max_new_tokens=max_new)

    def test_fifo_admission_and_chunk_plan(self):
        s = self._sched()
        for rid in range(3):
            s.submit(self._req(rid, plen=5))
        plan = s.schedule()
        assert [st.rid for st in plan.admitted] == [0, 1]
        assert [(st.rid, c) for st, c in plan.prefills] == [(0, 4), (1, 4)]
        assert s.decode_batch() == [] and len(s.queue) == 1

    def test_eviction_frees_slot_for_queue(self):
        s = self._sched()
        states = [s.submit(self._req(rid)) for rid in range(3)]
        s.schedule()
        s.evict(1)
        assert states[1].request.status == "evicted"
        assert not states[1].request.done
        plan = s.schedule()                            # rid 2 takes the slot
        assert [st.rid for st in plan.admitted] == [2]
        assert s.n_free == 0

    def test_evict_queued_request_cancels_it(self):
        """A request still waiting for a slot is cancellable: it leaves
        the queue marked evicted (used to KeyError — queued requests
        could not be cancelled)."""
        s = self._sched()
        states = [s.submit(self._req(rid)) for rid in range(3)]
        s.schedule()                                   # 0, 1 take the slots
        assert s.evict(2) is states[2]
        assert states[2].request.status == "evicted"
        assert not states[2].request.done
        assert len(s.queue) == 0 and s.n_active == 2   # slots untouched

    def test_evict_unknown_rid_raises(self):
        s = self._sched()
        s.submit(self._req(0))
        s.schedule()
        with pytest.raises(KeyError, match="neither active nor queued"):
            s.evict(42)

    def test_finish_releases_slot(self):
        s = self._sched(slots=1)
        st = s.submit(self._req(0))
        s.schedule()
        s.finish(st)
        assert st.request.done and st.request.status == "done"
        assert s.n_free == 1 and not s.has_work()

    def test_per_request_keys_are_independent_streams(self):
        s = self._sched()
        a = s.submit(self._req(0))
        b = s.submit(self._req(1))
        ka1, ka2 = a.next_key(), a.next_key()
        kb1 = b.next_key()
        assert not np.array_equal(np.asarray(ka1), np.asarray(ka2))
        assert not np.array_equal(np.asarray(ka1), np.asarray(kb1))


# ----------------------------------------------------------------- metrics
class TestMetrics:
    def test_latency_percentiles_with_fake_clock(self):
        t = [0.0]
        m = ServingMetrics(slots=4, clock=lambda: t[0])
        m.start()
        for rid, dt in enumerate([1.0, 2.0, 4.0]):
            t[0] = float(rid)
            m.on_submit(rid)
            t[0] += 0.5
            m.on_first_token(rid)
            t[0] = rid + dt
            m.on_finish(rid)
        t[0] = 10.0
        m.stop()
        snap = m.snapshot()
        assert snap["requests_done"] == 3
        assert snap["p50_latency_s"] == pytest.approx(2.0)
        assert snap["p99_latency_s"] == pytest.approx(4.0, rel=0.02)
        assert snap["p50_ttft_s"] == pytest.approx(0.5)
        assert snap["wall_s"] == pytest.approx(10.0)
        assert snap["tokens_out"] == 3
        assert snap["throughput_tok_s"] == pytest.approx(0.3)

    def test_utilization_counters(self):
        m = ServingMetrics(slots=4, clock=lambda: 0.0)
        m.on_decode(3, 4)
        m.on_decode(1, 1)
        m.on_tick(3)
        m.on_tick(1)
        snap = m.snapshot()
        assert snap["decode_efficiency"] == pytest.approx(4 / 5)
        assert snap["slot_occupancy"] == pytest.approx(0.5)


# ---------------------------------------------------- runtime (with model)
@pytest.fixture(scope="module")
def served():
    from repro.configs import get_config
    from repro.models.transformer import Model

    cfg = get_config("minicpm-2b", smoke=True).with_(n_periods=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _ragged_requests(cfg, lens, max_new=4):
    out = []
    for i, ln in enumerate(lens):
        rng = np.random.default_rng(i)
        out.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=ln).astype(np.int32),
            max_new_tokens=max_new,
        ))
    return out


def test_runtime_token_identical_to_legacy_engine(served):
    """The acceptance oracle: bucketed decode + chunked prefill vs the
    step-locked fixed-slot engine — same ragged request set, identical
    greedy token streams."""
    from repro.runtime.engine import ServingRuntime
    from repro.serving.engine import ServeEngine

    cfg, _, params = served
    lens = [3, 11, 7, 19, 2, 13]

    old = ServeEngine(cfg, params, slots=2, max_len=64, precompile=False)
    ref = _ragged_requests(cfg, lens)
    old.serve(ref)

    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        precompile=False)
    got = _ragged_requests(cfg, lens)
    rt.serve(got)

    for a, b in zip(ref, got):
        assert b.done and b.output == a.output, (a.rid, a.output, b.output)
    # the live shapes all snapped onto the lattice
    assert all(k[0] in ("decode", "prefill") for k in rt.buckets.keys())
    assert {k[1] for k in rt.buckets.keys() if k[0] == "prefill"} <= {1, 2, 4, 8}


def test_runtime_identity_with_padded_decode_bucket(served):
    """Non-power-of-two slot count: a 3-active tick decodes in the
    4-bucket with a duplicated slot index — the padded row must not
    perturb any token (value-deterministic scatter)."""
    from repro.runtime.engine import ServingRuntime
    from repro.serving.engine import ServeEngine

    cfg, _, params = served
    lens = [3, 11, 7, 19, 2]

    old = ServeEngine(cfg, params, slots=6, max_len=64, precompile=False)
    ref = _ragged_requests(cfg, lens, max_new=3)
    old.serve(ref)

    rt = ServingRuntime(cfg, params, slots=6, max_len=64, prefill_chunk=8,
                        precompile=False)
    got = _ragged_requests(cfg, lens, max_new=3)
    rt.serve(got)
    assert [r.output for r in got] == [r.output for r in ref]
    assert rt.lattice.slot_buckets == (1, 2, 4, 6)


def test_runtime_zero_recompiles_after_warmup(served):
    """Second trace with new ragged lengths: every shape is a bucket hit."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        precompile=False)
    rt.serve(_ragged_requests(cfg, [3, 11, 7, 19], max_new=3))
    warm = rt.buckets.compiles
    rt.serve(_ragged_requests(cfg, [5, 14, 1, 9, 12], max_new=3))
    assert rt.buckets.compiles == warm
    assert rt.buckets.stats()["bucket_hits"] > 0


def test_chunked_prefill_matches_whole_prompt(served):
    """Model-level: prefilling 8+4+1 chunks reproduces the 13-token
    one-shot prefill bit-exactly (cache and last-token logits)."""
    cfg, m, params = served
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)

    cache = m.init_cache(1, 32)
    want_logits, want_cache = m.prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)

    cache2 = m.init_cache(1, 32)
    pos = 0
    for chunk in (8, 4, 1):
        got_logits, cache2 = m.prefill(
            params, {"tokens": jnp.asarray(prompt[None, pos:pos + chunk])},
            cache2)
        pos += chunk
    np.testing.assert_array_equal(np.asarray(want_logits),
                                  np.asarray(got_logits))
    for a, b in zip(jax.tree.leaves(want_cache), jax.tree.leaves(cache2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_runtime_eviction_and_slot_reuse(served):
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=1, max_len=64, precompile=False)
    reqs = _ragged_requests(cfg, [4, 4], max_new=50)
    rt.submit(reqs[0])
    rt.submit(reqs[1])
    rt.tick()          # admits rid 0: prefill + first token + first decode
    assert reqs[0].status == "decode" and len(reqs[0].output) == 2
    rt.evict(0)
    assert reqs[0].status == "evicted" and not reqs[0].done
    rt.tick()                        # rid 1 reuses the slot
    assert reqs[1].status in ("prefill", "decode")
    while rt.scheduler.has_work() and len(reqs[1].output) < 3:
        rt.tick()
    assert len(reqs[1].output) >= 1
    assert rt.metrics.evictions == 1


def test_runtime_cache_length_cap_evicts(served):
    """prompt+generated hitting max_len ends the request as evicted
    instead of silently wrapping the cache."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=1, max_len=8, precompile=False)
    (req,) = _ragged_requests(cfg, [5], max_new=100)
    rt.serve([req], max_steps=50)
    assert req.status == "evicted" and not req.done
    # 5 prompt + first token + decodes up to cache row 7 → 4 tokens out
    assert len(req.output) == 4


def test_runtime_rejects_prompt_longer_than_max_len(served):
    """An over-long prompt would have its prefill cache writes clamped
    (silent KV corruption) — submit() must reject it up front."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=1, max_len=8, precompile=False)
    (req,) = _ragged_requests(cfg, [9])
    with pytest.raises(ValueError, match="exceeds max_len"):
        rt.submit(req)
    # exactly max_len is legal: prefill fits, the decode cap evicts
    (req,) = _ragged_requests(cfg, [8], max_new=5)
    rt.serve([req])
    assert req.output and req.status == "evicted"


def test_runtime_nongreedy_is_reproducible_per_request(served):
    """Sampled decode threads per-request PRNG streams: two fresh
    runtimes produce identical samples; greedy differs from sampled."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served

    def run(greedy):
        rt = ServingRuntime(cfg, params, slots=2, max_len=64, greedy=greedy,
                            precompile=False)
        reqs = _ragged_requests(cfg, [6, 9, 4], max_new=5)
        rt.serve(reqs)
        return [r.output for r in reqs]

    a, b = run(False), run(False)
    assert a == b                              # deterministic streams
    g = run(True)
    assert g != a                              # sampling actually happens
    assert all(len(o) == 5 for o in a)


def test_runtime_rejects_chunking_on_ssm_archs():
    from repro.configs import get_config
    from repro.runtime.engine import ServingRuntime, supports_chunked_prefill

    cfg = get_config("jamba-v0.1-52b", smoke=True).with_(n_periods=1)
    assert not supports_chunked_prefill(cfg)
    with pytest.raises(ValueError, match="chunked prefill"):
        ServingRuntime(cfg, {}, slots=1, max_len=16, chunked_prefill=True)


def test_runtime_metrics_snapshot_end_to_end(served):
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        precompile=False)
    reqs = _ragged_requests(cfg, [3, 11, 7], max_new=3)
    rt.serve(reqs)
    snap = rt.metrics.snapshot(rt.buckets)
    assert snap["requests_done"] == 3
    assert snap["tokens_out"] == sum(len(r.output) for r in reqs)
    assert snap["prefill_tokens"] == sum(len(r.prompt) for r in reqs)
    assert 0 < snap["bucket_hit_rate"] <= 1
    assert snap["throughput_tok_s"] > 0
    assert 0 < snap["slot_occupancy"] <= 1


def test_logits_probe_sees_every_decode_step(served):
    """``logits_probe`` is handed each paged decode launch's logits, one
    row per decoding request in batch order: the argmax of every row is
    the token that step emitted, and no decode token goes unseen."""
    from repro.runtime.engine import ServingRuntime

    cfg, _, params = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                        paged=True, precompile=False)
    at, seen, launches = [], [], []
    run_impl = rt._run_decode_impl

    def decode_impl(decodes):
        at[:] = [s.rid for s in decodes]
        launches.append(len(decodes))
        run_impl(decodes)

    rt._run_decode_impl = decode_impl
    rt.logits_probe = lambda lg: seen.append(
        [(rid, int(np.argmax(np.asarray(lg[r, 0], np.float32))))
         for r, rid in enumerate(at)])
    reqs = _ragged_requests(cfg, [3, 11, 7], max_new=4)
    rt.serve(reqs)
    assert all(r.done for r in reqs)
    assert [len(step) for step in seen] == launches
    for r in reqs:
        # the first token comes from prefill, every later one from a decode
        assert r.output[1:] == [t for step in seen for rid, t in step
                                if rid == r.rid]
