"""Observability subsystem: tracer, export, registry, metrics guards.

Covers the tracer's contracts (nesting/ordering under an injectable
clock, ring overflow, the disabled-mode no-op fast path), the Chrome
Trace Event exporter + its schema validator, the metrics registry, the
roofline attribution math, the ServingMetrics event-ordering guards
(evict-before-first-token, double-finish, unfinished), and end-to-end
instrumentation through contract / the autotuner / the program cache /
the serving runtime.
"""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import export as obs_export
from repro.obs import roofline as obs_roofline
from repro.obs import trace
from repro.obs.registry import MetricsRegistry


class FakeClock:
    """Deterministic seconds clock; advance() moves time forward."""

    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


@pytest.fixture(autouse=True)
def _isolate_process_tracer():
    """Every test leaves the process tracer off and cleared."""
    yield
    trace.disable_tracing()
    trace.set_tracer(None)


# ======================================================================
# Tracer
# ======================================================================

class TestTracer:
    def test_span_nesting_and_ordering(self):
        clk = FakeClock()
        t = trace.Tracer(clock=clk)
        with t.span("outer", "runtime") as outer:
            clk.advance(1e-6)
            with t.span("inner", "core") as inner:
                clk.advance(2e-6)
                inner.set(x=1)
            clk.advance(3e-6)
            outer.set(y=2)
        evs = t.events()
        # inner closes first
        assert [e["name"] for e in evs] == ["inner", "outer"]
        inner_ev, outer_ev = evs
        assert inner_ev["depth"] == 1 and outer_ev["depth"] == 0
        assert inner_ev["ts"] == pytest.approx(1.0)
        assert inner_ev["dur"] == pytest.approx(2.0)
        assert outer_ev["ts"] == pytest.approx(0.0)
        assert outer_ev["dur"] == pytest.approx(6.0)
        assert inner_ev["args"] == {"x": 1}
        assert outer_ev["args"] == {"y": 2}
        assert [e["seq"] for e in evs] == [0, 1]

    def test_instant(self):
        clk = FakeClock()
        t = trace.Tracer(clock=clk)
        clk.advance(5e-6)
        t.instant("evt", "runtime", {"rid": 3})
        (ev,) = t.events()
        assert ev["ph"] == "i" and ev["dur"] == 0.0
        assert ev["ts"] == pytest.approx(5.0)
        assert ev["args"] == {"rid": 3}

    def test_ring_overflow_keeps_newest(self):
        t = trace.Tracer(capacity=4, clock=FakeClock())
        for i in range(10):
            t.instant(f"e{i}")
        assert t.total == 10
        assert t.dropped == 6
        evs = t.events()
        assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]
        assert [e["seq"] for e in evs] == [6, 7, 8, 9]

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            trace.Tracer(capacity=0)

    def test_out_of_order_exit_tolerated(self):
        clk = FakeClock()
        t = trace.Tracer(clock=clk)
        a = t.span("a")
        b = t.span("b")
        a.__exit__(None, None, None)   # outer closes before inner
        clk.advance(1e-6)
        b.__exit__(None, None, None)
        names = [e["name"] for e in t.events()]
        assert names == ["a", "b"]
        assert t._open == []

    def test_exception_marks_span(self):
        t = trace.Tracer(clock=FakeClock())
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("x")
        (ev,) = t.events()
        assert ev["args"]["error"] == "RuntimeError"

    def test_roofline_fraction_derived_on_close(self, monkeypatch):
        real = obs_roofline.device_peaks

        def on(platform, kind):
            dev = types.SimpleNamespace(platform=platform, device_kind=kind)
            monkeypatch.setattr(obs_roofline, "device_peaks",
                                lambda: real(dev))

        clk = FakeClock()
        on("tpu", "TPU v5 lite")
        t = trace.Tracer(clock=clk)
        with t.span("c") as sp:
            sp.set(roofline_bound_us=2.0)
            clk.advance(8e-6)   # dur = 8 µs
        (ev,) = t.events()
        assert ev["args"]["roofline_fraction"] == pytest.approx(0.25)
        # a host timing is no device share; an unknown chip has no peaks
        on("cpu", "cpu")
        t = trace.Tracer(clock=clk)
        with t.span("c") as sp:
            sp.set(roofline_bound_us=2.0)
        assert "roofline_fraction" not in t.events()[0]["args"]
        on("tpu", "TPU v99")
        t = trace.Tracer(clock=clk)
        with pytest.raises(KeyError, match="TPU v99"):
            with t.span("c") as sp:
                sp.set(roofline_bound_us=2.0)

    def test_clear(self):
        t = trace.Tracer(clock=FakeClock())
        t.instant("x")
        t.clear()
        assert t.events() == [] and t.total == 0 and t.dropped == 0


class TestDisabledFastPath:
    def test_span_returns_null_singleton(self):
        assert not trace.enabled()
        sp = trace.span("anything", "core")
        assert sp is trace.NULL_SPAN
        assert not sp                      # falsy: guards attr construction
        assert sp.set(a=1) is sp           # chainable no-op
        with trace.span("ctx") as inner:
            assert inner is trace.NULL_SPAN

    def test_instant_noop_when_disabled(self):
        trace.instant("evt", "core", rid=1)   # must not raise, no tracer

    def test_enable_disable_roundtrip(self):
        t = trace.enable_tracing(capacity=16, clock=FakeClock())
        assert trace.enabled() and trace.get_tracer() is t
        with trace.span("s", "app"):
            pass
        kept = trace.disable_tracing()
        assert kept is t and not trace.enabled()
        # events survive disablement for export
        assert [e["name"] for e in t.events()] == ["s"]
        # and the fast path is a no-op again
        assert trace.span("x") is trace.NULL_SPAN
        assert t.total == 1

    def test_set_tracer_none_disables(self):
        trace.enable_tracing(capacity=16)
        trace.set_tracer(None)
        assert not trace.enabled() and trace.get_tracer() is None


# ======================================================================
# Export
# ======================================================================

def _sample_tracer():
    clk = FakeClock()
    t = trace.Tracer(clock=clk)
    with t.span("tick", "runtime") as sp:
        clk.advance(1e-6)
        with t.span("contract", "core") as c:
            c.set(strategy="auto", flops=np.int64(128),
                  tiles={"u": 8}, rids=(1, 2))
            clk.advance(1e-6)
        sp.set(n_decode=2)
    t.instant("submit", "runtime", {"rid": 1})
    return t


class TestChromeExport:
    def test_chrome_trace_schema_valid(self):
        obj = obs_export.chrome_trace(_sample_tracer())
        stats = obs_export.validate_chrome_trace(obj)
        assert stats["by_ph"]["X"] == 2
        assert stats["by_ph"]["i"] == 1
        assert "contract" in stats["names"]
        assert stats["by_cat"] == {"runtime": 2, "core": 1}

    def test_one_track_per_category(self):
        obj = obs_export.chrome_trace(_sample_tracer())
        meta = [e for e in obj["traceEvents"] if e["ph"] == "M"]
        names = {e["args"].get("name") for e in meta
                 if e["name"] == "thread_name"}
        assert {"runtime", "core"} <= names
        # layer ordering fixed by CATEGORY_TRACKS
        tids = {e["args"]["name"]: e["tid"] for e in meta
                if e["name"] == "thread_name"}
        assert tids["runtime"] < tids["core"]

    def test_args_json_safe(self, tmp_path):
        path = str(tmp_path / "t.json")
        n = obs_export.write_chrome_trace(path, _sample_tracer())
        obj = json.load(open(path))
        assert len(obj["traceEvents"]) == n
        con = [e for e in obj["traceEvents"] if e["name"] == "contract"][0]
        assert con["args"]["flops"] == 128          # np.int64 → int
        assert con["args"]["rids"] == [1, 2]        # tuple → list
        obs_export.validate_chrome_trace(path)      # file-path form

    def test_validate_rejections(self):
        V = obs_export.validate_chrome_trace
        with pytest.raises(ValueError, match="non-empty"):
            V({"traceEvents": []})
        with pytest.raises(ValueError, match="object"):
            V([1, 2])
        base = {"name": "e", "ph": "X", "ts": 0, "dur": 1,
                "pid": 1, "tid": 1}
        with pytest.raises(ValueError, match="phase"):
            V({"traceEvents": [{**base, "ph": "Z"}]})
        with pytest.raises(ValueError, match="'ts'"):
            V({"traceEvents": [{**base, "ts": -1}]})
        with pytest.raises(ValueError, match="dur"):
            V({"traceEvents": [{k: v for k, v in base.items()
                                if k != "dur"}]})
        with pytest.raises(ValueError, match="name"):
            V({"traceEvents": [{**base, "name": ""}]})
        with pytest.raises(ValueError, match="pid"):
            V({"traceEvents": [{**base, "pid": "x"}]})
        with pytest.raises(ValueError, match="args"):
            V({"traceEvents": [{**base, "args": 7}]})

    def test_cli_requirements(self, tmp_path, capsys):
        path = str(tmp_path / "t.json")
        obs_export.write_chrome_trace(path, _sample_tracer())
        obs_export.main(["--validate", path, "--require-cat", "core",
                         "--require-name", "contract"])
        with pytest.raises(SystemExit) as exc:
            obs_export.main(["--validate", path,
                             "--require-cat", "kernels"])
        assert exc.value.code == 1


class TestJsonl:
    def test_records_flat_and_hoisted(self):
        recs = list(obs_export.jsonl_records(_sample_tracer()))
        assert len(recs) == 3
        con = [r for r in recs if r["name"] == "contract"][0]
        assert con["kind"] == "span"
        assert con["strategy"] == "auto"      # attr hoisted to top level
        assert con["flops"] == 128
        assert con["dur_us"] == pytest.approx(1.0)

    def test_base_field_collision_prefixed(self):
        t = trace.Tracer(clock=FakeClock())
        t.instant("e", "app", {"name": "shadow", "ok": 1})
        (rec,) = obs_export.jsonl_records(t)
        assert rec["name"] == "e"
        assert rec["arg_name"] == "shadow"
        assert rec["ok"] == 1

    def test_write_jsonl(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        n = obs_export.write_jsonl(path, _sample_tracer())
        lines = [json.loads(ln) for ln in open(path)]
        assert len(lines) == n == 3

    def test_export_without_tracer_raises(self):
        assert trace.get_tracer() is None
        with pytest.raises(ValueError, match="no tracer"):
            obs_export.chrome_trace()


# ======================================================================
# Registry
# ======================================================================

class TestMetricsRegistry:
    def test_sources_and_snapshot(self):
        reg = MetricsRegistry()
        reg.register("a", lambda: {"x": 1})
        reg.register("b", lambda: {"y": 2.5})
        snap = reg.snapshot()
        assert snap == {"a": {"x": 1}, "b": {"y": 2.5}}
        assert reg.sources() == ("a", "b")
        reg.unregister("a")
        assert "a" not in reg.snapshot()

    def test_raising_source_isolated(self):
        reg = MetricsRegistry()
        reg.register("bad", lambda: 1 / 0)
        reg.register("good", lambda: {"x": 1})
        snap = reg.snapshot()
        assert snap["good"] == {"x": 1}
        assert "ZeroDivisionError" in snap["bad"]["error"]

    def test_counters(self):
        reg = MetricsRegistry()
        assert reg.snapshot() == {}            # no counters key when empty
        assert reg.counter("ticks") == 1
        assert reg.counter("ticks", 2) == 3
        assert reg.snapshot()["counters"] == {"ticks": 3}
        reg.reset_counters()
        assert reg.snapshot() == {}

    def test_non_callable_rejected(self):
        with pytest.raises(TypeError):
            MetricsRegistry().register("x", {"not": "callable"})

    def test_source_replacement_latest_wins(self):
        reg = MetricsRegistry()
        reg.register("s", lambda: {"v": 1})
        reg.register("s", lambda: {"v": 2})
        assert reg.snapshot()["s"] == {"v": 2}


# ======================================================================
# Roofline attribution
# ======================================================================

class TestRoofline:
    def test_contraction_record_flat_gemm(self):
        from repro.core.notation import parse_spec

        cs = parse_spec("mk,kn->mn")
        dims = {"m": 4, "n": 8, "k": 16}
        rec = obs_roofline.contraction_record(cs, dims, jnp.float32)
        assert rec["spec"] == "mk,kn->mn"
        assert rec["flops"] == 2 * 4 * 8 * 16
        assert rec["bytes"] == 4 * (4 * 16 + 16 * 8 + 4 * 8)
        assert rec["intensity"] == pytest.approx(
            rec["flops"] / rec["bytes"])
        assert rec["roofline_bound_us"] > 0

    def test_bound_is_max_of_ceilings(self):
        compute = obs_roofline.roofline_bound_us(1e15, 1.0)
        memory = obs_roofline.roofline_bound_us(1.0, 1e12)
        assert compute == pytest.approx(1e15 / obs_roofline.PEAK_FLOPS * 1e6)
        assert memory == pytest.approx(1e12 / obs_roofline.HBM_BW * 1e6)

    def test_measured_fraction(self):
        v5e = obs_roofline.peaks(obs_roofline.TARGET_KIND)
        f = obs_roofline.measured_fraction(1e12, 1e9, 10_000.0, v5e)
        bound = obs_roofline.roofline_bound_us(1e12, 1e9)
        assert f == pytest.approx(bound / 10_000.0)
        assert obs_roofline.measured_fraction(1.0, 1.0, 0.0, v5e) == 0.0
        for kind in ("cpu", "TPU v99"):  # no published peaks: no share
            with pytest.raises(KeyError, match="no published peaks"):
                obs_roofline.peaks(kind)

    def test_single_source_of_truth_with_launch(self):
        # launch.roofline re-exports these; equality by identity of value
        import importlib.util as iu
        if iu.find_spec("repro.launch.roofline") is None:  # pragma: no cover
            pytest.skip("launch extras missing")
        src = open("src/repro/launch/roofline.py").read()
        assert "from repro.obs.roofline import" in src
        assert src.count("PEAK_FLOPS =") == 0   # no duplicate definition


# ======================================================================
# ServingMetrics event-ordering guards (S1)
# ======================================================================

class TestServingMetricsGuards:
    def _m(self):
        from repro.runtime.metrics import ServingMetrics

        clk = FakeClock()
        return ServingMetrics(2, clock=clk), clk

    def test_evict_before_first_token(self):
        m, clk = self._m()
        m.on_submit(1)
        clk.advance(0.5)
        m.on_evict(1)
        clk.advance(0.5)
        m.on_first_token(1)          # stray: the request is gone
        snap = m.snapshot()
        assert snap["tokens_out"] == 0
        assert snap["p50_ttft_s"] == 0.0 and m._ttft == []
        assert snap["evictions"] == 1
        assert snap["stray_events"] == 1

    def test_double_finish_single_latency(self):
        m, clk = self._m()
        m.on_submit(1)
        clk.advance(1.0)
        m.on_first_token(1)
        m.on_finish(1)
        m.on_finish(1)               # stray duplicate
        snap = m.snapshot()
        assert snap["requests_done"] == 1
        assert snap["stray_events"] == 1
        assert len(m._latency) == 1

    def test_duplicate_first_token(self):
        m, clk = self._m()
        m.on_submit(1)
        clk.advance(1.0)
        m.on_first_token(1)
        m.on_first_token(1)          # stray duplicate
        assert m.tokens_out == 1
        assert len(m._ttft) == 1
        assert m.stray_events == 1

    def test_unknown_rid_events_are_stray(self):
        m, _ = self._m()
        m.on_first_token(9)
        m.on_finish(9)
        m.on_evict(9)
        m.on_unfinished(9)
        snap = m.snapshot()
        assert snap["stray_events"] == 4
        assert snap["tokens_out"] == 0 and snap["evictions"] == 0
        assert snap["requests_done"] == 0

    def test_normal_flow_unchanged(self):
        m, clk = self._m()
        m.on_submit(1)
        clk.advance(0.25)
        m.on_first_token(1)
        clk.advance(0.75)
        m.on_finish(1)
        m.on_submit(2)
        clk.advance(0.5)
        m.on_unfinished(2)
        snap = m.snapshot()
        assert snap["tokens_out"] == 1
        assert snap["requests_done"] == 1
        assert snap["stray_events"] == 0
        assert m._submit == {}       # no leaked timestamps
        assert snap["p50_ttft_s"] == pytest.approx(0.25)
        assert snap["p50_latency_s"] == pytest.approx(1.0)


# ======================================================================
# Instrumentation integration
# ======================================================================

class TestContractInstrumentation:
    def test_contract_span_attrs(self):
        from repro.core.contract import contract

        t = trace.enable_tracing(trace.Tracer())
        A = jnp.ones((4, 8), jnp.float32)
        B = jnp.ones((8, 2), jnp.float32)
        contract("mk,kn->mn", A, B)
        trace.disable_tracing()
        spans = [e for e in t.events()
                 if e["name"] == "contract" and e["cat"] == "core"]
        assert spans, "contract emitted no span"
        args = spans[-1]["args"]
        assert args["strategy"] == "auto"
        assert args["spec"] == "mk,kn->mn"
        assert args["eager"] is True
        assert args["case_kind"] == "flat_gemm"
        assert args["flops"] == 2 * 4 * 8 * 2
        assert args["roofline_bound_us"] > 0
        # measured on the CPU: a host timing reports no device share
        assert "roofline_fraction" not in args

    def test_contract_disabled_emits_nothing(self):
        from repro.core.contract import contract

        assert not trace.enabled()
        out = contract("mk,kn->mn", jnp.ones((2, 3)), jnp.ones((3, 2)))
        assert out.shape == (2, 2)
        assert trace.get_tracer() is None

    def test_jit_contract_flagged_non_eager(self):
        from repro.core.contract import contract

        t = trace.enable_tracing(trace.Tracer())

        @jax.jit
        def f(a, b):
            return contract("mk,kn->mn", a, b)

        f(jnp.ones((2, 4)), jnp.ones((4, 2)))
        trace.disable_tracing()
        spans = [e for e in t.events() if e["name"] == "contract"]
        assert spans and spans[-1]["args"]["eager"] is False


class TestDispatcherInstrumentation:
    def test_miss_tune_then_hit(self):
        from repro.tuning.dispatch import Dispatcher

        d = Dispatcher(None, policy="measure", iters=1, warmup=0)
        A = jnp.ones((4, 8), jnp.float32)
        B = jnp.ones((8, 4), jnp.float32)
        t = trace.enable_tracing(trace.Tracer())
        d.contract("mk,kn->mn", A, B)     # miss → tune
        d.contract("mk,kn->mn", A, B)     # hit
        trace.disable_tracing()
        names = [e["name"] for e in t.events() if e["cat"] == "tuning"]
        assert "tuning_miss" in names
        assert "tune" in names
        assert "tuning_hit" in names
        hit = [e for e in t.events() if e["name"] == "tuning_hit"][-1]
        assert hit["args"]["measured_us"] > 0
        assert "roofline_fraction" not in hit["args"]  # CPU: no device share
        assert "winner" in hit["args"]
        tune = [e for e in t.events() if e["name"] == "tune"][-1]
        assert tune["args"]["n_measured"] >= 1
        assert tune["args"]["best_us"] > 0

    def test_reset_counters(self):
        from repro.tuning.dispatch import Dispatcher

        d = Dispatcher(None, policy="cached")
        d.contract("mk,kn->mn", jnp.ones((2, 3)), jnp.ones((3, 2)))
        assert d.misses == 1
        d.reset_counters()
        assert (d.hits, d.misses, d.measurements) == (0, 0, 0)
        assert d.stats["entries"] == len(d.cache)   # cache untouched


class TestProgramInstrumentation:
    def test_compile_span_and_cache_hit(self):
        from repro.core.program import clear_program_cache, compile_program

        clear_program_cache()
        t = trace.enable_tracing(trace.Tracer())
        A = jnp.ones((2, 3)), jnp.ones((3, 4)), jnp.ones((4, 2))
        compile_program("ab,bc,cd->ad", *A)
        compile_program("ab,bc,cd->ad", *A)   # same signature: cache hit
        trace.disable_tracing()
        compiles = [e for e in t.events() if e["name"] == "program_compile"]
        hits = [e for e in t.events() if e["name"] == "program_cache_hit"]
        assert len(compiles) == 1 and len(hits) == 1
        assert compiles[0]["args"]["recompile"] is False
        assert compiles[0]["args"]["steps"] >= 1
        sig = compiles[0]["args"]["signature"]
        assert hits[0]["args"]["signature"] == sig
        assert len(sig) == 12


class TestRuntimeInstrumentation:
    @pytest.fixture(scope="class")
    def served(self):
        from repro.configs import get_config
        from repro.models.transformer import Model

        cfg = get_config("minicpm-2b", smoke=True).with_(n_periods=1)
        params = Model(cfg).init(jax.random.PRNGKey(0))
        return cfg, params

    def _requests(self, cfg, lens, max_new=2):
        from repro.runtime.scheduler import Request

        rng = np.random.default_rng(0)
        return [
            Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, size=ln).astype(np.int32),
                max_new_tokens=max_new)
            for i, ln in enumerate(lens)
        ]

    def test_serve_emits_correlated_spans(self, served):
        from repro.runtime.engine import ServingRuntime

        cfg, params = served
        rt = ServingRuntime(cfg, params, slots=2, max_len=64,
                            prefill_chunk=8, precompile=False)
        t = trace.enable_tracing(trace.Tracer())
        ticks_seen = []
        rt.serve(self._requests(cfg, [5, 9]),
                 tick_callback=ticks_seen.append)
        trace.disable_tracing()

        evs = t.events()
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        assert ticks_seen == list(range(1, len(by_name["tick"]) + 1))
        # every layer shows up on its own category
        assert all(e["cat"] == "runtime" for e in by_name["tick"])
        assert all(e["cat"] == "scheduler" for e in by_name["schedule"])
        # rid correlation: submit/prefill/first_token/finish per request
        assert {e["args"]["rid"] for e in by_name["submit"]} == {0, 1}
        assert {e["args"]["rid"] for e in by_name["first_token"]} == {0, 1}
        assert {e["args"]["rid"] for e in by_name["finish"]} == {0, 1}
        pf = by_name["prefill_chunk"]
        assert all({"rid", "chunk", "pos", "slot"} <= set(e["args"])
                   for e in pf)
        db = by_name["decode_batch"]
        assert all({"n_active", "bucket", "rids"} <= set(e["args"])
                   for e in db)
        assert all(set(e["args"]["rids"]) <= {0, 1} for e in db)
        tick = by_name["tick"][0]["args"]
        assert {"n_prefills", "n_decode", "engaged"} <= set(tick)
        adm = by_name["admit"]
        assert {e["args"]["rid"] for e in adm} == {0, 1}

    def test_cache_cap_evict_instant_and_metrics(self, served):
        from repro.runtime.engine import ServingRuntime

        cfg, params = served
        rt = ServingRuntime(cfg, params, slots=1, max_len=8,
                            precompile=False)
        t = trace.enable_tracing(trace.Tracer())
        rt.serve(self._requests(cfg, [6], max_new=8))
        trace.disable_tracing()
        evs = [e for e in t.events() if e["name"] == "evict"]
        assert evs and evs[0]["args"]["reason"] == "cache_cap"
        snap = rt.metrics.snapshot()
        assert snap["evictions"] == 1
        assert snap["stray_events"] == 0

    def test_register_metrics(self, served):
        from repro.runtime.engine import ServingRuntime

        cfg, params = served
        rt = ServingRuntime(cfg, params, slots=2, max_len=64,
                            prefill_chunk=8, precompile=False)
        reg = rt.register_metrics(MetricsRegistry())
        rt.serve(self._requests(cfg, [5]))
        snap = reg.snapshot()
        assert {"serving", "buckets", "programs"} <= set(snap)
        assert snap["serving"]["requests_done"] == 1
        assert snap["buckets"]["bucket_compiles"] >= 1
        assert "dispatcher" not in snap          # no tuner attached

    def test_serve_untraced_has_no_tracer_side_effects(self, served):
        from repro.runtime.engine import ServingRuntime

        cfg, params = served
        rt = ServingRuntime(cfg, params, slots=2, max_len=64,
                            prefill_chunk=8, precompile=False)
        assert not trace.enabled()
        reqs = rt.serve(self._requests(cfg, [5]))
        assert all(r.done for r in reqs)
        assert trace.get_tracer() is None


# ======================================================================
# Ring-drop soak: sampling + watchdogs under sustained tracer overflow
# ======================================================================

class TestRingDropSoak:
    """Sustained sampling far past the tracer ring's capacity must keep
    exact drop accounting, and the health layer's sample-counted windows
    must be oblivious to tracer drops — the sampler's series rings are
    independent state, so losing old trace events never skews a
    watchdog's view of the last N samples."""

    def test_soak_exact_drops_and_unskewed_watchdogs(self):
        from repro.obs.health import DecodeStallWatchdog, HealthMonitor
        from repro.obs.timeseries import MetricsSampler

        clk = FakeClock()
        tracer = trace.enable_tracing(trace.Tracer(capacity=64, clock=clk))
        state = {"ticks": 0, "toks": 0, "done": 0}
        reg = MetricsRegistry()
        reg.register("serving", lambda: {
            "ticks": state["ticks"], "tokens_out": state["toks"],
            "requests_done": state["done"]})
        mon = HealthMonitor(
            MetricsSampler(reg, capacity=32, interval_s=1.0, clock=clk),
            watchdogs=[DecodeStallWatchdog(budget=4)])

        stalls = [(200, 260), (400, 470)]   # iteration spans with no tokens
        alerts = []
        for i in range(500):
            clk.advance(1.0)
            state["ticks"] += 1
            if not any(lo <= i < hi for lo, hi in stalls):
                state["toks"] += 2
            # per-iteration span chatter overflows the 64-slot ring fast
            with tracer.span("decode_batch", "runtime") as sp:
                sp.set(i=i)
            tracer.instant("tick", "runtime", {"i": i})
            alerts += mon.tick()
        trace.disable_tracing()

        # --- exact tracer drop accounting at 10x+ overflow
        per_iter = 2                        # one span + one instant
        expected_total = 500 * per_iter + len(alerts)  # health instants too
        assert tracer.total == expected_total
        assert tracer.dropped == expected_total - 64
        evs = tracer.events()
        assert len(evs) == 64
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)                      # oldest-first
        assert seqs[-1] == expected_total - 1            # newest retained
        assert seqs[0] == expected_total - 64            # exactly capacity kept

        # --- watchdog windows counted in samples, never skewed by drops:
        # exactly one edge-triggered alert per stall episode, no phantoms
        assert [a.name for a in alerts] == ["decode_stall", "decode_stall"]
        assert mon.alert_counts == {"decode_stall": 2}
        assert mon.sampler.samples == 500

        # --- the sampler's own ring does its own exact accounting
        ser = mon.sampler.get("serving.ticks")
        assert len(ser) == 32 and ser.total == 500 and ser.dropped == 468
        assert ser.latest() == 500.0
        # the retained window is the *newest* 32 samples, contiguous
        vals = ser.values()
        assert vals == [float(v) for v in range(469, 501)]

    def test_sampler_interval_under_tracer_pressure(self):
        """Interval gating stays wall-clock-exact while the tracer ring
        churns: ticks between samples check no watchdog and take no
        sample."""
        from repro.obs.health import HealthMonitor
        from repro.obs.timeseries import MetricsSampler

        clk = FakeClock()
        trace.enable_tracing(trace.Tracer(capacity=16, clock=clk))
        reg = MetricsRegistry()
        reg.register("serving", lambda: {"ticks": 1})
        mon = HealthMonitor(
            MetricsSampler(reg, interval_s=2.0, clock=clk), watchdogs=[])
        for i in range(100):
            clk.advance(0.5)
            trace.instant("noise", "runtime", i=i)
            mon.tick()
        trace.disable_tracing()
        # 50s of clock at one sample per 2s (first tick samples at t+0.5)
        assert mon.sampler.samples == 25
        assert mon.checks == 25
