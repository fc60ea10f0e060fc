"""The serving runtime seen from inside: its spans on the profiler's
host plane, the names and scopes of its jitted steps, and its KV-row
counters.

A ``jax.profiler`` capture recorded here has no device plane, but its
host plane holds the program's annotations on the profiler's clock,
which is what places a device idle gap on the chip.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import trace
from repro.runtime.scheduler import Request

PROGRAM_CATS = ("runtime.", "pages.", "scheduler.")


@pytest.fixture(autouse=True)
def _isolate_process_tracer():
    yield
    trace.disable_tracing()
    trace.set_tracer(None)


@pytest.fixture(scope="module")
def served():
    from repro.configs import get_config
    from repro.models.transformer import Model

    cfg = get_config("minicpm-2b", smoke=True).with_(n_periods=1)
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _runtime(served, **kw):
    from repro.runtime.engine import ServingRuntime

    cfg, params = served
    kw = {"paged": True, "page_size": 4, **kw}
    return ServingRuntime(cfg, params, slots=2, max_len=64, prefill_chunk=8,
                          precompile=False, **kw)


def _requests(cfg, lens, max_new):
    rng = np.random.default_rng(7)
    return [Request(rid=i, max_new_tokens=m,
                    prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32))
            for i, (n, m) in enumerate(zip(lens, max_new))]


def _profile(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` capture; return the program's
    annotations as ``{name: [(start_ns, end_ns, line)]}``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_CATS):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         (plane.name, k)))
    return spans


def _inside(inner, outers):
    s, e, line = inner
    return any(line == ln and a <= s and e <= b for a, b, ln in outers)


def test_spans_nest_on_the_profiler_host_plane(served, tmp_path):
    cfg, _ = served
    rt = _runtime(served)
    trace.enable_tracing()
    spans = _profile(tmp_path,
                     lambda: rt.serve(_requests(cfg, [5, 9], [4, 2])))
    for name in ("runtime.tick", "scheduler.schedule",
                 "runtime.prefill_chunk", "pages.stage", "pages.commit",
                 "runtime.decode_batch", "pages.grow",
                 "runtime.decode_inputs", "runtime.decode_launch",
                 "runtime.decode_sync", "runtime.emit"):
        assert spans.get(name), f"no {name} on the host plane"
    ticks, batches = spans["runtime.tick"], spans["runtime.decode_batch"]
    for name in ("pages.grow", "runtime.decode_inputs",
                 "runtime.decode_launch", "runtime.decode_sync",
                 "runtime.emit"):
        assert all(_inside(sp, batches) for sp in spans[name]), name
    assert all(_inside(sp, ticks) for sp in batches)
    chunks = spans["runtime.prefill_chunk"]
    for name in ("pages.stage", "pages.commit"):
        assert all(_inside(sp, chunks) for sp in spans[name]), name
    # one decode launch per decode batch: 1 with both requests, 2 alone
    assert len(batches) == len(spans["runtime.decode_sync"]) == 3


def test_tracing_off_leaves_no_program_spans(served, tmp_path):
    cfg, _ = served
    rt = _runtime(served)
    assert not trace.enabled()
    spans = _profile(tmp_path,
                     lambda: rt.serve(_requests(cfg, [5, 9], [4, 2])))
    assert spans == {}


def test_span_attributes_stay_in_the_ring_buffer(served):
    cfg, _ = served
    rt = _runtime(served)
    t = trace.enable_tracing(trace.Tracer())
    rt.serve(_requests(cfg, [5, 9], [4, 2]))
    trace.disable_tracing()
    spans = [e for e in t.events() if e["ph"] == trace.PH_SPAN]
    by_name = {}
    for e in spans:
        by_name.setdefault((e["cat"], e["name"]), []).append(e["args"])
    for key in (("pages", "stage"), ("pages", "commit"),
                ("runtime", "prefill_chunk")):
        assert all(a["rid"] in (0, 1) for a in by_name[key]), key
    for key in (("runtime", "decode_batch"), ("pages", "grow"),
                ("runtime", "decode_inputs"), ("runtime", "decode_launch"),
                ("runtime", "decode_sync"), ("runtime", "emit")):
        rids = [sorted(a["rids"]) for a in by_name[key]]
        assert rids == [[0, 1], [0], [0]], key


def test_kv_row_counters_match_a_hand_count(served):
    """Prompts of 5 and 9 rows, 4 and 2 tokens out, pages of 4 rows.
    Both prompts finish prefill in one tick and decode together once
    (cache lengths 5 and 9, tables of 2 and 3 pages); request 0 then
    decodes alone twice (lengths 6 and 7, still 2 pages).  A launch
    attends length + 1 rows per request and reads the rows of the pages
    that hold them, ceil(length / 4) x 4, plus the new row: 2, 3, 2 and
    2 pages in the one attention layer."""
    cfg, _ = served
    rt = _runtime(served)
    rt.serve(_requests(cfg, [5, 9], [4, 2]))
    live = (5 + 1) + (9 + 1) + (6 + 1) + (7 + 1)
    viewed = (8 + 1) + (12 + 1) + (8 + 1) + (8 + 1)
    snap = rt.metrics.snapshot()
    assert (snap["kv_rows_live"], snap["kv_rows_viewed"]) == (live, viewed)
    assert (live, viewed) == (31, 40)
    assert snap["kv_pages_read"] == 2 + 3 + 2 + 2


def test_kv_pages_read_stop_at_the_window(served):
    """The same traffic through a full layer and a layer whose window
    of 6 reaches rows [length - 5, length): lengths 5, 9, 6 and 7 read
    pages [0, 2), [1, 3), [0, 2) and [0, 2) there, 8 pages, against 9 in
    the full layer."""
    from repro.configs.base import LayerSpec
    from repro.models.transformer import Model

    cfg, _ = served
    cfg = cfg.with_(pattern=(LayerSpec(window=6), LayerSpec()))
    rt = _runtime((cfg, Model(cfg).init(jax.random.PRNGKey(0))))
    rt.serve(_requests(cfg, [5, 9], [4, 2]))
    snap = rt.metrics.snapshot()
    assert (snap["kv_rows_live"], snap["kv_rows_viewed"]) == (31, 40)
    assert snap["kv_pages_read"] == 8 + 9


def test_unpaged_decode_counts_no_kv_rows(served):
    cfg, _ = served
    rt = _runtime(served, paged=False)
    reqs = rt.serve(_requests(cfg, [5, 9], [4, 2]))
    assert all(r.done for r in reqs)
    snap = rt.metrics.snapshot()
    assert snap["kv_rows_live"] == snap["kv_rows_viewed"] == 0


def test_serve_decode_is_named_and_scoped(served):
    rt = _runtime(served)
    step = rt.kv.build_decode(rt._decode_vmapped, 2, 4)
    assert step.__wrapped__.__name__ == "serve_decode"
    lowered = step.lower(rt.params, rt.kv.pool, jnp.zeros((2, 4), jnp.int32),
                         jnp.zeros((2,), jnp.int32),
                         jnp.zeros((2, 1, 1), jnp.int32))
    assert lowered.as_text().startswith("module @jit_serve_decode")
    hlo = lowered.compile().as_text()
    for scope in ("decode_model", "kv_write"):
        assert f'op_name="jit(serve_decode)/{scope}/' in hlo, scope
    assert "kv_view" not in hlo       # pages are read in place


@pytest.mark.parametrize("name", ["serve_prefill", "serve_page_view",
                                  "serve_page_commit", "serve_decode_dense"])
def test_step_builders_compile_under_stable_names(served, name):
    rt = _runtime(served, paged=name != "serve_decode_dense")
    P, c = 4, 8
    if name == "serve_decode_dense":
        step = rt._build_decode(1)
        args = (rt.params, rt.cache, jnp.zeros((2, 1, 1), jnp.int32),
                jnp.zeros((1,), jnp.int32))
    else:
        view = rt.kv.build_view(P)
        stage = view(rt.kv.pool, jnp.zeros((1, P), jnp.int32),
                     jnp.zeros((1,), jnp.int32))
        step, args = {
            "serve_page_view": (view, (rt.kv.pool,
                                       jnp.zeros((1, P), jnp.int32),
                                       jnp.zeros((1,), jnp.int32))),
            "serve_page_commit": (rt.kv.build_commit(P),
                                  (rt.kv.pool, stage,
                                   jnp.zeros((P,), jnp.int32))),
            "serve_prefill": (rt._build_prefill(),
                              (rt.params, jnp.zeros((1, c), jnp.int32),
                               stage)),
        }[name]
    assert step.__wrapped__.__name__ == name
    assert step.lower(*args).as_text().startswith(f"module @jit_{name}")
