"""Serving launcher: batched requests through the continuous-batching runtime.

    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
        --requests 8 --max-new 16

By default requests run through :class:`repro.runtime.engine.ServingRuntime`
(chunked prefill + bucketed decode + metrics); ``--legacy`` serves through
the old fixed-slot :class:`~repro.serving.engine.ServeEngine` wrapper
instead (the token-identical oracle).

Sharded serving over a device mesh (simulate the devices on CPU by
exporting ``XLA_FLAGS=--xla_force_host_platform_device_count=8``):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
        --mesh 2x4 --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_host_mesh, parse_mesh_shape
from repro.models.transformer import init_params
from repro.runtime.engine import ServingRuntime
from repro.serving.engine import Request, ServeEngine
from repro.utils import place_compile_cache

__all__ = ["init_serving_params", "main"]


def init_serving_params(cfg: ModelConfig, *, seed: int = 0, mesh=None):
    """Random serving weights, made under ``jit`` directly where they live.

    Weights take the activation dtype (bf16 for the published configs;
    the f32 master copy is a training concern).  On a ``mesh`` every
    leaf is created in the sharding the runtime serves it with (the
    model zoo's logical-axis rules), so no device ever holds the whole
    model; without one they are made on the default device.
    """
    cfg = cfg.with_(param_dtype=cfg.dtype)
    key = jax.random.PRNGKey(seed)
    shardings = None
    if mesh is not None:
        from repro.distributed.sharding import ShardingRules
        from repro.launch.shardings import param_logical_axes, tree_shardings

        shapes = jax.eval_shape(lambda k: init_params(k, cfg), key)
        shardings = tree_shardings(
            ShardingRules(mesh), param_logical_axes(shapes), shapes)
    return jax.jit(lambda k: init_params(k, cfg), out_shardings=shardings)(key)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=64,
                    help="max prefill chunk (power-of-two lattice below it); "
                         "auto-disabled for SSM/hybrid archs")
    ap.add_argument("--legacy", action="store_true",
                    help="serve through the old fixed-slot ServeEngine "
                         "(whole-prompt prefill, full-slot decode)")
    ap.add_argument("--paged", action="store_true",
                    help="serve off the paged KV-cache: fixed-size pages, "
                         "per-request page tables, admission capped by free "
                         "pages, content-hash prefix sharing")
    ap.add_argument("--page-size", type=int, default=16,
                    help="token rows per KV page (paged mode)")
    ap.add_argument("--pages", type=int, default=None,
                    help="total pool pages incl. the reserved null page "
                         "(default: null page + slots*max_len rows worth)")
    ap.add_argument("--no-prefix-share", action="store_true",
                    help="disable content-hash prefix sharing (paged mode)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="serve sharded over a data×model host mesh, e.g. "
                         "'2x4' (needs that many devices; simulate on CPU "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launch)")
    ap.add_argument("--pretune", action="store_true",
                    help="autotune the model's contraction working set "
                         "before serving (warm start for strategy='tuned')")
    ap.add_argument("--tuning-cache", default=None,
                    help="tuning-cache JSON path (default: "
                         "$REPRO_TUNING_CACHE or ~/.cache/repro/tuning.json)")
    ap.add_argument("--tune-policy", default=None,
                    choices=["off", "cached", "measure"],
                    help="dispatcher policy for pretune + serving "
                         "(default: measure)")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record a span trace of warm-up + serving and "
                         "write it as Chrome-trace JSON (open in "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--trace-jsonl", default=None, metavar="OUT_JSONL",
                    help="also write the trace as flat JSONL records "
                         "(one event per line, span attrs hoisted)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="trace ring-buffer size in events (oldest "
                         "events drop beyond it)")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="TICKS",
                    help="print a metrics-registry snapshot every N "
                         "serving ticks (runtime mode only)")
    args = ap.parse_args()
    place_compile_cache()
    if args.paged and args.legacy:
        ap.error("--paged serves through the runtime; drop --legacy")

    tracer = None
    if args.trace or args.trace_jsonl:
        from repro.obs import trace as obs_trace

        tracer = obs_trace.enable_tracing(capacity=args.trace_capacity)

    mesh = None
    if args.mesh:
        data, model_par = parse_mesh_shape(args.mesh)
        mesh = make_host_mesh(data, model_par)
        print(f"mesh: {args.mesh} over {len(jax.devices())} "
              f"{jax.default_backend()} devices")

    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_serving_params(cfg, seed=0, mesh=mesh)

    t0 = time.perf_counter()
    if args.legacy:
        engine = ServeEngine(
            cfg, params, slots=args.slots, max_len=args.max_len,
            pretune=args.pretune,
            tuning_cache=args.tuning_cache,
            tune_policy=args.tune_policy, mesh=mesh,
        )
        runtime = engine.runtime
    else:
        engine = runtime = ServingRuntime(
            cfg, params, slots=args.slots, max_len=args.max_len,
            prefill_chunk=args.chunk,
            paged=args.paged, page_size=args.page_size, pages=args.pages,
            prefix_sharing=not args.no_prefix_share,
            pretune=args.pretune,
            tuning_cache=args.tuning_cache,
            tune_policy=args.tune_policy, mesh=mesh,
        )
        print(f"runtime buckets: {runtime.lattice.describe()}")
        if args.paged:
            print(f"page pool: {runtime.pool.usable} usable pages x "
                  f"{runtime.pool.page_size} rows "
                  f"(prefix sharing {'off' if args.no_prefix_share else 'on'})")
    if args.pretune:
        print(f"pretune: {runtime.pretune_stats} "
              f"({time.perf_counter() - t0:.1f}s, "
              f"dispatcher {runtime.tuner.stats})")

    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=rng.integers(4, 24)).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]
    registry = runtime.register_metrics()

    tick_cb = None
    if args.metrics_every > 0 and not args.legacy:
        every = args.metrics_every

        def tick_cb(step):
            if step % every == 0:
                snap = registry.snapshot()
                s = snap.get("serving", {})
                d = snap.get("dispatcher", {})
                print(f"[tick {step}] tokens_out={s.get('tokens_out')} "
                      f"done={s.get('requests_done')} "
                      f"occupancy={s.get('slot_occupancy', 0.0):.2f} "
                      f"dispatcher_hits={d.get('hits')} "
                      f"misses={d.get('misses')}")

    t0 = time.perf_counter()
    if args.legacy:
        engine.serve(reqs)
    else:
        engine.serve(reqs, tick_callback=tick_cb)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in reqs)
    print(f"served {len(reqs)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    snap = runtime.metrics.snapshot(runtime.buckets)
    print("metrics: " + ", ".join(
        f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in snap.items()
    ))
    if args.paged:
        print("pages: " + ", ".join(
            f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in runtime.pool.stats().items()
        ))
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt[:8]={r.prompt[:8].tolist()} -> {r.output}")

    if tracer is not None:
        from repro.obs import export as obs_export

        if args.trace:
            n = obs_export.write_chrome_trace(args.trace, tracer)
            print(f"trace: {n} events -> {args.trace} "
                  f"({tracer.dropped} dropped)")
        if args.trace_jsonl:
            n = obs_export.write_jsonl(args.trace_jsonl, tracer)
            print(f"trace: {n} records -> {args.trace_jsonl}")


if __name__ == "__main__":
    main()
