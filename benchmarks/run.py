"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Run:
``PYTHONPATH=src python -m benchmarks.run [--only fig9] [--quick]``

``--quick`` is the CI profile: repeats are clamped globally
(``benchmarks.common.QUICK``) and modules whose ``run()`` accepts a
``quick`` keyword also shrink their problem sizes.

Modules that publish a ``LAST_RESULTS`` dict (``fig14_runtime``,
``fig15_predict``) get it written as machine-readable JSON next to the
repo root — ``BENCH_runtime.json`` tracks the serving perf trajectory
and ``BENCH_predict.json`` the cost-model regret/cold-start bars, PR
over PR (override the directory with ``REPRO_BENCH_DIR``).
"""

import argparse
import inspect
import json
import os
import sys
import traceback

from benchmarks import common
from benchmarks.common import emit
from repro.utils import place_compile_cache

MODULES = [
    "fig1_transpose_cost",
    "fig2_batched_intensity",
    "fig3_conventional_vs_sb",
    "fig4_flatten_vs_batch",
    "fig56_batch_mode",
    "fig78_exceptional",
    "fig9_tucker",
    "fig10_nary_path",
    "fig11_autotune",
    "fig12_sharded",
    "fig13_program",
    "fig14_runtime",
    "fig15_predict",
    "obs_overhead",
    "table2_cases",
]

#: module → JSON artifact written after a successful run.
JSON_ARTIFACTS = {
    "fig14_runtime": "BENCH_runtime.json",
    "fig15_predict": "BENCH_predict.json",
    "obs_overhead": "BENCH_obs.json",
}


def _write_json_artifact(mod, mod_name: str) -> None:
    payload = getattr(mod, "LAST_RESULTS", None)
    if not payload:
        return
    out_dir = os.environ.get(
        "REPRO_BENCH_DIR",
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    name = JSON_ARTIFACTS[mod_name]
    if common.QUICK:
        # quick-profile numbers are not comparable PR-over-PR: never
        # clobber the tracked full-profile artifact with them
        root, ext = os.path.splitext(name)
        name = f"{root}.quick{ext}"
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)
    # feed the regression sentinel: headline metrics land in the
    # append-only history ledger, tagged with the quick cohort so
    # quick-profile noise never judges full-profile baselines
    from benchmarks import history

    rec = history.append_record(mod_name, payload, quick=common.QUICK)
    if rec:
        print(f"# history: {mod_name} -> {history.history_path()} "
              f"{rec['metrics']}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="CI profile: fewer repeats, smaller sizes")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="record a span trace per module and write "
                         "DIR/<module>.trace.json (Perfetto-ready; a "
                         "fresh tracer per module, so figures don't "
                         "bleed into each other)")
    args = ap.parse_args()
    place_compile_cache()
    if args.quick:
        common.QUICK = True
        os.environ.setdefault("REPRO_BENCH_QUICK", "1")
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        if args.trace_dir:
            from repro.obs import export as obs_export
            from repro.obs import trace as obs_trace

            obs_trace.enable_tracing(obs_trace.Tracer())
        try:
            if "quick" in inspect.signature(mod.run).parameters:
                emit(mod.run(quick=args.quick))
            else:
                emit(mod.run())
            if mod_name in JSON_ARTIFACTS:
                _write_json_artifact(mod, mod_name)
            if args.trace_dir:
                obs_trace.disable_tracing()
                path = os.path.join(args.trace_dir,
                                    f"{mod_name}.trace.json")
                n = obs_export.write_chrome_trace(path)
                print(f"# trace: {n} events -> {path}", file=sys.stderr)
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
        finally:
            if args.trace_dir:
                obs_trace.disable_tracing()
                obs_trace.set_tracer(None)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
