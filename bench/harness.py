"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one configuration, traffic mix or metric is a
file that the harness finds by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run; its
  ``kind`` names the driver ``bench/drivers/<kind>.py``;
* ``bench/traffic/<mix>.json``: the parameters of one generator kind,
  ``bench/generators/<generator>.py``;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.
  A reader that finds nothing to read returns None and the metric is
  left out of the line.

A driver module has ``setup(conf, work, seed, control)``,
``measure(sut, seconds, tracer)``, ``attempted_failed(record)``,
``free(sut)`` and ``check(sut, record, seed)``.  With ``control`` the
driver puts the control (the reference one precision step down) in the
program's place, and ``correct`` has to come out false.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = ["Run", "load_spec", "cell_parts", "metrics_for", "require_device",
           "run_cell", "main"]


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: dict
    conf: dict
    record: dict            # the driver's record of the window
    setup_s: float
    trace: dict | None      # the device-trace reduction (--trace 1)
    peaks: object | None    # bench.peaks.Peaks of the device


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def cell_parts(spec: dict, name: str, bench: Path = BENCH):
    """The cell ``name`` and its configuration and traffic files."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = _json(bench / "configs" / f"{cell['config']}.json")
    mix = _json(bench / "traffic" / f"{cell['traffic']}.json")
    return cell, conf, mix


def _applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def metrics_for(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in spec["end_to_end"] if _applies(m, cell)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"] if _applies(m, cell, names)]


def read_metric(name: str, run: Run, bench: Path = BENCH):
    """``bench/metrics/<name>.py``'s reading of ``run`` (None: nothing
    to read)."""
    path = bench / "metrics" / f"{name}.py"
    modspec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod.read(run)


def require_device(chips: int):
    """The first device, if JAX finds a chip with published peaks and at
    least ``chips`` devices; otherwise exit non-zero."""
    import jax

    from bench.peaks import PEAKS

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found {d.platform}")
    if d.device_kind not in PEAKS:
        raise SystemExit(f"device kind {d.device_kind!r} is not in "
                         f"bench/peaks.py")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return d


def place_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    if set, else ``.jax_cache/`` at the checkout root; every program is
    written to it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCount:
    """Backend compiles and persistent-cache hits while active."""

    def __init__(self):
        from jax import monitoring

        self.compiles = self.cache_hits = 0
        self.on = False
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _device_block(devices, peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}


def _peak_bytes(devices) -> int:
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for x in devices]
    return int(max(peaks)) if peaks else 0


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, conf: dict | None = None, mix: dict | None = None,
             control: bool = False, started: float | None = None,
             log=sys.stderr) -> dict:
    """Run ``cell_name`` once and return the result object.  No device
    check here (``require_device`` is the caller's), so the CPU tests
    can drive a whole run at a small size by passing ``conf``/``mix``.
    ``started``: the ``perf_counter`` time set-up is counted from (the
    harness's start; default: now)."""
    import jax

    from bench import devtrace, generators
    from bench.peaks import PEAKS

    cell, conf0, mix0 = cell_parts(spec, cell_name)
    conf, mix = conf or conf0, mix or mix0
    driver = importlib.import_module(f"bench.drivers.{conf['kind']}")
    gen = generators.load(mix["generator"])
    work = gen.generate(mix, seed=seed, seconds=seconds,
                        vocab=conf.get("vocab_size", 1))
    devices = jax.devices()
    counter = CompileCount()

    t0 = time.perf_counter() if started is None else started
    counter.on = True
    sut = driver.setup(conf, work, seed, control)
    counter.on = False
    setup_s = time.perf_counter() - t0
    print(f"set-up: {setup_s:.3f} s, {counter.compiles} compiles, "
          f"{counter.cache_hits} cache hits, {sut.warmed} steps warmed",
          file=log, flush=True)

    tracer = (devtrace.Tracer(ROOT / ".bench_tmp" / "trace",
                              *devtrace.span(seconds))
              if trace else devtrace.NullTracer())
    c0, h0 = counter.compiles, counter.cache_hits
    counter.on = True
    record = driver.measure(sut, seconds, tracer)
    counter.on = False
    print(f"window: {record['window_s']:.3f} s, "
          f"{counter.compiles - c0} compiles, "
          f"{counter.cache_hits - h0} cache hits inside it",
          file=log, flush=True)
    if record.get("late_s"):
        late = sorted(record["late_s"])
        print(f"generator lateness: median {late[len(late) // 2]:.4f} s, "
              f"max {late[-1]:.4f} s over {len(late)} submissions",
              file=log, flush=True)
    peak = _peak_bytes(devices)
    driver.free(sut)
    reduced = tracer.reduce() if trace else None
    kind = devices[0].device_kind
    run = Run(cell=cell, conf=conf, record=record, setup_s=setup_s,
              trace=reduced, peaks=PEAKS.get(kind))
    metrics = {}
    for m in metrics_for(spec, cell_name, trace):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted, failed = driver.attempted_failed(record)
    verdict = driver.check(sut, record, seed)
    checks = verdict["checks"]
    device = _device_block(devices, peak)
    out = {"correct": all(ok for *_, ok in checks), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["readings"] = verdict["readings"]
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim, _ in checks}
    for name, v, lim, ok in checks:
        print(f"check {name}: {v} (limit {lim}) "
              f"{'ok' if ok else 'FAILED'}", file=log, flush=True)
    return out


def main(argv=None) -> int:
    import argparse

    started = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the control (the reference one precision "
                         "step down) in the program's place; its run has to "
                         "read not correct.  Not part of a benchmark run")
    args = ap.parse_args(argv)
    spec = load_spec()
    cell, _, _ = cell_parts(spec, args.workload)
    place_cache()
    require_device(cell["chips"])
    out = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace), control=args.control, started=started)
    print(json.dumps(out), flush=True)
    return 0
