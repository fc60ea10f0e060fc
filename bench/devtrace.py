"""Device trace: capture a window with the JAX profiler, reduce it.

Capture: :class:`Tracer` starts the profiler (host Python tracing off)
at the first point between two units of work (a tick, a job) after a
fifth of the window has passed, so that the traced span reads the
steady state and not the first arrivals, and stops it ``TRACE_S``
later, at the next such point; the traced span is wrapped in a host
annotation ``bench.window``.

Reduction (:func:`reduce_profile`), from the ``.xplane.pb`` that
``jax.profiler.ProfileData`` reads:

* device planes are those named ``/device:TPU:<n>``; the ops are the
  events of their ``XLA Ops`` line, the program launches those of their
  ``XLA Modules`` line;
* busy time is the union of the op intervals inside the traced window,
  averaged over the devices that ran anything; idle share is
  1 - busy / window;
* a launch is attributed to its program by the module event's name
  (``jit_<function>(<id>)``), an op to its program by its enclosing
  module event;
* an op event's name is its HLO text (``%run.8 = f32[..] custom-call(..),
  custom_call_target="tpu_custom_call", ..``).  Container ops (``while``,
  ``conditional``, ``call``) enclose ops that are listed too, so they
  count toward busy time but not toward any op's time.  A Pallas kernel
  is a ``tpu_custom_call``; a contraction is a Pallas kernel or an XLA
  dot (a fusion named ``convolution..`` or of kind ``kOutput``);
* each idle gap inside the window is attributed to the innermost host
  annotation ``bench.*`` that holds its midpoint (``host`` if none).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import time
from pathlib import Path

__all__ = ["TRACE_S", "span", "Tracer", "NullTracer", "reduce_profile",
           "load", "union_length"]

#: seconds of each run's window that a ``--trace 1`` run records
TRACE_S = 10.0


def span(seconds: float) -> tuple[float, float]:
    """(start, length) of the traced part of a ``seconds`` window: from a
    fifth of the way in, ``TRACE_S`` or the rest of the window."""
    start = 0.2 * seconds
    return start, min(TRACE_S, seconds - start)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"


class NullTracer:
    def arm(self, t0: float) -> None:
        pass

    def poll(self) -> bool:
        return False

    def finish(self) -> None:
        pass


class Tracer:
    """Profiles ``seconds`` of the window from ``start_s`` on."""

    def __init__(self, out_dir: Path, start_s: float, seconds: float):
        self.out_dir = Path(out_dir)
        self.start_s, self.seconds = max(0.0, start_s), seconds
        self.state = 0          # 0 waiting, 1 tracing, 2 done
        self.t0 = 0.0

    def arm(self, t0: float) -> None:
        self.t0 = t0

    def poll(self) -> bool:
        import jax

        now = time.perf_counter() - self.t0
        if self.state == 0 and now >= self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.out_dir.mkdir(parents=True, exist_ok=True)
            self.since = time.perf_counter()
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation(WINDOW)
            self.ann.__enter__()
            self.state = 1
        elif self.state == 1 and now >= self.start_s + self.seconds:
            self._stop()
        return self.state == 1

    def _stop(self) -> None:
        import jax

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = 2

    def finish(self) -> None:
        if self.state == 1:
            self._stop()

    def reduce(self) -> dict:
        """The reduction of the recorded profile, which is then deleted."""
        import shutil

        try:
            return reduce_profile(load(self.out_dir))
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)


def load(out_dir):
    """The newest profile under ``out_dir``."""
    import jax

    files = glob.glob(os.path.join(str(out_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return jax.profiler.ProfileData.from_file(max(files,
                                                  key=os.path.getmtime))


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, lo, hi):
    """Uncovered ``(start, end)`` stretches of ``[lo, hi]``."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def module_name(event_name: str) -> str:
    """``jit_serve_decode(123)`` -> ``serve_decode``."""
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


CONTAINERS = ("while", "conditional", "call")


def hlo_parts(text: str) -> tuple[str, str, str]:
    """(name without its number, opcode, custom-call target) of an HLO
    instruction's text."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%").rstrip("0123456789").rstrip(".")
    if rest.startswith("("):                      # tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.lstrip().partition("(")[0]
    target = ""
    if 'custom_call_target="' in text:
        target = text.split('custom_call_target="', 1)[1].split('"', 1)[0]
    return name, opcode, target


def is_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def is_contraction(text: str) -> bool:
    name, opcode, _ = hlo_parts(text)
    return is_kernel(text) or (opcode == "fusion" and (
        "convolution" in name or "kind=kOutput" in text))


def _holder(acts: dict, t) -> str:
    """The shortest host annotation that holds time ``t``."""
    best, length = "host", None
    for name, spans in acts.items():
        k = bisect.bisect_right(spans, (t, float("inf"))) - 1
        if k >= 0 and spans[k][1] >= t:
            d = spans[k][1] - spans[k][0]
            if length is None or d < length:
                best, length = name, d
    return best


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def reduce_profile(pd) -> dict:
    """The numbers the per-layer metrics read, in seconds:

    ``window_s``, ``busy_s``; ``launches``: program -> list of launch
    device durations; ``op_s``: (program, op) -> summed device time;
    ``kernel_s``, ``contract_s``: device time in Pallas kernels, in
    contractions; ``jobs``: whole ``bench.hooi`` jobs in the window;
    ``breakdown``: the ten longest device ops and idle stretches by host
    activity, as the result line carries them."""
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if lines.get(OPS_LINE):
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [e for e in _events(ln) if e[0].startswith("bench.")]
    if not devices:
        raise ValueError("the trace holds no device ops")
    wins = [e for e in host if e[0] == WINDOW]
    if wins:
        lo, hi = wins[0][1], wins[0][2]
    else:
        lo = min(e[1] for d in devices for e in d[OPS_LINE])
        hi = max(e[2] for d in devices for e in d[OPS_LINE])
    acts = collections.defaultdict(list)          # name -> sorted spans
    for name, s, e in sorted(host):
        if name != WINDOW:
            acts[name].append((s, e))

    busy, launches = [], collections.defaultdict(list)
    op_s, idle = collections.Counter(), collections.Counter()
    kernel_s = contract_s = 0.0
    for d in devices:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in d[OPS_LINE]
               if e > lo and s < hi]
        spans = [(s, e) for _, s, e in ops]
        busy.append(union_length(spans))
        mods = sorted(((module_name(n), s, e) for n, s, e in
                       d.get(MODULES_LINE, []) if e > lo and s < hi),
                      key=lambda m: m[1])
        for m, s, e in mods:
            if lo <= s and e <= hi:               # whole launches only
                launches[m].append((e - s) / 1e9)
        starts = [s for _, s, _ in mods]
        for n, s, e in ops:
            name, opcode, target = hlo_parts(n)
            if opcode in CONTAINERS:
                continue
            k = bisect.bisect_right(starts, s) - 1
            m = mods[k][0] if k >= 0 and mods[k][2] >= e else "?"
            label = f"{name}:{target}" if target else name
            op_s[(m, label)] += (e - s) / 1e9
            if is_kernel(n):
                kernel_s += (e - s) / 1e9
            if is_contraction(n):
                contract_s += (e - s) / 1e9
        for s, e in _gaps(spans, lo, hi):
            idle[_holder(acts, (s + e) / 2)] += (e - s) / 1e9
    n = len(devices)
    top_ops = collections.Counter()
    for (m, name), secs in op_s.items():
        top_ops[f"{m}/{name}"] += secs / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "launches": dict(launches),
        "op_s": {k: v / n for k, v in op_s.items()},
        "kernel_s": kernel_s / n,
        "contract_s": contract_s / n,
        "jobs": sum(1 for name, s, e in host if name == "bench.hooi"
                    and lo <= s and e <= hi),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops.most_common(10)],
            "idle_gaps": [[k, v / n] for k, v in idle.most_common(10)],
        },
    }
