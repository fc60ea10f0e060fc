"""Smoke run of the contraction engine and full-width serving on a TPU.

    python chip_smoke.py              # one chip: engine, Tucker, serving
    python chip_smoke.py --chips 4    # the four-chip phase alone

Every phase checks its results against a reference and the script exits
non-zero on any mismatch; nothing is caught and skipped.  It needs a TPU:
on any other platform it fails before the first phase.  The last line
of standard output is one JSON object naming the device.  The times it
prints are set-up and smoke timings, not benchmark numbers.

Phases (one chip):

* engine  — Table II cases at 512 per mode in bf16 through
  ``contract(...)``: the native kernel, and the Pallas backend under the
  ``auto`` and ``batched`` plans; each compiled program must hold a
  Mosaic kernel (``tpu_custom_call``) and match ``strategy="direct"``
  in f32 at ``precision="highest"``;
* tucker  — HOOI on a seeded low-rank 512³ tensor with the Pallas
  backend against the XLA direct route, on reconstruction error;
* serving — minicpm-2b at its published widths, weights made in bf16
  on the chip, through the launcher's set-up and ``ServingRuntime``:
  8 seeded requests finish, paged and unpaged; the logits row behind
  every output token, prefill and decode, matches the teacher-forced
  cache-free ``forward``; every greedy token is that forward's argmax
  or one of a few near-ties; and the paged runtime gives the same
  greedy tokens.

Four chips: the same model served over a 1x4 model mesh with its weights
made in their shardings, against the same weights on one chip; and
sharded ``contract(mesh=...)`` in the psum and reduce-scatter regimes
against the unsharded result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Table II cases (paper numbering) the engine phase runs
ENGINE_CASES = ("1.1", "1.3", "3.1", "3.4", "5.6", "6.6")
ENGINE_N = 512
#: bf16 output of an f32-accumulated kernel: max |Δ| over max |ref|
#: within two bf16 ulps (2^-7)
ENGINE_TOL = 2.0**-7
#: contract() routes that must run a compiled Pallas kernel
ENGINE_ROUTES = (
    ("native", {"strategy": "native"}),
    ("pallas-auto", {"strategy": "auto", "backend": "pallas"}),
    ("pallas-batched", {"strategy": "batched", "backend": "pallas"}),
)

TUCKER_N, TUCKER_RANKS, TUCKER_ITERS = 512, (10, 10, 10), 5
#: |Δ relative reconstruction error| between the Pallas and XLA routes
TUCKER_TOL = 1e-3

ARCH = "minicpm-2b"
SLOTS, MAX_LEN = 4, 1024
N_REQUESTS, MAX_PROMPT, MAX_NEW = 8, 512, 32
#: the four-chip phase serves fewer, shorter requests: fewer compiles
MESH_REQUESTS, MESH_MAX_PROMPT, MESH_MAX_NEW = 4, 128, 16
#: bf16 logits of two evaluation orders, at every prefill and decode
#: step: ||Δ||₂ / ||ref||₂.  On a TPU v5e the served rows read at most
#: 2.07e-2 against the teacher-forced forward; a decode whose RoPE
#: position or paged KV row is one ahead reads 0.23 at the median step.
LOGITS_TOL = 5e-2
#: two logits closer than this many standard deviations of their row are
#: a near-tie, which bf16 evaluation order may break either way (v5e:
#: 0.0288 at most; the two faults above put ~90% of their non-argmax
#: tokens further off) ...
TIE_TOL = 0.05
#: ... and at most this share of a run's greedy tokens may be near-ties
#: (v5e: 7.4% at most; the faults, 43% and 44%)
TIE_SHARE = 0.15
#: f32 at precision="highest", sharded vs unsharded: ||Δ||₂ / ||ref||₂
CONTRACT_TOL = 1e-5


class Checks:
    """Prints one line per comparison and remembers failures."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"{'PASS' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _timed(label: str, t0: float) -> None:
    print(f"  [smoke timing] {label}: {time.perf_counter() - t0:.1f}s "
          f"wall, set-up included", flush=True)


def _memory(label: str, devices) -> None:
    """Print bytes in use, and the peak, per device."""
    for d in devices:
        st = d.memory_stats() or {}
        print(f"  [memory] {label} {d}: bytes_in_use="
              f"{st.get('bytes_in_use')} peak_bytes_in_use="
              f"{st.get('peak_bytes_in_use')}", flush=True)


def _rel_l2(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


# ------------------------------------------------------------------ engine
def engine_phase(check: Checks, n: int = ENGINE_N) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.contract import contract
    from repro.core.notation import parse_spec
    from repro.core.table2 import CASES

    key = jax.random.PRNGKey(11)
    for label in ENGINE_CASES:
        spec = CASES[label].row_major()
        cs = parse_spec(spec)
        ka, kb, key = jax.random.split(key, 3)
        A = jax.random.normal(ka, (n,) * len(cs.a_modes), jnp.bfloat16)
        B = jax.random.normal(kb, (n,) * len(cs.b_modes), jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda a, b, spec=spec: contract(
                spec, a.astype(jnp.float32), b.astype(jnp.float32),
                strategy="direct"))(A, B)
        scale = float(jnp.max(jnp.abs(ref)))
        for route, kw in ENGINE_ROUTES:
            t0 = time.perf_counter()
            compiled = jax.jit(lambda a, b, spec=spec, kw=kw: contract(
                spec, a, b, **kw)).lower(A, B).compile()
            kernel = "tpu_custom_call" in compiled.as_text()
            got = compiled(A, B)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))) / scale
            check(kernel and err <= ENGINE_TOL,
                  f"engine {label} {spec} {route}: kernel={kernel} "
                  f"max|d|/max|ref|={err:.3e} (tol {ENGINE_TOL:.3e})")
            _timed(f"engine {label} {route} compile+run", t0)


# ------------------------------------------------------------------ tucker
def tucker_phase(check: Checks, n: int = TUCKER_N) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.program import record_programs
    from repro.core.tucker import hooi

    kg, ka, kb, kc, kn = jax.random.split(jax.random.PRNGKey(71), 5)
    r = TUCKER_RANKS

    @jax.jit
    def low_rank():
        G = jax.random.normal(kg, r)
        A, B, C = (jax.random.normal(k, (n, ri)) for k, ri in zip((ka, kb, kc), r))
        T = jnp.einsum("ijk,mi,nj,pk->mnp", G, A, B, C,
                       precision="highest")
        return T + 0.01 * jnp.std(T) * jax.random.normal(kn, (n, n, n))

    T = low_rank()
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        with record_programs() as programs:
            err_pallas = float(hooi(T, r, n_iter=TUCKER_ITERS,
                                    backend="pallas").rel_error)
        _timed("tucker pallas compile+run", t0)
        t0 = time.perf_counter()
        err_xla = float(hooi(T, r, n_iter=TUCKER_ITERS, backend="xla",
                             strategy="direct").rel_error)
        _timed("tucker xla direct", t0)
    # every contraction program the Pallas run built calls a Mosaic kernel
    # (an interpreted kernel lowers to plain HLO, with no custom call)
    kernels = [
        "tpu_custom_call" in p._jit.lower(*(
            jax.ShapeDtypeStruct(i.shape, i.dtype) for i in p.program.inputs
        )).as_text()
        for p in programs
    ]
    check(bool(kernels) and all(kernels)
          and abs(err_pallas - err_xla) <= TUCKER_TOL,
          f"tucker n={n} ranks={r}: {sum(kernels)}/{len(kernels)} programs "
          f"call a kernel; rel_err pallas={err_pallas:.6f} "
          f"xla-direct={err_xla:.6f} (|d| tol {TUCKER_TOL})")


# ----------------------------------------------------------------- serving
def _requests(cfg, n: int, max_prompt: int, max_new: int, seed: int = 5):
    """``n`` seeded prompts of 32 to ``max_prompt`` tokens, in steps of 32."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    lens = 32 * rng.integers(1, max_prompt // 32 + 1, size=n)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=int(k)
                                               ).astype(np.int32),
                    max_new_tokens=max_new)
            for i, k in enumerate(lens)]


def _serve(cfg, params, check: Checks, label: str, reqs, **runtime_kw):
    """Serve ``reqs`` through ``ServingRuntime``, greedily; returns, by
    rid, the logits row that chose each output token (row 0 from the
    prefill, the rest from decode steps).  Every request must finish and
    every row be finite."""
    import numpy as np

    from repro.runtime.engine import ServingRuntime

    t0 = time.perf_counter()
    rt = ServingRuntime(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                        **runtime_kw)
    rows: dict[int, list] = {}

    def record(state, row):  # greedy, keeping the row it picks from
        row = np.asarray(row, np.float32)
        rows.setdefault(state.rid, []).append(row)
        return int(row.argmax())

    rt.greedy, rt._sample = False, record  # every token goes through _sample
    rt.serve(reqs)
    del rt._sample  # drop the closure's reference cycle: frees the cache
    _timed(f"{label} serve (compiles included)", t0)
    want = reqs[0].max_new_tokens
    done = sum(r.done and len(r.output) == want for r in reqs)
    check(done == len(reqs), f"{label}: {done}/{len(reqs)} requests finished "
          f"with {want} tokens")
    n_rows = sum(len(v) for v in rows.values())
    check(n_rows == len(reqs) * want
          and all(np.isfinite(r).all() for v in rows.values() for r in v),
          f"{label}: {n_rows} logits rows, all finite")
    print(f"  {label} buckets: {rt.buckets.stats()}", flush=True)
    return {rid: np.stack(v) for rid, v in rows.items()}


def _forced_logits(cfg, params, reqs) -> dict:
    """Cache-free ``forward`` over each prompt plus its greedy output,
    teacher-forced: row ``t`` holds the logits that chose output token
    ``t`` (row 0 is the prompt's last position).  One compile, at the
    longest sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import forward

    n_new = len(reqs[0].output)
    width = max(len(r.prompt) for r in reqs) + n_new

    @jax.jit
    def rows(p, toks, last):
        logits = forward(cfg, p, {"tokens": toks}, remat=False)[0][0]
        return logits[last + jnp.arange(n_new)]

    out = {}
    for r in reqs:
        seq = np.zeros((1, width), np.int32)
        n = len(r.prompt) + n_new - 1
        seq[0, :n] = np.concatenate([r.prompt, r.output[:-1]])
        out[r.rid] = np.asarray(rows(params, seq, len(r.prompt) - 1),
                                np.float32)
    return out


def _check_logits(check: Checks, label: str, reqs, rows, forced) -> None:
    """Every served logits row matches the teacher-forced forward's."""
    import numpy as np

    err = np.array([[_rel_l2(a, b) for a, b in zip(rows[r.rid],
                                                   forced[r.rid])]
                    for r in reqs])
    check(err.max() <= LOGITS_TOL,
          f"{label}: logits vs cache-free forward at every step, "
          f"||d||/||ref|| worst prefill {err[:, 0].max():.3e}, worst decode "
          f"{err[:, 1:].max():.3e}, median decode "
          f"{np.median(err[:, 1:]):.3e} (tol {LOGITS_TOL:.1e})")


def _gap(row, a: int, b: int) -> float:
    """Distance of tokens ``a`` and ``b`` in a logits row, in standard
    deviations of the row."""
    return abs(float(row[a]) - float(row[b])) / float(row.std())


def _check_greedy(check: Checks, label: str, reqs, forced) -> None:
    """Every greedy token of ``reqs`` is the forced reference's argmax or
    a near-tie with it, and near-ties are rare: decode agrees with the
    cache-free forward."""
    gaps = [_gap(row, tok, int(row.argmax()))
            for r in reqs for tok, row in zip(r.output, forced[r.rid])]
    ties = sorted(g for g in gaps if g > 0)
    bad = sum(g > TIE_TOL for g in ties)
    check(bad == 0 and len(ties) <= TIE_SHARE * len(gaps),
          f"{label}: greedy tokens vs teacher-forced forward: "
          f"{len(gaps) - len(ties)}/{len(gaps)} argmax, {len(ties) - bad} "
          f"near-ties (tol {TIE_TOL} std, at most {TIE_SHARE:.0%}), {bad} "
          f"off; gaps in std: {[round(g, 4) for g in ties]}")


def _check_same_tokens(check: Checks, label: str, reqs, ref_reqs,
                       forced) -> None:
    """``reqs`` repeat ``ref_reqs``' greedy tokens.  Two evaluation orders
    of a bf16 model may pick differently where the reference's top two
    logits are a near-tie; from there the contexts differ, so a request
    counts as agreeing if it is identical or first departs at a near-tie
    of the forced reference."""
    same, ties = 0, []
    for r, ref in zip(reqs, ref_reqs):
        if r.output == ref.output:
            same += 1
            continue
        t = next(i for i, (a, b) in enumerate(zip(r.output, ref.output))
                 if a != b)
        gap = _gap(forced[ref.rid][t], r.output[t], ref.output[t])
        if gap <= TIE_TOL:
            ties.append((ref.rid, t, round(gap, 4)))
    check(same + len(ties) == len(reqs),
          f"{label}: greedy tokens identical for {same}/{len(reqs)} requests"
          f"; departures at near-ties (rid, step, gap in std): {ties}")


def _check_served(check: Checks, label: str, cfg, params, reqs,
                  rows) -> dict:
    """Logits and greedy tokens of served ``reqs`` against the
    teacher-forced forward of their own sequences, which it returns."""
    t0 = time.perf_counter()
    forced = _forced_logits(cfg, params, reqs)
    _timed(f"{label} teacher-forced forward reference", t0)
    _check_logits(check, label, reqs, rows, forced)
    _check_greedy(check, label, reqs, forced)
    return forced


def serving_phase(check: Checks, cfg=None) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import init_serving_params

    cfg = cfg or get_config(ARCH)
    print(f"  serving {cfg.arch_id}: d_model={cfg.d_model} "
          f"layers={cfg.n_layers} heads={cfg.n_heads} vocab={cfg.vocab_size} "
          f"dtype={cfg.dtype}", flush=True)
    t0 = time.perf_counter()
    params = init_serving_params(cfg, seed=0)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"  weights: {n_bytes} bytes", flush=True)
    _timed("weights init", t0)
    _memory("after weights", jax.devices()[:1])

    reqs = _requests(cfg, N_REQUESTS, MAX_PROMPT, MAX_NEW)
    rows = _serve(cfg, params, check, "serving unpaged", reqs)
    _memory("after unpaged serve", jax.devices()[:1])
    forced = _check_served(check, "serving unpaged", cfg, params, reqs, rows)

    paged = _requests(cfg, N_REQUESTS, MAX_PROMPT, MAX_NEW)
    rows = _serve(cfg, params, check, "serving paged", paged, paged=True)
    _check_served(check, "serving paged", cfg, params, paged, rows)
    _check_same_tokens(check, "serving paged vs unpaged", paged, reqs, forced)
    _memory("after paged serve", jax.devices()[:1])


# --------------------------------------------------------------- 4 chips
def four_chip_phase(check: Checks, cfg=None, n: int = 4096) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.core.contract import contract
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import init_serving_params

    devices = jax.devices()
    mesh = make_mesh((1, len(devices)), ("data", "model"))
    cfg = cfg or get_config(ARCH)

    t0 = time.perf_counter()
    params = init_serving_params(cfg, seed=0, mesh=mesh)
    jax.block_until_ready(params)
    _timed("sharded weights init", t0)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    per_dev = [0] * len(devices)
    index = {d: i for i, d in enumerate(devices)}
    for x in jax.tree.leaves(params):
        for s in x.addressable_shards:
            per_dev[index[s.device]] += s.data.nbytes
    print(f"  weights: {n_bytes} bytes in all, per device {per_dev}",
          flush=True)
    check(max(per_dev) < n_bytes,
          f"4-chip: no device holds the whole model "
          f"(largest share {max(per_dev)} of {n_bytes} bytes)")
    _memory("after sharded weights", devices)

    shape = (MESH_REQUESTS, MESH_MAX_PROMPT, MESH_MAX_NEW)
    sharded = _requests(cfg, *shape)
    rows_s = _serve(cfg, params, check, "4-chip sharded", sharded, mesh=mesh)
    _memory("after sharded serve", devices)
    one = jax.device_put(params, SingleDeviceSharding(devices[0]))
    single = _requests(cfg, *shape)
    rows_1 = _serve(cfg, one, check, "4-chip device-0", single)
    worst = max(_rel_l2(rows_s[r.rid][0], rows_1[r.rid][0]) for r in single)
    check(worst <= LOGITS_TOL,
          f"4-chip: sharded first-token logits vs device 0, worst "
          f"||d||/||ref||={worst:.3e} (tol {LOGITS_TOL:.1e})")
    forced = _check_served(check, "4-chip device-0", cfg, one, single, rows_1)
    _check_served(check, "4-chip sharded", cfg, one, sharded, rows_s)
    _check_same_tokens(check, "4-chip sharded vs device 0", sharded, single,
                       forced)
    _memory("after device-0 serve", devices)
    del one

    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    A = jax.random.normal(ka, (n, n), jnp.float32)
    B = jax.random.normal(kb, (n, n), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(contract("mk,kn->mn", A, B, strategy="direct"))
        for regime, out_spec in (("psum", None),
                                 ("reduce-scatter", P(None, "model"))):
            got = contract("mk,kn->mn", A, B, mesh=mesh,
                           in_specs=(P(None, "model"), P("model", None)),
                           out_spec=out_spec)
            err = _rel_l2(got, ref)
            check(err <= CONTRACT_TOL, f"4-chip: sharded contract {regime} "
                  f"vs unsharded, ||d||/||ref||={err:.3e} "
                  f"(tol {CONTRACT_TOL:.0e})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("REPRO_TUNING_CACHE",
                          str(ROOT / ".smoke" / "tuning.json"))
    from repro.utils import place_compile_cache

    print(f"compile cache: {place_compile_cache()}", flush=True)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    check = Checks()
    phases = ([four_chip_phase] if args.chips == 4
              else [engine_phase, tucker_phase, serving_phase])
    for phase in phases:
        t0 = time.perf_counter()
        print(f"== {phase.__name__}", flush=True)
        phase(check)
        _timed(phase.__name__, t0)
    if check.failed:
        print(f"{len(check.failed)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
