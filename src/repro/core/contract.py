"""Public contraction API — executes planner output on XLA or Pallas.

``contract(spec, A, B, strategy=..., backend=...)`` is the framework's
single entry point for pairwise tensor contractions.  Strategies:

* ``"auto"``      — paper heuristics: flatten when possible, else the
                    strided-batched plan (Algorithm 2).
* ``"flatten"``   — require a flattened single-GEMM evaluation.
* ``"batched"``   — forbid flattening; use the strided-batched plan
                    (what the paper benchmarks as STRIDEDBATCHEDGEMM).
* ``"direct"``    — one ``lax.dot_general`` with every shared mode as a dot
                    batch dim, plus a lazy output transpose if needed.  This
                    is the "good XLA user" reference point.
* ``"conventional"`` — the matricization baseline (BTAS / Tensor Toolbox):
                    explicit, materialized permutes into `C_IJ = A_IK B_KJ`
                    form, one flat GEMM, materialized permute back.  Copies
                    are pinned with ``lax.optimization_barrier`` so XLA
                    cannot elide what the paper's baseline pays for.
* ``"native"``    — the layout-oblivious Pallas kernel
                    (:func:`repro.kernels.ops.execute_native`): block-
                    scatter-style per-mode addressing lowers *any* mode
                    ordering — including the exceptional and degenerate
                    layouts — to a single kernel with no pre-permute or
                    copy.  Implies the Pallas backend (``backend`` is
                    ignored, as with ``"tuned"``).
* ``"tuned"``     — empirical dispatch through the autotuner
                    (:mod:`repro.tuning.dispatch`): run the measured
                    winner when the persistent cache has one, measure on
                    miss per the dispatcher's policy, fall back to the
                    analytic ``"auto"`` plan otherwise.

Backends: ``"xla"`` (dot_general / vmap composition) or ``"pallas"``
(the StridedBatchedGEMM family of TPU kernels).  With
``backend="pallas"``, ``tiles={"u"|"v"|"k"|"b": int}`` overrides the
kernel tile sizes per call (validated against the blocks the kernel
runs; see :func:`repro.tuning.candidates.validate_plan_tiles`, and
:func:`~repro.tuning.candidates.validate_native_tiles` for
``strategy="native"``, whose working set is accounted per mode).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Literal, get_args

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.notation import CaseKind, ContractionSpec, parse_spec
from repro.core.planner import Plan, make_plan
from repro.obs import trace as _trace

__all__ = [
    "contract",
    "infer_dims",
    "record_contractions",
    "conventional_transpose_count",
    "count_hlo_ops",
]

Strategy = Literal[
    "auto", "flatten", "batched", "direct", "conventional", "native", "tuned"
]
Backend = Literal["xla", "pallas"]
#: runtime mirror of ``Strategy`` — anything else raises ValueError (a
#: typo used to fall through silently to the batched plan).
STRATEGIES = get_args(Strategy)


# --------------------------------------------------------------------------
# Working-set recording (used by the serving warm-up / autotuner pretune)
# --------------------------------------------------------------------------

_ACTIVE_RECORDERS: list[list] = []


@contextlib.contextmanager
def record_contractions():
    """Record every ``contract`` call in this context (including under a
    jit/``eval_shape`` trace) as ``(spec_str, dims, dtype_str)`` tuples —
    the *contraction working set* the autotuner's warm-up pass pre-tunes.

    Yields the list the records accumulate into.
    """
    rec: list[tuple] = []
    _ACTIVE_RECORDERS.append(rec)
    try:
        yield rec
    finally:
        # remove by identity: equal (e.g. both-empty) nested recorders must
        # not evict each other
        for i, r in enumerate(_ACTIVE_RECORDERS):
            if r is rec:
                del _ACTIVE_RECORDERS[i]
                break


def infer_dims(spec: ContractionSpec, A, B) -> dict:
    """Map every mode of ``spec`` to its size from the operand shapes.

    Raises ``ValueError`` on rank mismatch between an operand and its mode
    string, or when a mode appears with two different sizes.
    """
    if A.ndim != len(spec.a_modes) or B.ndim != len(spec.b_modes):
        raise ValueError(
            f"rank mismatch: A{A.shape} vs '{spec.a_modes}', B{B.shape} vs '{spec.b_modes}'"
        )
    dims: dict = {}
    for modes, x in ((spec.a_modes, A), (spec.b_modes, B)):
        for m, d in zip(modes, x.shape):
            if dims.setdefault(m, d) != d:
                raise ValueError(f"inconsistent size for mode {m!r}: {dims[m]} vs {d}")
    return dims


def contract(
    spec: str | ContractionSpec,
    A,
    B,
    *,
    strategy: Strategy = "auto",
    backend: Backend = "xla",
    force_batch: str | None = None,
    tiles: dict | None = None,
    preferred_element_type=jnp.float32,
    out_dtype=None,
    mesh=None,
    in_specs=None,
    out_spec=None,
):
    """Evaluate one pairwise contraction ``C = A · B``.

    This is the engine's pairwise entry point; for multi-operand
    expressions use :func:`repro.core.einsum.xeinsum`, which plans a
    contraction path and lowers each step through this function.

    Args:
      spec: row-major einsum spec, e.g. ``"mk,pkn->pmn"``, or a parsed
        :class:`~repro.core.notation.ContractionSpec`.  Exactly two
        operands; no traces, no ellipses; every free mode must appear in
        the output.
      A, B: the operand arrays, ranks matching the spec.
      strategy: one of the seven strategies in the module docstring
        (``"auto"``, ``"flatten"``, ``"batched"``, ``"direct"``,
        ``"conventional"``, ``"native"``, ``"tuned"``).  ``"flatten"``
        raises ``ValueError`` if the spec admits no flattened single-GEMM
        evaluation; ``"native"`` always runs the layout-oblivious Pallas
        kernel; ``"tuned"`` dispatches through the autotuner.  Both
        ignore ``backend`` (the winner/kernel carries its own).
      backend: ``"xla"`` (dot_general/vmap composition) or ``"pallas"``
        (the StridedBatchedGEMM kernel family; interpret mode off-TPU).
        Ignored by ``"direct"``, ``"conventional"``, ``"native"`` and
        ``"tuned"``.
      force_batch: pin the strided-batch mode (benchmark use — Fig. 5/6
        compare batching the last vs. the middle output mode).
      tiles: per-call Pallas tile overrides (role → size for
        ``u``/``v``/``k``/``b``), validated against divisibility and the
        VMEM budget; only legal with ``strategy="native"`` or with
        ``backend="pallas"`` and a planning strategy
        (``"auto"``/``"flatten"``/``"batched"``).
      preferred_element_type: accumulator dtype passed to ``dot_general``.
      out_dtype: result dtype; defaults to the promoted operand dtype.
      mesh: a ``jax.sharding.Mesh`` — execute *sharded*: every device
        runs this contraction's plan on its local block under
        ``shard_map``, with collectives only where the contracted mode is
        sharded (see :mod:`repro.distributed.contract`).
      in_specs: with ``mesh``, a pair of ``PartitionSpec`` (or ``None``)
        aligned to the operand mode strings.
      out_spec: with ``mesh``, the requested output sharding (default:
        the natural one — batch/free modes keep their input sharding).

    Returns:
      The contracted array with modes ordered as ``spec``'s output.
    """
    if not _trace.enabled():
        return _contract_impl(
            spec, A, B, strategy=strategy, backend=backend,
            force_batch=force_batch, tiles=tiles,
            preferred_element_type=preferred_element_type,
            out_dtype=out_dtype, mesh=mesh, in_specs=in_specs,
            out_spec=out_spec,
        )
    with _trace.span("contract", "core") as sp:
        _annotate_contraction(sp, spec, A, B, strategy, backend, tiles, mesh)
        return _contract_impl(
            spec, A, B, strategy=strategy, backend=backend,
            force_batch=force_batch, tiles=tiles,
            preferred_element_type=preferred_element_type,
            out_dtype=out_dtype, mesh=mesh, in_specs=in_specs,
            out_spec=out_spec,
        )


def _annotate_contraction(sp, spec, A, B, strategy, backend, tiles, mesh):
    """Attach the roofline-attribution attributes to a ``contract`` span.

    Best-effort: malformed calls annotate nothing and let the
    implementation raise its usual error (the span then records with an
    ``error`` attribute)."""
    try:
        cs = parse_spec(spec) if isinstance(spec, str) else spec
        dims = infer_dims(cs, A, B)
        dtype = jnp.result_type(A.dtype, B.dtype)
    except Exception:
        return
    from repro.obs.roofline import contraction_record

    eager = not (isinstance(A, jax.core.Tracer)
                 or isinstance(B, jax.core.Tracer))
    sp.set(
        strategy=strategy, backend=backend, eager=eager,
        sharded=mesh is not None,
        dims={m: int(v) for m, v in dims.items()},
        **contraction_record(cs, dims, dtype),
    )
    if tiles:
        sp.set(tiles=dict(tiles))
    if strategy in ("auto", "flatten", "batched"):
        try:
            plan = make_plan(cs, dims,
                             allow_flatten=strategy in ("auto", "flatten"))
            sp.set(case_kind=plan.kind)
        except Exception:
            pass


def _contract_impl(
    spec, A, B, *, strategy, backend, force_batch, tiles,
    preferred_element_type, out_dtype, mesh, in_specs, out_spec,
):
    if strategy not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {strategy!r}; expected one of {STRATEGIES}")
    if backend not in ("xla", "pallas"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'xla' or 'pallas'")
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    dims = infer_dims(cs, A, B)
    out_dtype = out_dtype or jnp.result_type(A.dtype, B.dtype)

    if _ACTIVE_RECORDERS:
        rec_dtype = str(jnp.result_type(A.dtype, B.dtype))
        for rec in _ACTIVE_RECORDERS:
            rec.append((cs.spec_str(), dict(dims), rec_dtype))

    if mesh is not None:
        from repro.distributed.contract import sharded_contract  # no cycle

        return sharded_contract(
            cs, A, B, mesh=mesh, in_specs=in_specs, out_spec=out_spec,
            strategy=strategy, backend=backend, tiles=tiles,
            preferred_element_type=preferred_element_type, out_dtype=out_dtype,
        )
    if in_specs is not None or out_spec is not None:
        raise ValueError("in_specs/out_spec require mesh=")

    if strategy == "tuned":
        if tiles is not None:
            raise ValueError(
                "tiles= cannot be combined with strategy='tuned' "
                "(the tuner owns tile selection)"
            )
        from repro.tuning.dispatch import get_dispatcher  # deferred: no cycle

        return get_dispatcher().contract(
            cs, A, B,
            preferred_element_type=preferred_element_type, out_dtype=out_dtype,
        )

    if strategy == "native":
        from repro.kernels import ops  # deferred: keeps core importable sans pallas

        if tiles is not None:
            from repro.tuning.candidates import validate_native_tiles  # no cycle

            validate_native_tiles(cs, dims, tiles, dtype=jnp.result_type(A.dtype, B.dtype))
        return ops.execute_native(cs, A, B, tiles=tiles, out_dtype=out_dtype)

    if tiles is not None:
        if strategy not in ("auto", "flatten", "batched"):
            raise ValueError(f"tiles= is meaningless for strategy={strategy!r}")
        if backend != "pallas":
            raise ValueError("tiles= requires backend='pallas'")

    if strategy == "direct":
        out = _direct(cs, A, B, preferred_element_type)
        return out.astype(out_dtype)
    if strategy == "conventional":
        out, _ = _conventional(cs, A, B, dims, preferred_element_type)
        return out.astype(out_dtype)

    allow_flatten = strategy in ("auto", "flatten")
    plan = make_plan(cs, dims, allow_flatten=allow_flatten, force_batch=force_batch)
    if strategy == "flatten" and plan.kind != CaseKind.FLAT_GEMM:
        raise ValueError(f"{cs.spec_str()} admits no flattened single-GEMM evaluation")

    if backend == "pallas":
        from repro.kernels import ops  # deferred: keeps core importable sans pallas

        if tiles is not None:
            from repro.tuning.candidates import validate_plan_tiles  # no cycle

            validate_plan_tiles(plan, tiles, jnp.result_type(A.dtype, B.dtype))
        return ops.execute_plan(plan, A, B, out_dtype=out_dtype, tiles=tiles)
    return _execute_xla(plan, A, B, preferred_element_type).astype(out_dtype)


# --------------------------------------------------------------------------
# XLA execution
# --------------------------------------------------------------------------

def _reshape_to_fspec(x, modes: str, fmodes: str, fdims: dict):
    """Fuse flattened mode groups — a pure view under row-major packing."""
    if modes == fmodes:
        return x
    return x.reshape(tuple(fdims[m] for m in fmodes))


def _dot(a, a_modes: str, b, b_modes: str, out_modes: str, kmodes: str, prefer):
    """Single dot_general contracting ``kmodes``; output must equal
    ``out_modes`` up to the (a_free, b_free) / (b_free, a_free) operand
    order — the caller guarantees no interleaving."""
    a_free = [m for m in a_modes if m not in kmodes]
    b_free = [m for m in b_modes if m not in kmodes]
    a_k = [a_modes.index(m) for m in kmodes]
    b_k = [b_modes.index(m) for m in kmodes]
    natural = "".join(a_free) + "".join(b_free)
    swapped = "".join(b_free) + "".join(a_free)
    if out_modes == natural:
        out = lax.dot_general(a, b, ((tuple(a_k), tuple(b_k)), ((), ())),
                              preferred_element_type=prefer)
    elif out_modes == swapped:
        out = lax.dot_general(b, a, ((tuple(b_k), tuple(a_k)), ((), ())),
                              preferred_element_type=prefer)
    else:  # general fallback: natural order + lazy transpose
        out = lax.dot_general(a, b, ((tuple(a_k), tuple(b_k)), ((), ())),
                              preferred_element_type=prefer)
        perm = [natural.index(m) for m in out_modes]
        out = jnp.transpose(out, perm)
    return out


def _execute_xla(plan: Plan, A, B, prefer):
    if "degenerate" in plan.notes:
        # no matrix view of C exists (its minor mode is a shared batch
        # mode): no BLAS-style evaluation applies — use the direct path.
        return _direct(plan.spec, A, B, prefer)
    fs, fd = plan.fspec, plan.fdims
    A = _reshape_to_fspec(A, plan.spec.a_modes, fs.a_modes, fd)
    B = _reshape_to_fspec(B, plan.spec.b_modes, fs.b_modes, fd)

    if plan.kind == CaseKind.FLAT_GEMM and not plan.batch_modes:
        out = _dot(A, fs.a_modes, B, fs.b_modes, fs.c_modes, fs.contracted, prefer)
    else:
        out = _nested_batched(fs, plan.batch_modes, A, B, prefer)
    return out.reshape(tuple(plan.dims[m] for m in plan.spec.c_modes))


def _nested_batched(fs: ContractionSpec, batch_modes: str, A, B, prefer):
    """Nested vmaps (outermost-first) around a 2D dot core.

    Each vmap batches one mode *in place* (in_axes/out_axes at the mode's
    native position) — the JAX rendering of looped sb_gemm: no data is
    moved, the batch loop walks a stride.
    """

    def build(a_modes: str, b_modes: str, c_modes: str, todo: str):
        if not todo:
            k = "".join(m for m in a_modes if m in b_modes and m not in c_modes)
            return lambda a, b: _dot(a, a_modes, b, b_modes, c_modes, k, prefer)
        beta, rest = todo[0], todo[1:]
        inner = build(
            a_modes.replace(beta, ""), b_modes.replace(beta, ""),
            c_modes.replace(beta, ""), rest,
        )
        in_a = a_modes.index(beta) if beta in a_modes else None
        in_b = b_modes.index(beta) if beta in b_modes else None
        out_c = c_modes.index(beta)
        return jax.vmap(inner, in_axes=(in_a, in_b), out_axes=out_c)

    return build(fs.a_modes, fs.b_modes, fs.c_modes, batch_modes)(A, B)


def _direct(cs: ContractionSpec, A, B, prefer):
    """One dot_general: shared modes as dot batch dims, then lazy transpose."""
    shared = cs.batch
    k = cs.contracted
    a_k = tuple(cs.a_modes.index(m) for m in k)
    b_k = tuple(cs.b_modes.index(m) for m in k)
    a_b = tuple(cs.a_modes.index(m) for m in shared)
    b_b = tuple(cs.b_modes.index(m) for m in shared)
    out = lax.dot_general(A, B, ((a_k, b_k), (a_b, b_b)), preferred_element_type=prefer)
    a_free = [m for m in cs.a_modes if m not in set(k) | set(shared)]
    b_free = [m for m in cs.b_modes if m not in set(k) | set(shared)]
    natural = shared + "".join(a_free) + "".join(b_free)
    if natural != cs.c_modes:
        out = jnp.transpose(out, [natural.index(m) for m in cs.c_modes])
    return out


# --------------------------------------------------------------------------
# Conventional (matricization) baseline
# --------------------------------------------------------------------------

def _conventional(cs: ContractionSpec, A, B, dims: dict, prefer):
    """Explicit-copy matricization: permute to ``C_IJ = A_IK B_KJ``, flat
    GEMM, permute back.  Shared batch modes (in A, B *and* C — absent
    from the paper's Table II regime but legal specs) ride along as a
    leading batch group ``T`` on both matricized operands: per batch
    entry the evaluation is still the textbook permute–GEMM–permute.
    Returns (result, n_materialized_transposes)."""
    k = cs.contracted
    T = "".join(m for m in cs.c_modes if m in cs.batch)
    I = "".join(m for m in cs.c_modes if m in cs.a_modes and m not in T)
    J = "".join(m for m in cs.c_modes if m in cs.b_modes and m not in T)
    n_trans = 0

    def permute(x, modes: str, target: str):
        nonlocal n_trans
        if modes == target:
            return x
        perm = [modes.index(m) for m in target]
        n_trans += 1
        # materialize the copy — this is the cost the baseline pays
        return lax.optimization_barrier(jnp.transpose(x, perm))

    a2 = permute(A, cs.a_modes, T + I + k).reshape(
        _prod(dims, T), _prod(dims, I), _prod(dims, k)
    )
    b2 = permute(B, cs.b_modes, T + k + J).reshape(
        _prod(dims, T), _prod(dims, k), _prod(dims, J)
    )
    c2 = jnp.matmul(a2, b2, preferred_element_type=prefer)
    c = c2.reshape(tuple(dims[m] for m in T + I + J))
    out = permute(c, T + I + J, cs.c_modes)
    return out, n_trans


def _prod(dims: dict, modes: str) -> int:
    p = 1
    for m in modes:
        p *= dims[m]
    return p


def conventional_transpose_count(spec: str | ContractionSpec) -> int:
    """How many materialized permutes the conventional approach performs.

    Counts the explicit copies of the matricization baseline (permute A
    into ``I×K`` form, B into ``K×J`` form, and the result back into the
    requested output order) — the paper's Fig. 1 motivation: each one is
    pure memory traffic the strided-batched evaluation never pays.
    """
    cs = parse_spec(spec) if isinstance(spec, str) else spec
    k = cs.contracted
    T = "".join(m for m in cs.c_modes if m in cs.batch)
    I = "".join(m for m in cs.c_modes if m in cs.a_modes and m not in T)
    J = "".join(m for m in cs.c_modes if m in cs.b_modes and m not in T)
    n = 0
    n += cs.a_modes != T + I + k
    n += cs.b_modes != T + k + J
    n += cs.c_modes != T + I + J
    return int(n)


# --------------------------------------------------------------------------
# HLO introspection (used by tests + the Fig.1/Fig.3 benchmarks)
# --------------------------------------------------------------------------

def count_hlo_ops(fn, *args, ops=("transpose", "copy")) -> dict:
    """Count occurrences of given HLO op kinds in the *optimized* module.

    Jit-lowers ``fn(*args)``, compiles it, and scans the optimized HLO
    text — the tests and the Fig. 1/Fig. 3 benchmarks use this to verify
    that engine-planned contractions really compile transpose-free while
    the conventional baseline's copies survive into the executable.
    """
    lowered = jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    counts = {}
    for op in ops:
        counts[op] = sum(
            1 for line in text.splitlines()
            if f" {op}(" in line or f"= {op}" in line.replace(f"{op}.", op)
        )
    return counts
