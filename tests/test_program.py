"""Contraction-program IR: construction, each pass in isolation, CSE and
buffer-donation correctness, and program-cache behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.einsum import contraction_path, xeinsum
from repro.core.passes import (
    CSEPass,
    DEFAULT_PIPELINE,
    LayoutTieBreakPass,
    LivenessPass,
    PassContext,
    PathOptimizationPass,
    ShardPlacementPass,
    TunedRerankPass,
    run_pipeline,
)
from repro.core.program import (
    CompiledProgram,
    ProgramOptions,
    build_program,
    clear_program_cache,
    compile_program,
    program_cache_stats,
    propagate_shapes,
    record_programs,
)
from repro.tuning import Dispatcher, set_dispatcher
from repro.launch.mesh import make_mesh


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_program_cache()
    set_dispatcher(None)
    yield
    clear_program_cache()
    set_dispatcher(None)


def _rand(seed, shape, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _ctx(**kw):
    return PassContext(options=ProgramOptions(**kw))


# --------------------------------------------------------------- IR building
def test_build_program_structure_and_describe():
    T, W = _rand(0, (4, 5, 6)), _rand(1, (6, 3))
    prog = build_program(
        {"T": T, "W": W},
        [("y", "mnk,kr->mnr", ("T", "W")),
         ("g", "mnr,qnr->mq", ("y", "y"))],
        outputs=("g",),
    )
    assert prog.input_names == ("T", "W")
    assert [s.op for s in prog.steps] == ["einsum", "einsum"]
    shapes, dtypes = propagate_shapes(prog)
    assert shapes["y"] == (4, 5, 3) and shapes["g"] == (4, 4)
    assert dtypes["g"] == jnp.float32
    text = prog.describe()
    assert "T:float32[4, 5, 6]" in text and "-> (g)" in text


def test_build_program_validation_errors():
    T = _rand(0, (4, 5, 6))
    with pytest.raises(ValueError, match="unknown buffer"):
        build_program({"T": T}, [("y", "mnk,kr->mnr", ("T", "W"))])
    with pytest.raises(ValueError, match="operands"):
        build_program({"T": T}, [("y", "mnk,kr->mnr", ("T",))])
    with pytest.raises(ValueError, match="duplicate"):
        build_program({"T": T}, [("T", "mnk->knm", ("T",))])
    with pytest.raises(ValueError, match="not a program buffer"):
        build_program({"T": T}, [("y", "mnk->knm", ("T",))], outputs=("z",))
    with pytest.raises(ValueError, match="rank mismatch"):
        build_program({"T": T}, [("y", "mn->nm", ("T",))])
    with pytest.raises(ValueError, match="at least one expression"):
        build_program({"T": T}, [])


def test_compile_rejects_operands_with_program():
    prog = build_program({"T": _rand(0, (3, 4))}, [("y", "mn->nm", ("T",))])
    with pytest.raises(ValueError, match="spec string"):
        compile_program(prog, _rand(0, (3, 4)))


# ----------------------------------------------------------- end-to-end exec
def test_single_expression_matches_einsum():
    ops = [_rand(i, s) for i, s in enumerate([(6, 8, 10), (10, 4), (6, 5)])]
    ref = jnp.einsum("mnk,kr,ms->nrs", *ops)
    prog = compile_program("mnk,kr,ms->nrs", *ops)
    np.testing.assert_allclose(np.asarray(prog(*ops)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # eager interpreter and jitted executable agree
    np.testing.assert_allclose(np.asarray(prog.eager(*ops)[0]),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_multi_output_program_and_shared_stage():
    T, B, C = _rand(0, (6, 7, 8)), _rand(1, (7, 3)), _rand(2, (8, 4))
    prog = compile_program(build_program(
        {"T": T, "C": C, "B": B},
        [("t1", "mnp,pk->mnk", ("T", "C")),
         ("y1", "mnk,nj->mjk", ("t1", "B"))],
        outputs=("y1", "t1"),
    ))
    y1, t1 = prog(T, C, B)
    ref_t1 = jnp.einsum("mnp,pk->mnk", T, C)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(ref_t1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(y1), np.asarray(jnp.einsum("mnk,nj->mjk", ref_t1, B)),
        rtol=1e-4, atol=1e-4,
    )


def test_xeinsum_is_bit_identical_to_compiled_program():
    ops = [_rand(i, s) for i, s in enumerate([(5, 6, 7), (7, 3), (6, 3)])]
    prog = compile_program("mnp,pk,nj->mjk", *ops)
    assert np.array_equal(
        np.asarray(prog(*ops)), np.asarray(xeinsum("mnp,pk,nj->mjk", *ops))
    )


def test_operand_validation_at_call_time():
    A, B = _rand(0, (4, 5)), _rand(1, (5, 6))
    prog = compile_program("ab,bc->ac", A, B)
    with pytest.raises(ValueError, match="takes 2 operands"):
        prog(A)
    with pytest.raises(ValueError, match="compiled for shape"):
        prog(A, _rand(2, (5, 7)))


# ------------------------------------------------------------ passes, alone
def test_path_optimization_pass_expands_and_orders():
    shapes = [(64, 2), (2, 64), (64, 2)]
    ops = [_rand(i, s) for i, s in enumerate(shapes)]
    prog = build_program(
        {"a": ops[0], "b": ops[1], "c": ops[2]},
        [("out", "ab,bc,cd->ad", ("a", "b", "c"))],
    )
    ctx = _ctx(optimize="optimal")
    planned = PathOptimizationPass().run(prog, ctx)
    assert [s.op for s in planned.steps] == ["contract", "contract"]
    # the cheap pair (b, c) contracts first — the thin–fat–thin chain
    assert set(planned.steps[0].args) == {"b", "c"}
    naive = PathOptimizationPass().run(prog, _ctx(optimize="naive"))
    assert set(naive.steps[0].args) == {"a", "b"}
    assert sum(s.flops for s in planned.steps) < sum(
        s.flops for s in naive.steps
    )


def test_path_optimization_pass_sum_only_and_single_operand():
    A = _rand(0, (3, 9))
    prog = build_program({"A": A}, [("out", "aq->a", ("A",))])
    planned = PathOptimizationPass().run(prog, _ctx())
    assert [s.op for s in planned.steps] == ["reduce", "transpose"]
    assert planned.steps[0].axes == (1,)
    got = compile_program(prog)(A)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("aq->a", A)),
                               rtol=1e-5, atol=1e-5)


def test_layout_tie_break_pass_annotates_kinds():
    G, A, B, C = (_rand(i, s) for i, s in enumerate(
        [(10, 10, 10), (96, 10), (96, 10), (96, 10)]
    ))
    prog = build_program(
        {"G": G, "A": A, "B": B, "C": C},
        [("out", "ijk,mi,nj,pk->mnp", ("G", "A", "B", "C"))],
    )
    ctx = _ctx(optimize="optimal")
    planned = PathOptimizationPass().run(prog, ctx)
    annotated = LayoutTieBreakPass().run(planned, ctx)
    kinds = [s.kind for s in annotated.steps if s.op == "contract"]
    assert kinds and all(k for k in kinds)
    assert all(k != "exceptional" for k in kinds)
    assert all(s.penalty >= 0 for s in annotated.steps if s.op == "contract")


def test_tuned_rerank_pass_prefers_measured_path():
    """Seed the tuning cache so the naive path's steps look measured-fast;
    the re-rank pass must then splice the naive order in."""
    from repro.tuning.cache import canonical_key

    shapes = [(64, 2), (2, 64), (64, 2)]
    ops = [_rand(i, s) for i, s in enumerate(shapes)]
    prog = build_program(
        {"a": ops[0], "b": ops[1], "c": ops[2]},
        [("out", "ab,bc,cd->ad", ("a", "b", "c"))],
    )
    disp = Dispatcher(None, policy="cached")
    set_dispatcher(disp)
    naive = contraction_path("ab,bc,cd->ad", *shapes, optimize="naive")
    for s in naive.steps:
        dims = {m: naive.dims[m] for m in set(s.spec.a_modes + s.spec.b_modes)}
        disp.cache.put(
            canonical_key(s.spec, naive.dims, jnp.float32),
            {"best": "xla:auto", "results": {"xla:auto": 0.001}},
        )
    ctx = _ctx(optimize="tuned")
    planned = PathOptimizationPass().run(prog, ctx)
    assert set(planned.steps[0].args) == {"b", "c"}  # auto's choice first
    reranked = TunedRerankPass().run(planned, ctx)
    assert set(reranked.steps[0].args) == {"a", "b"}  # measured naive wins
    # and the re-ranked program still computes the right thing
    final = LivenessPass().run(reranked, ctx)
    got = CompiledProgram(final, ctx.options, ("t",), lambda *a: None).eager(*ops)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(jnp.einsum("ab,bc,cd->ad", *ops)),
        rtol=1e-4, atol=1e-4,
    )


def test_shard_placement_pass_annotates_pspecs():
    """On a 1-device mesh the placement machinery runs end to end (specs
    thread through the DAG) without needing simulated devices."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("x",))
    A, B, C = _rand(0, (4, 6)), _rand(1, (6, 8)), _rand(2, (8, 2))
    prog = build_program(
        {"A": A, "B": B, "C": C},
        [("out", "ab,bc,cd->ad", ("A", "B", "C"))],
    )
    ctx = _ctx(mesh=mesh, in_specs=(P("x", None), P(None, None), None),
               out_specs=(None,))
    planned = PathOptimizationPass().run(prog, ctx)
    placed = ShardPlacementPass().run(planned, ctx)
    contracts = [s for s in placed.steps if s.op == "contract"]
    assert all(len(s.in_pspecs) == 2 for s in contracts)
    assert all(s.out_pspec is not None for s in contracts)
    got = compile_program(prog, mesh=mesh,
                          in_specs=(P("x", None), P(None, None), None))(A, B, C)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.einsum("ab,bc,cd->ad", A, B, C)),
        rtol=1e-4, atol=1e-4,
    )


def test_cse_pass_merges_repeated_subexpressions():
    T, W = _rand(0, (5, 6, 7)), _rand(1, (7, 3))
    prog = build_program(
        {"T": T, "W": W},
        [("a1", "mnk,kr->mnr", ("T", "W")),
         ("a2", "mnk,kr->mnr", ("T", "W")),      # duplicate of a1
         ("g", "mnr,qnr->mq", ("a1", "a2"))],
        outputs=("g",),
    )
    ctx = _ctx()
    planned = PathOptimizationPass().run(prog, ctx)
    assert len([s for s in planned.steps if s.op == "contract"]) == 3
    deduped = CSEPass().run(planned, ctx)
    assert len([s for s in deduped.steps if s.op == "contract"]) == 2
    # the gram's operands were rewired to the surviving buffer
    gram = next(s for s in deduped.steps if s.out == "g")
    assert gram.args == ("a1", "a1")
    t1 = jnp.einsum("mnk,kr->mnr", T, W)
    got = compile_program(prog)(T, W)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.einsum("mnr,qnr->mq", t1, t1)),
        rtol=1e-3, atol=1e-3,
    )


def test_cse_does_not_merge_different_strategies():
    T, W = _rand(0, (5, 6, 7)), _rand(1, (7, 3))
    prog = build_program(
        {"T": T, "W": W},
        [("a1", "mnk,kr->mnr", ("T", "W")),
         ("a2", "mnk,kr->mnr", ("T", "W"), {"strategy": "direct"}),
         ("g", "mnr,qnr->mq", ("a1", "a2"))],
        outputs=("g",),
    )
    ctx = _ctx()
    steps = CSEPass().run(PathOptimizationPass().run(prog, ctx), ctx).steps
    assert len([s for s in steps if s.op == "contract"]) == 3


def test_liveness_pass_marks_last_uses_and_keeps_outputs():
    T, B, C = _rand(0, (5, 6, 7)), _rand(1, (6, 3)), _rand(2, (7, 4))
    prog = build_program(
        {"T": T, "C": C, "B": B},
        [("t1", "mnp,pk->mnk", ("T", "C")),
         ("y1", "mnk,nj->mjk", ("t1", "B"))],
        outputs=("y1", "t1"),
    )
    ctx = _ctx()
    final = LivenessPass().run(PathOptimizationPass().run(prog, ctx), ctx)
    freed = [n for s in final.steps for n in s.last_uses]
    assert "C" in freed and "B" in freed and "T" in freed
    assert "t1" not in freed and "y1" not in freed  # outputs stay live


# ------------------------------------------------------------------ donation
def test_donation_releases_input_buffer():
    A, B = _rand(0, (32, 32)), _rand(1, (32, 32))
    prog = compile_program("ab,bc->ac", A, B, donate=("%0",))
    ref = jnp.einsum("ab,bc->ac", A, B)
    got = prog(A, B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert A.is_deleted()          # buffer handed to XLA for reuse
    assert not B.is_deleted()


def test_donation_validation():
    A, B = _rand(0, (4, 4)), _rand(1, (4, 4))
    with pytest.raises(ValueError, match="not a program input"):
        compile_program("ab,bc->ac", A, B, donate=("nope",))
    prog = build_program(
        {"A": A, "B": B}, [("y", "ab,bc->ac", ("A", "B"))],
        outputs=("y", "A"),
    )
    with pytest.raises(ValueError, match="program output"):
        compile_program(prog, donate=("A",))


# ------------------------------------------------------------- program cache
def test_program_cache_hits_and_shape_misses():
    A, B = _rand(0, (4, 5)), _rand(1, (5, 6))
    p1 = compile_program("ab,bc->ac", A, B)
    base = program_cache_stats()
    p2 = compile_program("ab,bc->ac", A, B)
    assert p2 is p1
    assert program_cache_stats()["hits"] == base["hits"] + 1
    p3 = compile_program("ab,bc->ac", _rand(2, (7, 5)), B)
    assert p3 is not p1
    assert program_cache_stats()["misses"] == base["misses"] + 1


def test_xeinsum_populates_program_cache():
    A, B, C = _rand(0, (4, 5)), _rand(1, (5, 6)), _rand(2, (6, 3))
    xeinsum("ab,bc,cd->ad", A, B, C)
    before = program_cache_stats()
    xeinsum("ab,bc,cd->ad", A, B, C)
    after = program_cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


def test_identical_plans_share_the_jitted_executor():
    A, B = _rand(0, (4, 5)), _rand(1, (5, 6))
    compile_program("ab,bc->ac", A, B, optimize="auto")
    n = program_cache_stats()["executors"]
    # two operands: every optimizer plans the same single step
    compile_program("ab,bc->ac", A, B, optimize="greedy")
    stats = program_cache_stats()
    assert stats["programs"] == 2 and stats["executors"] == n


def test_custom_pipeline_bypasses_program_cache():
    """Pass identity is not in the canonical signature, so a custom
    pipeline must not poison the cache for default-pipeline callers."""
    A, B = _rand(0, (4, 5)), _rand(1, (5, 6))
    partial = compile_program("ab,bc->ac", A, B,
                              pipeline=(PathOptimizationPass(),))
    assert program_cache_stats()["programs"] == 0
    full = compile_program("ab,bc->ac", A, B)
    assert full is not partial
    assert any(s.last_uses for s in full.program.steps)   # liveness ran
    assert not any(s.last_uses for s in partial.program.steps)


def test_record_programs_sees_hits_and_misses():
    A, B = _rand(0, (4, 5)), _rand(1, (5, 6))
    with record_programs() as rec:
        compile_program("ab,bc->ac", A, B)
        compile_program("ab,bc->ac", A, B)
    assert len(rec) == 2 and rec[0] is rec[1]


def test_pipeline_runs_to_fixed_valid_program():
    T, W, U = (_rand(i, s) for i, s in enumerate(
        [(6, 8, 10), (10, 4), (6, 5)]
    ))
    prog = build_program(
        {"T": T, "W": W, "U": U},
        [("out", "mnk,kr,ms->nrs", ("T", "W", "U"))],
    )
    final = run_pipeline(prog, ProgramOptions())
    assert all(s.op != "einsum" for s in final.steps)
    final.validate()
    assert len(DEFAULT_PIPELINE) == 6


# ----------------------------------------------------------- tuned programs
def test_tuned_cache_change_invalidates_program_and_executor():
    """A tuning-cache change must mint a new program AND a new jitted
    executor — the executor bakes the dispatcher's winners in at trace
    time, so sharing it across cache states would pin stale winners."""
    A, B = _rand(0, (8, 8)), _rand(1, (8, 8))
    set_dispatcher(Dispatcher(None, policy="cached"))
    p1 = compile_program("ab,bc->ac", A, B, strategy="tuned")
    set_dispatcher(Dispatcher(None, policy="cached"))  # same size, new cache
    p2 = compile_program("ab,bc->ac", A, B, strategy="tuned")
    assert p2 is not p1
    assert p2._jit is not p1._jit



def test_tuned_strategy_measures_once_then_runs_jitted(tmp_path):
    A, B = _rand(0, (12, 16)), _rand(1, (4, 16, 8))
    disp = Dispatcher(tmp_path / "t.json", backends=("xla",),
                      iters=1, warmup=1)
    set_dispatcher(disp)
    ref = jnp.einsum("mk,pkn->pmn", A, B)
    got = xeinsum("mk,pkn->pmn", A, B, strategy="tuned")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert disp.measurements > 0          # eager fallback measured the miss
    before = disp.measurements
    got = xeinsum("mk,pkn->pmn", A, B, strategy="tuned")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert disp.measurements == before    # warm cache: jitted path, no timing
