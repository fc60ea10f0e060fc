"""Autotuner subsystem: candidates, measurement, cache durability, dispatch.

All tests run XLA-only candidates at tiny sizes (Pallas interpret mode is
exercised separately via the tiles-plumbing tests) so the module stays
fast on CPU CI.
"""

import json
import os
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.tuning.dispatch as dispatch_mod
from repro.core.contract import contract, record_contractions
from repro.core.einsum import contraction_path, xeinsum
from repro.core.notation import parse_spec
from repro.tuning import (
    SCHEMA_VERSION,
    Candidate,
    Dispatcher,
    TuningCache,
    canonical_key,
    enumerate_candidates,
    pick_best,
    set_dispatcher,
    tuned_contract,
    validate_tiles,
)

SPEC = "mk,pkn->pmn"
DIMS = {"m": 12, "k": 16, "p": 4, "n": 8}


def _operands(spec=SPEC, dims=DIMS, dtype=jnp.float32, seed=0):
    cs = parse_spec(spec)
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal([dims[m] for m in cs.a_modes]), dtype)
    B = jnp.asarray(rng.standard_normal([dims[m] for m in cs.b_modes]), dtype)
    return A, B


def _disp(cache=None, **kw):
    kw.setdefault("backends", ("xla",))
    kw.setdefault("iters", 1)
    kw.setdefault("warmup", 1)
    return Dispatcher(cache, **kw)


@pytest.fixture(autouse=True)
def _no_global_dispatcher():
    set_dispatcher(None)
    yield
    set_dispatcher(None)


# ---------------------------------------------------------------- candidates
def test_candidates_all_execute_and_agree():
    A, B = _operands()
    ref = jnp.einsum(SPEC, A, B)
    cands = enumerate_candidates(SPEC, DIMS, backends=("xla", "pallas"))
    assert any(c.backend == "pallas" for c in cands)
    for c in cands:
        got = contract(SPEC, A, B, strategy=c.strategy, backend=c.backend,
                       tiles=c.tiles_dict or None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_candidate_key_round_trip():
    for c in enumerate_candidates(SPEC, DIMS, backends=("xla", "pallas")):
        assert Candidate.from_key(c.key()) == c


def test_candidates_scalar_spec_degrades_to_direct():
    cands = enumerate_candidates("k,k->", {"k": 7}, backends=("xla", "pallas"))
    assert cands == [Candidate("direct", "xla")]


def test_all_pallas_candidates_pass_contract_validation():
    # a candidate the enumerator emits must never be rejected at execution
    # time — contract(tiles=...) applies the same gate to the override
    from repro.core.planner import make_plan
    from repro.core.table2 import CASES
    from repro.tuning.candidates import (
        validate_native_tiles, validate_plan_tiles,
    )

    for label in ("1.3", "3.4"):  # sb_gemm and exceptional regimes
        rm = CASES[label].row_major()
        cs = parse_spec(rm)
        dims = {m: 256 if m in "kn" else 32 for m in set(cs.a_modes + cs.b_modes)}
        for c in enumerate_candidates(rm, dims, backends=("xla", "pallas")):
            if not c.tiles:
                continue
            if c.strategy == "native":  # must not raise
                validate_native_tiles(cs, dims, c.tiles_dict)
                continue
            plan = make_plan(cs, dims, allow_flatten=c.strategy == "auto")
            validate_plan_tiles(plan, c.tiles_dict, jnp.float32)


def test_tiles_validated_as_the_kernel_raises_them():
    # Table II 3.4 at 512: its batch mode is an operand's lane axis, so a
    # b=8 brick runs 128 deep.  The role formula passes the tiles as
    # requested; the gate must see the blocks the kernel would run.
    from repro.core.table2 import CASES

    rm = CASES["3.4"].row_major()
    cs = parse_spec(rm)
    A, B = (jax.ShapeDtypeStruct((512,) * len(modes), jnp.bfloat16)
            for modes in (cs.a_modes, cs.b_modes))
    tiles = {"b": 8, "u": 256, "k": 256}
    validate_tiles(tiles)  # fits as requested
    with pytest.raises(ValueError, match="as the kernel runs them"):
        jax.eval_shape(lambda a, b: contract(
            rm, a, b, strategy="batched", backend="pallas", tiles=tiles), A, B)


def test_exceptional_case_gets_brick_candidates():
    # row-major mirror of Table II case 3.4 plans as exceptional
    from repro.core.table2 import CASES

    # Its batch mode is the 3D operand's stride-1 (lane) axis, so the TPU
    # block rule fixes the brick at one lane tile, or the whole mode when
    # shorter: every emitted candidate runs that brick.
    from repro.core.planner import make_plan
    from repro.kernels.addressing import effective_tile, role_mode_tiles
    from repro.kernels.ops import EXT_BATCH_TILE, plan_roles

    rm = CASES["3.4"].row_major()
    cs = parse_spec(rm)
    for n, brick in ((16, 16), (512, 128)):
        dims = {m: n for m in set(cs.a_modes + cs.b_modes)}
        plan = make_plan(cs, dims, allow_flatten=False)
        roles = plan_roles(plan)
        cands = [c for c in enumerate_candidates(rm, dims,
                                                 backends=("xla", "pallas"))
                 if c.backend == "pallas" and c.strategy != "native"]
        assert cands
        for c in cands:
            tiles = {"b": EXT_BATCH_TILE, **c.tiles_dict}
            fs = plan.fspec
            mt = role_mode_tiles(fs.a_modes, fs.b_modes, fs.c_modes, dims,
                                 roles, tiles)
            assert effective_tile(n, mt[plan.sb_batch]) == brick, c.key()


def test_native_candidates_enumerated_and_execute():
    """The ``native`` strategy is a pallas candidate for every non-scalar
    spec — including the multi-k and batch-minor classes that have no
    role-based sb_gemm lowering at all — and every emitted candidate
    executes to the einsum answer."""
    cases = [
        (SPEC, DIMS),
        ("mkj,jkn->nm", {"m": 8, "k": 4, "j": 5, "n": 8}),  # unfused k-group
        ("mq,qn->qnm", {"m": 6, "q": 5, "n": 4}),           # batch-minor out
    ]
    for spec, dims in cases:
        cands = enumerate_candidates(spec, dims, backends=("xla", "pallas"))
        native = [c for c in cands if c.strategy == "native"]
        assert native, f"no native candidates for {spec}"
        assert all(c.backend == "pallas" for c in native)
        A, B = _operands(spec, dims)
        ref = jnp.einsum(spec, A, B)
        for c in native:
            got = contract(spec, A, B, strategy="native",
                           tiles=c.tiles_dict or None)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{spec} {c.key()}")


def test_native_vmem_validated_at_enumeration_not_launch():
    """Satellite check: the per-mode VMEM estimate.  A two-batch-brick
    spec blows past the budget under tiles the 4-role formula accepts —
    the native validator must reject it at enumeration/call time, and the
    enumerator must never emit a config it would reject."""
    from repro.tuning.candidates import (
        VMEM_BUDGET_BYTES, estimate_native_vmem_bytes, validate_native_tiles,
    )

    spec = "tsmk,tskn->tsmn"
    dims = {m: 64 for m in "tsmkn"}
    tiles = {"u": 64, "v": 64, "k": 64, "b": 32}
    validate_tiles(tiles)  # the role-level check passes this config
    with pytest.raises(ValueError, match="native tiles .* oversized"):
        validate_native_tiles(spec, dims, tiles)
    # the same gate guards the public API before any kernel launch
    A, B = _operands(spec, dims)
    with pytest.raises(ValueError, match="native tiles .* oversized"):
        contract(spec, A, B, strategy="native", tiles=tiles)
    # role-name/value rules still apply to native overrides
    with pytest.raises(ValueError, match="unknown tile roles"):
        validate_native_tiles(spec, dims, {"q": 8})
    # enumeration applies the same estimate: emitted ⇒ within budget
    for c in enumerate_candidates(spec, dims, backends=("xla", "pallas")):
        if c.strategy == "native":
            assert estimate_native_vmem_bytes(
                spec, dims, c.tiles_dict, jnp.float32
            ) <= VMEM_BUDGET_BYTES


def test_pre_native_cache_incremental_retune(tmp_path):
    """Schema-growth round-trip: a cache written before the ``native``
    strategy existed loads cleanly, and re-tuning measures ONLY the new
    candidate keys — prior timings survive verbatim."""
    path = tmp_path / "t.json"
    A, B = _operands()
    d1 = _disp(path, backends=("xla", "pallas"))
    d1.contract(SPEC, A, B)
    ((key, entry),) = d1.cache.entries.items()
    native_keys = {k for k in entry["results"]
                   if k.startswith("pallas:native")}
    assert native_keys  # this spec does get native candidates
    # rewrite the entry as a pre-native cache would have recorded it,
    # with distinctive timings so preservation is provable
    pre = {k: round(v + 1000.0, 3) for k, v in entry["results"].items()
           if k not in native_keys}
    d1.cache.put(key, {"best": "xla:auto", "results": pre})

    d2 = _disp(path, backends=("xla", "pallas"))
    entry2 = d2.tune(SPEC, A, B)
    assert d2.measurements == len(native_keys)  # only the new candidates
    assert set(entry2["results"]) == set(pre) | native_keys
    for k, v in pre.items():
        assert entry2["results"][k] == v        # old µs kept verbatim
    assert entry2["best"] in entry2["results"]
    # steady state: the grown entry is a plain hit — nothing re-measures
    d2.contract(SPEC, A, B)
    assert d2.hits == 1 and d2.measurements == len(native_keys)


# --------------------------------------------------------------------- tiles
def test_tiles_plumbing_end_to_end():
    A, B = _operands()
    ref = jnp.einsum(SPEC, A, B)
    got = contract(SPEC, A, B, strategy="batched", backend="pallas",
                   tiles={"u": 16, "v": 8, "k": 8})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    got = xeinsum(SPEC, A, B, strategy="batched", backend="pallas",
                  tiles={"u": 16})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tiles,msg", [
    ({"q": 8}, "unknown tile roles"),
    ({"u": 0}, "positive int"),
    ({"u": 8.0}, "positive int"),
    ({"k": 12}, "not divisible by 8"),
    ({"u": 4096, "v": 4096, "k": 4096}, "oversized"),
])
def test_tiles_validation_errors(tiles, msg):
    A, B = _operands()
    with pytest.raises(ValueError, match=msg):
        contract(SPEC, A, B, strategy="batched", backend="pallas", tiles=tiles)


def test_tiles_require_pallas_and_planning_strategy():
    A, B = _operands()
    with pytest.raises(ValueError, match="backend='pallas'"):
        contract(SPEC, A, B, strategy="batched", tiles={"u": 8})
    with pytest.raises(ValueError, match="meaningless"):
        contract(SPEC, A, B, strategy="direct", backend="pallas", tiles={"u": 8})
    with pytest.raises(ValueError, match="tuned"):
        contract(SPEC, A, B, strategy="tuned", tiles={"u": 8})
    validate_tiles({"u": 64, "v": 128, "k": 8, "b": 2})  # legal: no raise


def test_xeinsum_rejects_misplaced_tiles():
    A, B = _operands()
    with pytest.raises(ValueError, match="backend='pallas'"):
        xeinsum(SPEC, A, B, tiles={"u": 8})  # default backend is xla
    with pytest.raises(ValueError, match="tuned"):
        xeinsum(SPEC, A, B, strategy="tuned", tiles={"u": 8})
    with pytest.raises(ValueError, match="not divisible by 8"):
        xeinsum(SPEC, A, B, strategy="batched", backend="pallas",
                tiles={"u": 9})


def test_exceptional_tiles_validated_at_kernel_brick_depth():
    # tiles that fit VMEM at b=1 must still be rejected when the plan is
    # exceptional (execute_plan defaults the brick depth to 8)
    from repro.core.table2 import CASES

    rm = CASES["3.4"].row_major()
    cs = parse_spec(rm)
    dims = {m: 16 for m in set(cs.a_modes + cs.b_modes)}
    A, B = _operands(rm, dims)
    tiles = {"u": 512, "v": 512, "k": 64}
    validate_tiles(tiles)  # fits at b=1
    with pytest.raises(ValueError, match="oversized"):
        contract(rm, A, B, strategy="batched", backend="pallas", tiles=tiles)


# --------------------------------------------------------------------- cache
def test_cache_round_trip(tmp_path):
    path = tmp_path / "t.json"
    c1 = TuningCache(path)
    entry = {"best": "xla:auto", "results": {"xla:auto": 12.5, "xla:direct": 20.0}}
    c1.put("k1", entry)
    c2 = TuningCache(path)
    assert c2.get("k1") == entry
    assert "k1" in c2 and len(c2) == 1


def test_cache_atomic_write_survives_crash(tmp_path, monkeypatch):
    path = tmp_path / "t.json"
    c1 = TuningCache(path)
    good = {"best": "xla:auto", "results": {"xla:auto": 1.0}}
    c1.put("k1", good)

    monkeypatch.setattr(os, "replace",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("crash")))
    with pytest.raises(OSError):
        c1.put("k2", {"best": "xla:auto", "results": {"xla:auto": 2.0}})
    monkeypatch.undo()

    # the file on disk is the last complete snapshot — parseable, k1 intact
    c2 = TuningCache(path)
    assert c2.get("k1") == good
    assert "k2" not in c2


def test_cache_corrupted_file_degrades_to_empty(tmp_path):
    path = tmp_path / "t.json"
    path.write_text("{not json!!")
    with pytest.warns(UserWarning, match="unreadable"):
        c = TuningCache(path)
    assert len(c) == 0


def test_cache_old_schema_degrades_to_empty(tmp_path):
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"schema": SCHEMA_VERSION + 1, "entries": {"k": {}}}))
    with pytest.warns(UserWarning, match="schema"):
        c = TuningCache(path)
    assert len(c) == 0


def test_cache_malformed_entries_dropped(tmp_path):
    path = tmp_path / "t.json"
    good = {"best": "xla:auto", "results": {"xla:auto": 1.0}}
    path.write_text(json.dumps({
        "schema": SCHEMA_VERSION,
        "entries": {
            "ok": good,
            "bad": {"results": "nope"},
            # "best" not among the results: lookup would KeyError
            "dangling": {"best": "xla:direct", "results": {"xla:auto": 5.0}},
            # "best" not a parseable candidate key: lookup would ValueError
            "garbage": {"best": "garbage", "results": {"garbage": 5.0}},
        },
    }))
    with pytest.warns(UserWarning, match="malformed"):
        c = TuningCache(path)
    assert c.get("ok") == good
    assert "bad" not in c and "dangling" not in c and "garbage" not in c


# ------------------------------------------------------------------ dispatch
def test_lookup_dangling_entry_warns_once_and_misses():
    """A structurally dangling entry (in-memory mutation; put() and the
    loader both reject them) must read as a miss with one warning, never
    a KeyError on the serve path."""
    dispatch_mod._WARNED_DANGLING.clear()
    d = _disp(None)
    key = canonical_key(SPEC, DIMS, jnp.float32)
    d.cache.entries[key] = {"best": "xla:direct", "results": {"xla:auto": 5.0}}
    with pytest.warns(UserWarning, match="dangling"):
        assert d.lookup(SPEC, DIMS, jnp.float32) is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second lookup: silent miss
        assert d.lookup(SPEC, DIMS, jnp.float32) is None
    # contract() treats it as a cold key: re-tunes and repairs the entry
    A, B = _operands()
    got = d.contract(SPEC, A, B)
    assert d.misses == 1 and d.measurements > 0  # direct lookups don't count
    entry = d.cache.get(key)
    assert entry["best"] in entry["results"]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum(SPEC, A, B)),
                               rtol=2e-5, atol=2e-5)


def test_audit_transposes_stored_in_entry(tmp_path):
    d = _disp(tmp_path / "t.json", audit_transposes=True)
    A, B = _operands()
    entry = d.tune(SPEC, A, B)
    assert set(entry["transposes"]) == set(entry["results"])
    assert all(isinstance(v, int) and v >= 0
               for v in entry["transposes"].values())
    # counts survive the JSON round trip next to the timings
    reloaded = TuningCache(tmp_path / "t.json").get(
        canonical_key(SPEC, DIMS, jnp.float32))
    assert reloaded["transposes"] == entry["transposes"]


# ------------------------------------------------------------------ policies
def test_unknown_policy_refused():
    with pytest.raises(ValueError, match="unknown tuning policy"):
        Dispatcher(None, policy="predict")
    d = Dispatcher(None, policy="cached")
    with pytest.raises(ValueError, match="bogus"):
        d.policy = "bogus"
    assert d.policy == "cached"


def test_pick_best_ties_toward_auto():
    # the fastest candidate wins when it beats auto by more than the margin
    assert pick_best({"xla:auto": 10.0, "xla:direct": 4.0,
                      "xla:flat": 9.0}) == "xla:direct"
    # ... but a hair-thin challenger still loses to auto
    assert pick_best({"xla:auto": 10.0, "xla:direct": 9.5}) == "xla:auto"
    # without auto in the map, the fastest wins outright
    assert pick_best({"xla:direct": 9.5, "xla:flat": 9.0}) == "xla:flat"


def test_predicted_entry_loads_and_tune_discards_it(tmp_path):
    """A cache file written by the retired cost-model policy still loads
    and dispatches as a hit, but ``tune`` never keeps its guessed µs."""
    key = canonical_key(SPEC, DIMS, jnp.float32)
    guess = {"best": "xla:direct", "results": {"xla:direct": 0.001},
             "predicted": True, "confidence": 0.9}
    path = tmp_path / "old.json"
    path.write_text(json.dumps({"schema": SCHEMA_VERSION,
                                "entries": {key: guess}}))
    d = _disp(path)
    assert d.cache.get(key) == guess
    cand, us = d.lookup(SPEC, DIMS, jnp.float32)
    assert (cand.key(), us) == ("xla:direct", 0.001)
    A, B = _operands()
    entry = d.tune(SPEC, A, B)
    assert "predicted" not in entry
    assert entry["results"]["xla:direct"] != 0.001
    assert set(entry["results"]) == {
        c.key() for c in enumerate_candidates(
            parse_spec(SPEC), DIMS, dtype=jnp.float32, backends=("xla",))}
    assert TuningCache(path).get(key) == entry


def test_tuned_contract_correct_and_counts(tmp_path):
    A, B = _operands()
    ref = jnp.einsum(SPEC, A, B)
    d = _disp(tmp_path / "t.json")
    got = tuned_contract(SPEC, A, B, dispatcher=d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert d.misses == 1 and d.measurements > 0
    tuned_contract(SPEC, A, B, dispatcher=d)
    assert d.hits == 1


def test_cache_hit_short_circuits_measurement(tmp_path, monkeypatch):
    path = tmp_path / "t.json"
    A, B = _operands()
    _disp(path).contract(SPEC, A, B)  # warm the cache file

    d2 = _disp(path)
    monkeypatch.setattr(
        dispatch_mod, "measure_candidates",
        lambda *a, **k: pytest.fail("measurer called despite cache hit"),
    )
    got = d2.contract(SPEC, A, B)
    assert d2.hits == 1 and d2.measurements == 0
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum(SPEC, A, B)),
                               rtol=2e-5, atol=2e-5)


def test_policy_cached_never_measures():
    A, B = _operands()
    d = _disp(None, policy="cached")
    got = d.contract(SPEC, A, B)  # miss → analytic fallback, no measuring
    assert d.measurements == 0 and d.misses == 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum(SPEC, A, B)),
                               rtol=2e-5, atol=2e-5)


def test_tuned_under_jit_falls_back_without_measuring():
    A, B = _operands()
    d = _disp(None)
    set_dispatcher(d)
    f = jax.jit(lambda a, b: contract(SPEC, a, b, strategy="tuned"))
    got = f(A, B)
    assert d.measurements == 0  # tracers cannot be timed
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum(SPEC, A, B)),
                               rtol=2e-5, atol=2e-5)


def test_canonical_key_mode_renaming():
    k1 = canonical_key("mk,pkn->pmn", DIMS, jnp.float32, "cpu")
    dims2 = {"a": 12, "b": 16, "c": 4, "d": 8}
    k2 = canonical_key("ab,cbd->cad", dims2, jnp.float32, "cpu")
    assert k1 == k2
    assert canonical_key("mk,pkn->pmn", DIMS, jnp.bfloat16, "cpu") != k1


def test_record_contractions_nested_removal_by_identity():
    A, B = _operands()
    with record_contractions() as outer:
        with record_contractions() as inner:
            pass  # both empty → equal lists; exit must remove by identity
        contract(SPEC, A, B)
    assert len(outer) == 1 and inner == []


def test_pretune_from_recorded_working_set(tmp_path):
    A, B = _operands()
    with record_contractions() as rec:
        jax.eval_shape(lambda a, b: contract(SPEC, a, b), A, B)
    assert rec and rec[0][0] == SPEC
    d = _disp(tmp_path / "t.json")
    stats = d.pretune(rec)
    assert stats["unique"] == 1 and stats["tuned"] == 1
    assert d.pretune(rec)["cached"] == 1  # idempotent


# -------------------------------------------------------------------- einsum
def test_xeinsum_optimize_tuned_matches_reference():
    rng = np.random.default_rng(0)
    T = jnp.asarray(rng.standard_normal((6, 8, 10)), jnp.float32)
    W = jnp.asarray(rng.standard_normal((10, 4)), jnp.float32)
    U = jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)
    ref = jnp.einsum("mnk,kr,ms->nrs", T, W, U)

    set_dispatcher(_disp(None))
    # cold cache: analytic fallback ranking
    out = xeinsum("mnk,kr,ms->nrs", T, W, U, optimize="tuned")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # warm the per-step entries, then re-rank from measurements
    xeinsum("mnk,kr,ms->nrs", T, W, U, strategy="tuned")
    out = xeinsum("mnk,kr,ms->nrs", T, W, U, optimize="tuned")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    path = contraction_path("mnk,kr,ms->nrs", T, W, U, optimize="tuned")
    assert path.optimize == "tuned" and len(path.steps) == 2


def test_optimal_error_suggests_auto_and_greedy():
    shapes = [(2, 2)] * 12
    spec = ",".join(["ab", "bc", "cd", "de", "ef", "fg", "gh", "hi", "ij",
                     "jk", "kl", "lm"]) + "->am"
    with pytest.raises(ValueError) as ei:
        contraction_path(spec, *shapes, optimize="optimal")
    assert "greedy" in str(ei.value) and "auto" in str(ei.value)
    assert "REPRO_OPTIMAL_MAX_OPERANDS" in str(ei.value)


def test_optimal_cap_env_override(monkeypatch):
    shapes = [(2, 2)] * 3
    monkeypatch.setenv("REPRO_OPTIMAL_MAX_OPERANDS", "2")
    with pytest.raises(ValueError, match="≤ 2"):
        contraction_path("ab,bc,cd->ad", *shapes, optimize="optimal")
    monkeypatch.setenv("REPRO_OPTIMAL_MAX_OPERANDS", "4")
    path = contraction_path("ab,bc,cd->ad", *shapes, optimize="optimal")
    assert len(path.steps) == 2


# ------------------------------------------------------------------- serving
def test_serve_engine_pretune(tmp_path):
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.serving.engine import ServeEngine

    cfg = get_config("minicpm-2b", smoke=True).with_(n_periods=1)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    path = tmp_path / "t.json"

    eng = ServeEngine(cfg, params, slots=2, max_len=64, pretune=True,
                      tuner=_disp(path))
    assert eng.pretune_stats["unique"] > 0
    assert eng.pretune_stats["tuned"] == eng.pretune_stats["unique"]

    # same cache → warm start: zero new measurements
    tuner2 = _disp(path)
    eng2 = ServeEngine(cfg, params, slots=2, max_len=64, pretune=True,
                      tuner=tuner2)
    assert eng2.pretune_stats["cached"] == eng2.pretune_stats["unique"]
    assert tuner2.measurements == 0


# ------------------------------------------------------- cache concurrency
def _entry(us: float) -> dict:
    return {"best": "xla:auto", "results": {"xla:auto": float(us)}}


def test_cache_interleaved_writers_never_corrupt(tmp_path):
    """Two cache handles on one file, saves interleaved save-for-save.

    Last-writer-wins per save is the accepted semantics (each handle
    rewrites its full view); a *corrupt or torn* file is not.  After every
    single interleaved write the file must reload as a valid cache whose
    entries all pass validation.
    """
    path = os.fspath(tmp_path / "shared.json")
    c1, c2 = TuningCache(path), TuningCache(path)
    for i in range(25):
        c1.put(f"a{i}|4|float32|cpu", _entry(i))
        c2.put(f"b{i}|4|float32|cpu", _entry(100 + i))
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # corruption degrades via warning
            fresh = TuningCache(path)
        assert fresh.entries, "interleaved save produced an empty cache"
        assert all(e["best"] in e["results"] for e in fresh.entries.values())
    # c2 wrote last: its view (which never saw c1's keys) is the survivor
    final = TuningCache(path)
    assert f"b{24}|4|float32|cpu" in final


def test_cache_threaded_writers_and_readers_stress(tmp_path):
    """4 writer threads × 20 atomic saves + concurrent raw readers.

    ``os.replace`` atomicity is the invariant under test: a reader may see
    an older version but must *never* see a torn JSON document, and no
    writer may raise.
    """
    path = os.fspath(tmp_path / "stress.json")
    TuningCache(path).put("seed|1|float32|cpu", _entry(1.0))
    caches = [TuningCache(path) for _ in range(2)]
    errors: list = []

    def writer(tid: int):
        try:
            for i in range(20):
                caches[tid % 2].put(f"t{tid}i{i}|2|float32|cpu", _entry(i))
        except BaseException as e:  # noqa: BLE001 - collected for the assert
            errors.append(e)

    def reader():
        try:
            for _ in range(60):
                with open(path, encoding="utf-8") as f:
                    payload = json.load(f)  # a torn write would raise here
                assert payload.get("schema") == SCHEMA_VERSION
                assert isinstance(payload.get("entries"), dict)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=(t,)) for t in range(4)
    ] + [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        final = TuningCache(path)
    assert final.entries


def test_two_dispatchers_sharing_cache_file(tmp_path):
    """The satellite scenario end-to-end: two Dispatchers, one cache file.

    Each measures a different working set; neither corrupts the file, and
    a third dispatcher loading it afterwards executes from cache with
    zero new measurements for both sets.
    """
    path = tmp_path / "two.json"
    d1, d2 = _disp(path), _disp(path)
    A1, B1 = _operands(seed=1)
    spec2, dims2 = "ab,bc->ac", {"a": 8, "b": 8, "c": 8}
    A2, B2 = _operands(spec2, dims2, seed=2)
    d1.contract(SPEC, A1, B1)
    d2.contract(spec2, A2, B2)   # d2 never saw d1's entry; both persist out
    d1.contract(SPEC, A1, B1)    # d1's own entry survives in memory
    assert d1.hits >= 1

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        d3 = _disp(path)
    d3.contract(spec2, A2, B2)
    assert d3.measurements == 0 and d3.hits == 1
