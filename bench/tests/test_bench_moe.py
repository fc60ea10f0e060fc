"""The sparse decoder's cell at a size a test run holds, on the CPU: the
program against the float32 reference on logits, a whole ``serving_moe``
run through the harness (sound: correct; the fp8 control: not correct),
and the decode step's counts against a hand count."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts_moe, harness
from bench.drivers import serving_moe
from bench.reference import mellum as ref

CELL = "mellum2-12b-a2.5b.code-batch"
CONF = json.loads((Path(__file__).parents[1] / "configs" /
                   "mellum2-12b-a2.5b.json").read_text())


def tiny(conf=CONF, **kw):
    """The configuration at test size: one period of the published layer
    pattern (three sliding layers, window 8, and a full YaRN layer whose
    ramp falls inside 16 original positions), 4 of 16 experts held,
    top-4."""
    rope = conf["rope_parameters"]
    conf = dict(conf, hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, moe_intermediate_size=64,
                num_experts=4, num_experts_published=16, first_held_expert=4,
                num_experts_per_tok=4, num_hidden_layers=4,
                layer_types=conf["layer_types"][:4], sliding_window=8,
                vocab_size=512, rope_parameters=dict(rope, full_attention=dict(
                    rope["full_attention"],
                    original_max_position_embeddings=16)))
    conf["runtime"] = dict(conf["runtime"], slots=2, max_len=64,
                           prefill_chunk=8)
    conf.update(kw)
    return conf


def test_prefill_then_paged_decode_matches_the_reference():
    """Two requests with contexts past the window, prefilled in chunks
    and decoded together through ``ServingRuntime(paged=True)``: every
    logits row the program produced (each prompt's last, each decode
    step's) against the reference's full forward at that position."""
    from repro.runtime.engine import ServingRuntime
    from repro.runtime.scheduler import Request

    conf = tiny(dtype="float32")
    cfg = serving_moe.model_config(conf)
    w = ref.make_weights(conf, 11)
    rc = conf["runtime"]
    rt = ServingRuntime(cfg, serving_moe.program_params(cfg, w),
                        slots=rc["slots"], max_len=rc["max_len"], paged=True,
                        page_size=rc["page_size"],
                        prefill_chunk=rc["prefill_chunk"], precompile=False)
    got = []                                   # (rid, position, logits)
    run_prefill, run_decode = rt._run_prefill_chunk, rt._run_decode
    build_prefill, at = rt._build_prefill, []

    def prefill(state, chunk):
        at[:] = [(state.rid, state.pos + chunk - 1)]
        run_prefill(state, chunk)

    def build():
        fn = build_prefill()

        def step(p, toks, c):
            out = fn(p, toks, c)
            got.append((*at[0], out[0][0]))
            return out
        return step

    def decode(decodes):
        at[:] = [(s.rid, s.prompt_len + s.n_generated - 1) for s in decodes]
        run_decode(decodes)

    rt._run_prefill_chunk, rt._run_decode = prefill, decode
    rt._build_prefill = build
    rt.logits_probe = lambda lg: got.extend(
        (rid, pos, lg[r, 0]) for r, (rid, pos) in enumerate(at))
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, max_new_tokens=6, prompt=rng.integers(
        0, conf["vocab_size"], n, dtype=np.int32)) for i, n in
        enumerate((21, 13))]
    rt.serve(reqs)
    assert all(r.status == "done" for r in reqs)
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, r.output[:-1]]).astype(np.int32)
        # each prefill chunk's last row, then every decode step's
        rows = {pos: lg for rid, pos, lg in got if rid == r.rid}
        assert set(range(len(r.prompt) - 1, len(seq))) <= set(rows)
        toks = np.zeros(ref.QUERY_BLOCK, np.int32)
        toks[:len(seq)] = seq
        want, _ = ref._logits(w, toks, np.asarray(sorted(rows)),
                              conf_items=_items(conf), quant=None)
        have = np.stack([np.asarray(rows[p]) for p in sorted(rows)])
        want = np.asarray(want)
        worst = max(worst, float(np.max(np.abs(have - want)
                                        / want.std(-1, keepdims=True))))
    # float32 on both sides (routing too): what differs is summation
    # order, ~1e-6 of a row's spread; a bfloat16 program reads ~1e-2
    assert worst < 1e-4, worst


def _items(conf):
    return tuple(sorted((k, ref._freeze(conf[k])) for k in ref._KEYS))


def test_bf16_program_is_caught_by_the_float32_tolerance():
    """The logits test's tolerance would fail the program computed one
    precision step down (bfloat16): the same weights, served in
    bfloat16, differ from the reference by more than 1e-4 of a row."""
    conf = tiny(dtype="float32")
    w = ref.make_weights(conf, 11)
    seq = np.arange(3, 3 + ref.QUERY_BLOCK, dtype=np.int32) % 500
    rows = np.arange(20, 30)
    f32, _ = ref._logits(w, seq, rows, conf_items=_items(conf), quant=None)
    w16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                       w)
    b16, _ = ref._logits(w16, seq, rows, conf_items=_items(conf), quant=None)
    gap = np.max(np.abs(np.asarray(b16) - np.asarray(f32))
                 / np.asarray(f32).std(-1, keepdims=True))
    assert gap > 1e-3


def run(seed, control=False):
    spec = harness.load_spec()
    _, conf, mix = harness.cell_parts(spec, CELL)
    mix = dict(mix, requests=6,
               prompt_len={"dist": "uniform", "min": 12, "max": 40},
               output_len={"dist": "uniform", "min": 8, "max": 24})
    return harness.run_cell(spec, CELL, seed, 2.0, False, conf=tiny(conf),
                            mix=mix, control=control)


@pytest.mark.parametrize("control", [False, True])
def test_cell_run_is_correct_and_its_control_is_not(control):
    out = run(2**31 + 9, control)
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["tokens_compared"]["value"] > 0
    assert 0.0 <= out["readings"]["route_flip_share"] <= 1.0
    gap = out["checks"]["served_token_gap"]["value"]
    if control:
        assert not out["correct"] and gap > serving_moe.GAP_LIMIT, out
    else:
        assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def test_parent_without_the_architecture_fails_before_weights(monkeypatch):
    """A program that does not register the architecture (the parent
    commit) fails at once, before any weight is made."""
    from repro.configs import registry

    monkeypatch.delitem(registry.ARCHS, CONF["arch"])
    monkeypatch.setattr(ref, "make_weights", lambda *a: pytest.fail(
        "weights made"))
    with pytest.raises(KeyError, match="unknown arch"):
        serving_moe.setup(tiny(), None, 1)


def test_decode_and_expert_counts_by_hand():
    m = counts_moe.SparseDecoder(
        d_model=8, d_expert=4, layers=4, sliding_layers=3, window=5,
        heads=2, kv_heads=1, head_dim=4, n_experts=6, vocab=10, itemsize=2)
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8
    expert = 3 * 8 * 4
    assert m.experts(2, touched=3, held=5) == (
        2 * 5 * expert, (3 * expert + 2 * 4 * 2 * 8) * 2)
    # contexts 2 and 7: a sliding layer reads min(c, 4) cached rows
    rows = (1 * 2 + 3 * 2) + (1 * 7 + 3 * 4)
    flops, nbytes = m.decode_step([2, 7], touched=3, held=5)
    assert flops == (2 * 4 * 2 * (attn + 8 * 6) + 4 * 2 * 4 * (rows + 2 * 4)
                     + 2 * 2 * 8 * 10 + 2 * 5 * expert)
    kv = 2 * 1 * 4 * 2
    assert nbytes == (4 * (attn + 8 * 6) * 2 + 10 * 8 * 2
                      + (3 * expert + 2 * 4 * 2 * 8) * 2
                      + rows * kv + 2 * 4 * kv + 2 * (8 * 2 + 10 * 4))


def test_mellum_step_counts_at_published_widths():
    m = counts_moe.sparse_decoder(CONF)
    assert m.attn_params() == 21_233_664           # 28 x 21.2 M, 1.19 GB
    assert m.expert_params() * 2 == 12_386_304     # one expert, bf16
    assert m.kv_row_bytes() * 28 == 57_344         # a token's K and V
    # every held expert of every layer touched: 5.55 GB of weights
    assert m.experts(8, 28 * 16, 0)[1] == pytest.approx(5.55e9, rel=2e-3)
