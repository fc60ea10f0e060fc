"""``correct`` at a size a test run holds: sound runs pass, the control
and the planted faults fail.  Each run goes through the whole harness
(traffic, set-up, open-loop window, reference check) except its look for
a chip, on the CPU, with the configuration shrunk.  A fault breaks the
timed path underneath the harness: the program as the window drives it."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness

SECONDS = 2.0


SERVE, TUCKER = "minicpm-2b.decode-batch", "tucker-hooi-r10.jobs"
CELLS = [SERVE, TUCKER]


def tiny(cell: str):
    spec = harness.load_spec()
    if cell == TUCKER:      # its files are kept; the cell is out (PERF.md)
        spec["workloads"].append({"name": TUCKER, "config": "tucker-hooi-r10",
                                  "traffic": "jobs", "chips": 1})
    _, conf, mix = harness.cell_parts(spec, cell)
    if conf["kind"] == "serving":
        conf.update(hidden_size=256, intermediate_size=512,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=64, vocab_size=4000)
        conf["assumed"] = dict(conf["assumed"], vocab_padded=4096)
        conf["runtime"] = dict(conf["runtime"], slots=2, max_len=256,
                               prefill_chunk=16)
        mix = dict(mix, requests=6,
                   prompt_len={"dist": "uniform", "min": 8, "max": 40},
                   output_len={"dist": "uniform", "min": 4, "max": 16})
    else:
        conf.update(n=32, ranks=[4, 4, 4], n_iter=3)
        conf["tensor"] = dict(conf["tensor"], rank=[4, 4, 4])
    return spec, conf, mix


def run(cell, seed, control=False):
    spec, conf, mix = tiny(cell)
    return harness.run_cell(spec, cell, seed, SECONDS, False, conf=conf,
                            mix=mix, control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run(cell, 2**31 + 5)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"          # the comparisons come last


def test_serving_control_is_not_correct():
    """The control in the program's place: at each served position, the
    token the fp8 reference puts first.  Its widest gap is over the
    limit, so the run reads not correct."""
    from bench.drivers import serving

    out = run(SERVE, 77, control=True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] > 0
    assert out["checks"]["served_token_gap"]["value"] > serving.GAP_LIMIT


def test_tucker_control_is_not_correct():
    """The control in the program's place: the reference at
    ``precision="high"``, three bf16 passes on a TPU, where its run reads
    not correct.  A CPU computes ``high`` in full float32, so there the
    test sees the control's run through the harness and checks what it
    asks the compiler for; its chip readings are in PERF.md."""
    from bench.reference import hooi as ref

    out = run(TUCKER, 43, control=True)
    assert out["checks"]["jobs_compared"]["value"] >= 1
    if jax.default_backend() == "tpu":
        assert not out["correct"], out["checks"]
    T = jax.ShapeDtypeStruct((8, 8, 8), jnp.float32)
    text = lambda p: ref.hooi.lower(  # noqa: E731
        T, ranks=(2, 2, 2), n_iter=1, precision=p, slab=8).as_text()
    assert "HIGH" in text("high") and "HIGHEST" not in text("high")
    assert "HIGHEST" in text("highest")


# ------------------------------------------------------------ faults
def _break_decode(monkeypatch, how):
    from repro.runtime import pages

    orig = pages.PagedKV.build_decode

    def build(self, decode_vmapped, bucket, P):
        step = orig(self, decode_vmapped, bucket, P)

        def broken(params, pool, tables, lengths, toks):
            logits, new_pool = step(params, pool, tables, lengths, toks)
            if how == "token":       # the token is altered where it is made
                return jnp.roll(logits, 1, axis=-1), new_pool
            if how == "batch":       # the batch's second half left out:
                return (jnp.broadcast_to(logits[:1], logits.shape),  # row 0
                        new_pool)                                     # for all
            return logits, pool      # the step returns its state unchanged

        return jax.jit(broken)

    monkeypatch.setattr(pages.PagedKV, "build_decode", build)


@pytest.mark.parametrize("how", ["token", "state", "batch"])
def test_serving_fault_is_not_correct(monkeypatch, how):
    _break_decode(monkeypatch, how)
    out = run(SERVE, 31)
    assert not out["correct"], out["checks"]


def _break_hooi(monkeypatch, how):
    from repro.core import tucker

    orig = tucker.hooi

    def broken(T, ranks, **kw):
        if how == "state":           # the sweeps leave the HOSVD's factors
            kw["n_iter"] = 0         # as they are
        r = orig(T, ranks, **kw)
        if how == "answer":          # the core is altered where it is made
            r.core = r.core.at[0, 0, 0].add(0.01 * jnp.abs(r.core).max())
        return r

    monkeypatch.setattr(tucker, "hooi", broken)


@pytest.mark.parametrize("how", ["answer", "state"])
def test_tucker_fault_is_not_correct(monkeypatch, how):
    _break_hooi(monkeypatch, how)
    out = run(TUCKER, 41)
    assert not out["correct"], out["checks"]
