"""Driver for a sparse decoder served by the paged runtime (``kind:
serving_moe``): one chip's share of an expert-parallel deployment.

As ``bench/drivers/serving.py`` (whose window, sample, failure count
and clean-up it uses, and whose warm-up of the reachable lattice points
it inherits), with three differences:

* the configuration file is a published ``config.json`` plus the share
  this chip holds, and the program's config is built from it; set-up
  stops before it makes any weight where the program does not know the
  architecture;
* the weights and the float32 reference are ``bench/reference/mellum.py``;
* each decode launch in the traced part of the window also records the
  routing the runtime counted for it (held experts with a token, routed
  pairs on held experts), which the expert kernel's roofline reads.
"""

from __future__ import annotations

import jax
import numpy as np

from bench import counts_moe
from bench.drivers import serving
from bench.drivers.serving import attempted_failed, free, measure, sample
from bench.reference import mellum as ref

__all__ = ["setup", "measure", "check", "free", "attempted_failed",
           "GAP_LIMIT"]

#: the widest served-token gap to the float32 reference, in standard
#: deviations of the logits row.  On a v5e the program read 0.000-0.050
#: over five runs, with a bfloat16 rounding of the router's input
#: flipping the top-8 in ~1% of (row, layer) pairs, and the fp8 control
#: 0.985 (PERF.md)
GAP_LIMIT = 0.25


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, holding
    ``num_experts`` experts from ``first_held_expert``.  Raises where the
    program does not know the architecture (before any weight is
    made)."""
    from repro.configs import get_config
    from repro.configs.base import LayerSpec, MoEConfig, YaRN

    cfg = get_config(conf["arch"], pad_vocab=False)
    kinds = ref.pattern(conf)
    rope = conf["rope_parameters"]
    theta, = {rope[k]["rope_theta"] for k in kinds}

    def spec(kind):
        rp = rope[kind]
        yarn = None if rp["rope_type"] == "default" else YaRN(
            factor=rp["factor"],
            original_max_positions=rp["original_max_position_embeddings"],
            beta_fast=rp["beta_fast"], beta_slow=rp["beta_slow"],
            attention_factor=rp["attention_factor"])
        window = (conf["sliding_window"] if kind == "sliding_attention"
                  else None)
        return LayerSpec(mixer="attn", ff="moe", window=window, yarn=yarn)

    return cfg.with_(
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab_size=conf["vocab_size"], pattern=tuple(map(spec, kinds)),
        n_periods=conf["num_hidden_layers"] // len(kinds),
        rms_eps=conf["rms_norm_eps"], rope_theta=theta,
        tie_embeddings=conf["tie_word_embeddings"],
        max_seq_len=conf["max_position_embeddings"],
        moe=MoEConfig(n_experts=conf["num_experts_published"],
                      top_k=conf["num_experts_per_tok"],
                      d_expert=conf["moe_intermediate_size"],
                      n_held=conf["num_experts"],
                      first_held=conf["first_held_expert"]),
        dtype=conf["dtype"], param_dtype=conf["dtype"])


def program_params(cfg, w):
    """The benchmark's weights in the program's parameter tree (the same
    device arrays; norm gains g become the program's offsets g - 1)."""
    from repro.models.transformer import init_params

    off = jax.jit(lambda g: g - 1)
    p = {"embed": w["embed"], "lm_head": w["lm_head"],
         "final_norm": off(w["gain_final"]),
         "pattern": [{
             "norm1": off(lw["gain_attn"]), "norm2": off(lw["gain_moe"]),
             "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
             "moe": {"router": lw["router"], "wi": lw["w_up"],
                     "wg": lw["w_gate"], "wo": lw["w_down"]},
         } for lw in w["layers"]]}
    want = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    got = jax.eval_shape(lambda: p)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's tree")
    return p


class SparseServing(serving.Serving):
    """Everything a run holds: weights, runtime, what it recorded."""

    def __init__(self, conf, work, seed, control=False):
        from repro.runtime.engine import ServingRuntime

        self.cfg = model_config(conf)
        self.conf, self.work, self.control = conf, work, control
        self.model = counts_moe.sparse_decoder(conf)
        rc = conf["runtime"]
        self.w = ref.make_weights(conf, seed)
        jax.block_until_ready(self.w)
        self.rt = rt = ServingRuntime(
            self.cfg, program_params(self.cfg, self.w), slots=rc["slots"],
            max_len=rc["max_len"], paged=rc["paged"],
            page_size=rc["page_size"], prefix_sharing=rc["prefix_sharing"],
            prefill_chunk=rc["prefill_chunk"], precompile=False)
        names = serving.STEP_NAMES
        rt._build_prefill = serving._named(rt._build_prefill, names["prefill"])
        rt.kv.build_decode = serving._named(rt.kv.build_decode,
                                            names["decode"])
        rt.kv.build_view = serving._named(rt.kv.build_view, names["view"])
        rt.kv.build_commit = serving._named(rt.kv.build_commit,
                                            names["commit"])
        self.launches = {"decode": [], "prefill": [], "experts": []}
        self.recording = False
        run_prefill, run_decode = rt._run_prefill_chunk, rt._run_decode
        moe = rt.metrics.moe

        def prefill(state, chunk):
            if self.recording:
                self.launches["prefill"].append((chunk, state.pos))
            run_prefill(state, chunk)

        def decode(decodes):
            if not (self.recording and decodes):
                return run_decode(decodes)
            ctx = [s.prompt_len + s.n_generated - 1 for s in decodes]
            t0 = moe["moe_experts_touched_decode"]
            h0 = moe["moe_pairs_held_decode"]
            run_decode(decodes)
            self.launches["decode"].append(ctx)
            self.launches["experts"].append(
                (moe["moe_experts_touched_decode"] - t0,
                 moe["moe_pairs_held_decode"] - h0))

        rt._run_prefill_chunk = serving._annotated(prefill,
                                                   "bench.prefill_chunk")
        rt._run_decode = serving._annotated(decode, "bench.decode")


def setup(conf: dict, work, seed: int, control: bool = False):
    sut = SparseServing(conf, work, seed, control)
    sut.warmed = sut.warm()
    return sut


def check(sut, rec: dict, seed: int) -> dict:
    """Compare a sample of the served requests with the float32
    reference, as ``bench/drivers/serving.py`` does: the widest gap by
    which a served token's logit lies below the reference's best, in
    standard deviations of its row; under the control the compared
    tokens are those the fp8 reference puts first.  Readings: the share
    of compared rows whose routing flips in some layer on a bfloat16
    rounding of the router's input, and how many layers flip at the
    widest gap's row."""
    picked = sample(rec, seed)
    short = [r.rid for r in rec["requests"]
             if r.status == "done" and len(r.output) != r.max_new_tokens]
    seqs = [(np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)]),
             len(r.prompt) - 1, np.asarray(r.output, np.int32))
            for r in picked]
    width = sut.conf["runtime"]["max_len"]
    rows = max(g.max_new for g in sut.work.requests)
    gaps, flips = ref.served_gaps(sut.conf, sut.w, seqs, width, rows,
                                  quant="fp8" if sut.control else None)
    tokens = sum(len(g) for g in gaps)
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    at = max(((float(g.max()), int(f[int(np.argmax(g))]))
              for g, f in zip(gaps, flips)), default=(0.0, 0))[1]
    flipped = sum(int(np.count_nonzero(f)) for f in flips)
    checks = [
        ("served_token_gap", widest, GAP_LIMIT, widest <= GAP_LIMIT),
        ("tokens_compared", tokens, 1, tokens >= 1),
        ("short_outputs", len(short), 0, not short),
    ]
    return {"checks": checks, "readings": {
        "requests_compared": len(seqs),
        "route_flip_rows": flipped,
        "route_flip_share": flipped / tokens if tokens else 0.0,
        "route_flips_at_widest_gap": at}}
