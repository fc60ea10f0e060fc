"""Driver for a decoder served by the paged runtime (``kind: serving``).

Set-up makes the weights from the seed (the benchmark's own, adapted to
the program's parameter tree), builds ``ServingRuntime`` as the
configuration says, and warms every lattice point (prefill chunk x page
bucket, decode slot bucket x page bucket, page view and commit) that the
cell's traffic can reach, and no other.

The window is an open loop: each request is submitted when it is due,
whether or not the runtime keeps up, and ``tick()`` runs while there is
work.  A token's time is when the tick that made it returned (the
runtime pulls greedy tokens to the host inside the tick).

Host annotations (``jax.profiler.TraceAnnotation``) go around the
instance's ``_run_prefill_chunk`` and ``_run_decode``.  The runtime
names every jitted step ``fn``, so the benchmark also re-wraps the
instance's step builders under stable names (``serve_prefill``,
``serve_decode``, ``serve_page_view``, ``serve_page_commit``): the same
computation, under a name the device trace can attribute.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.reference import decoder as ref

__all__ = ["setup", "measure", "check", "free", "STEP_NAMES"]

#: module name of each jitted step, as the device trace shows it
STEP_NAMES = {"prefill": "serve_prefill", "decode": "serve_decode",
              "view": "serve_page_view", "commit": "serve_page_commit"}
#: the widest served-token gap to the float32 reference, in standard
#: deviations of the logits row.  On a v5e the program read at most
#: 0.072 over 25 runs and the fp8 control at least 0.744 (PERF.md)
GAP_LIMIT = 0.25
#: the sample compared: requests drawn from the seed, the longest among
#: them, until this many served tokens
SAMPLE_TOKENS = 600


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_config

    return get_config(
        conf["arch"], pad_vocab=False,
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab_size=conf["assumed"].get("vocab_padded", conf["vocab_size"]),
        n_periods=conf["num_hidden_layers"], rms_eps=conf["rms_norm_eps"],
        rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"], dtype=conf["dtype"],
        param_dtype=conf["dtype"])


def program_params(cfg, w):
    """The benchmark's weights in the program's parameter tree (the same
    device arrays; norm gains g become the program's offsets g - 1)."""
    from repro.models.transformer import init_params

    off = jax.jit(lambda g: g - 1)
    p = {"embed": w["embed"], "final_norm": off(w["gain_final"]),
         "pattern": [{
             "norm1": off(w["gain_attn"]), "norm2": off(w["gain_mlp"]),
             "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "mlp": {"wi": w["w_up"], "wg": w["w_gate"], "wo": w["w_down"]},
         }]}
    want = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    got = jax.eval_shape(lambda: p)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's tree")
    return p


def _named(build, name: str):
    """``build`` re-wrapped so that its jitted step compiles as ``name``."""
    def wrapped(*args, **kw):
        inner = build(*args, **kw).__wrapped__

        def step(*a):
            return inner(*a)

        step.__name__ = step.__qualname__ = name
        return jax.jit(step)

    return wrapped


def _annotated(method, name: str):
    def wrapped(*args, **kw):
        with jax.profiler.TraceAnnotation(name):
            return method(*args, **kw)

    return wrapped


class Serving:
    """Everything a run holds: weights, runtime, what it recorded."""

    def __init__(self, conf, work, seed, control=False):
        from repro.runtime.engine import ServingRuntime

        self.conf, self.work, self.control = conf, work, control
        self.model = counts.decoder(conf)
        self.cfg = model_config(conf)
        rc = conf["runtime"]
        self.w = ref.make_weights(conf, seed)
        jax.block_until_ready(self.w)
        self.rt = rt = ServingRuntime(
            self.cfg, program_params(self.cfg, self.w), slots=rc["slots"],
            max_len=rc["max_len"], paged=rc["paged"],
            page_size=rc["page_size"], prefix_sharing=rc["prefix_sharing"],
            prefill_chunk=rc["prefill_chunk"], precompile=False)
        rt._build_prefill = _named(rt._build_prefill, STEP_NAMES["prefill"])
        rt.kv.build_decode = _named(rt.kv.build_decode, STEP_NAMES["decode"])
        rt.kv.build_view = _named(rt.kv.build_view, STEP_NAMES["view"])
        rt.kv.build_commit = _named(rt.kv.build_commit, STEP_NAMES["commit"])
        self.launches = {"decode": [], "prefill": []}
        self.recording = False
        run_prefill, run_decode = rt._run_prefill_chunk, rt._run_decode

        def prefill(state, chunk):
            if self.recording:
                self.launches["prefill"].append((chunk, state.pos))
            run_prefill(state, chunk)

        def decode(decodes):
            if self.recording and decodes:
                self.launches["decode"].append(
                    [s.prompt_len + s.n_generated - 1 for s in decodes])
            run_decode(decodes)

        rt._run_prefill_chunk = _annotated(prefill, "bench.prefill_chunk")
        rt._run_decode = _annotated(decode, "bench.decode")

    # ---------------------------------------------------------------- warm
    def reachable(self):
        """Page buckets, chunks and slot buckets the traffic can reach."""
        rt, lat = self.rt, self.rt.lattice
        rows = min(self.work.max_rows(), rt.max_len)
        lo = lat.page_bucket(rt.pool.pages_for(min(self.work.min_rows(),
                                                   rt.max_len)))
        hi = lat.page_bucket(rt.pool.pages_for(rows))
        pages = [P for P in lat.page_buckets if lo <= P <= hi]
        longest = max(self.work.prompt_lens)
        chunks = [c for c in lat.chunk_buckets if c <= longest]
        return pages, chunks, list(lat.slot_buckets)

    def warm(self) -> int:
        """Compile and run once every reachable lattice point; returns
        how many steps were warmed."""
        rt = self.rt
        fp, bk, bg = rt._fingerprint(), rt.buckets.key, rt.buckets.get
        pages, chunks, slots = self.reachable()
        n = 0
        for P in pages:
            view = bg(bk("page_view", P, fp), lambda P=P: rt.kv.build_view(P))
            commit = bg(bk("page_commit", P, fp),
                        lambda P=P: rt.kv.build_commit(P))
            stage = view(rt.kv.pool, jnp.zeros((1, P), jnp.int32),
                         jnp.zeros((1,), jnp.int32))
            for c in chunks:
                fn = bg(bk("prefill", (c, P), fp), rt._build_prefill)
                jax.block_until_ready(
                    fn(rt.params, jnp.zeros((1, c), jnp.int32), stage))
            jax.block_until_ready(
                commit(rt.kv.pool, stage, jnp.zeros((P,), jnp.int32)))
            del stage
            for b in slots:
                fn = bg(bk("decode", (b, P), fp),
                        lambda b=b, P=P: rt.kv.build_decode(
                            rt._decode_vmapped, b, P))
                jax.block_until_ready(fn(
                    rt.params, rt.kv.pool, jnp.zeros((b, P), jnp.int32),
                    jnp.zeros((b,), jnp.int32),
                    jnp.zeros((b, 1, 1), jnp.int32)))
            n += 2 + len(chunks) + len(slots)
        self.warm_eager()
        return n

    def warm_eager(self) -> None:
        """Serve one request per slot, of the shortest prompt length and
        of 2, 3, ... output tokens, so that the decode batch passes every
        slot bucket: the runtime's eager ops on each bucket's logits then
        compile here and not in the window."""
        from repro.runtime.scheduler import Request

        rng = np.random.default_rng(0)
        L = min(self.work.prompt_lens)
        self.rt.serve([
            Request(rid=-1 - k, max_new_tokens=k + 2,
                    prompt=rng.integers(0, self.conf["vocab_size"], L,
                                        dtype=np.int32))
            for k in range(self.rt.slots)])


def setup(conf: dict, work, seed: int, control: bool = False) -> Serving:
    sut = Serving(conf, work, seed, control)
    sut.warmed = sut.warm()
    return sut


def measure(sut: Serving, seconds: float, tracer) -> dict:
    """Serve ``sut.work`` open loop for ``seconds``; returns the record:
    for every request due in the window its due time, the host time of
    each of its tokens, and the request itself."""
    from repro.runtime.scheduler import Request

    rt, gen = sut.rt, sut.work.requests
    m0 = rt.metrics.snapshot()
    reqs, times, live, late = [], [], [], []
    i, t0 = 0, time.perf_counter()
    tracer.arm(t0)
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        while i < len(gen) and gen[i].due <= now:
            g = gen[i]
            r = Request(rid=g.rid, prompt=g.prompt, max_new_tokens=g.max_new)
            rt.submit(r)
            reqs.append(r)
            times.append([])
            live.append(len(reqs) - 1)
            late.append(now - g.due)
            i += 1
        sut.recording = tracer.poll()
        if rt.scheduler.has_work():
            with jax.profiler.TraceAnnotation("bench.tick"):
                rt.tick()
            t = time.perf_counter() - t0
            for j in list(live):
                r, ts = reqs[j], times[j]
                ts.extend([t] * (len(r.output) - len(ts)))
                if r.status not in ("queued", "prefill", "decode"):
                    live.remove(j)
        else:
            nxt = gen[i].due if i < len(gen) else seconds
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    close = time.perf_counter() - t0
    tracer.finish()
    sut.recording = False
    m1 = rt.metrics.snapshot()
    return {
        "window_s": close,
        "due": [g.due for g in gen[:i]],
        "times": times,
        "requests": reqs,
        "late_s": late,
        "counters": {k: m1[k] - m0[k] for k in
                     ("prefix_shared_tokens", "prefill_tokens", "tokens_out")},
        "launches": sut.launches,
        "decoder": sut.model,
    }


def attempted_failed(rec: dict) -> tuple[int, int]:
    """Requests due in the window, and those the runtime gave up on
    (evicted, rejected, unfinished).  One still being served when the
    window closed is late, not failed."""
    bad = sum(r.status in ("evicted", "rejected", "unfinished")
              for r in rec["requests"])
    return len(rec["requests"]), bad


def free(sut: Serving) -> None:
    """Drop the runtime (its page pool and compiled steps); the weights
    stay for the reference."""
    import gc

    sut.rt = None
    gc.collect()


def sample(rec: dict, seed: int) -> list:
    """Requests drawn from the seed until ``SAMPLE_TOKENS`` served tokens:
    the longest finished request first, then the other finished ones,
    then those still being served when the window closed, with the
    tokens they had served (each served token is final)."""
    from bench.generators import rng_for

    rng = rng_for(seed)
    done = [r for r in rec["requests"] if r.status == "done"]
    live = [r for r in rec["requests"] if r.status != "done" and r.output]
    done.sort(key=lambda r: -(len(r.prompt) + len(r.output)))
    order = done[:1] + [done[1:][k] for k in rng.permutation(len(done) - 1)
                        ] if done else []
    order += [live[k] for k in rng.permutation(len(live))]
    out, n = [], 0
    for r in order:
        if n >= SAMPLE_TOKENS:
            break
        out.append(r)
        n += len(r.output)
    return out


def check(sut: Serving, rec: dict, seed: int) -> dict:
    """Compare a sample of the served requests with the float32
    reference: the widest gap by which a served token's logit lies below
    the reference's best, in standard deviations of its row.  Under the
    control the compared tokens are those the fp8 reference puts first
    at the same positions.  Returns ``{"checks": [(name, value, limit,
    ok)], "readings": {...}}``."""
    picked = sample(rec, seed)
    short = [r.rid for r in rec["requests"]
             if r.status == "done" and len(r.output) != r.max_new_tokens]
    seqs = [(np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)]),
             len(r.prompt) - 1, np.asarray(r.output, np.int32))
            for r in picked]
    width = sut.conf["runtime"]["max_len"]
    rows = max(g.max_new for g in sut.work.requests)
    gaps = ref.served_gaps(sut.conf, sut.w, seqs, width, rows,
                           quant="fp8" if sut.control else None)
    tokens = sum(len(g) for g in gaps)
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    checks = [
        ("served_token_gap", widest, GAP_LIMIT, widest <= GAP_LIMIT),
        ("tokens_compared", tokens, 1, tokens >= 1),
        ("short_outputs", len(short), 0, not short),
    ]
    return {"checks": checks, "readings": {"requests_compared": len(seqs)}}
