"""Operations and bytes the work needs, from the configuration's own shapes.

Counted from unpadded shapes and the configuration's dtype, whatever
implements them: a padded tile, a gather, a copy or a whole-``max_len``
read that a program does is never counted as work.  So a faster kernel
moves a share toward 1 and never over it.

Conventions: a matmul of ``m x k`` by ``k x n`` is ``2 m k n``
operations.  A contraction's bytes are each distinct operand read once
and its output written once.  Reductions, softmax and norms are not
counted.
"""

from __future__ import annotations

import dataclasses
import math

__all__ = [
    "Decoder", "decoder", "Contraction", "contraction", "hooi_contractions",
]


# ------------------------------------------------------------------ decoder
@dataclasses.dataclass(frozen=True)
class Decoder:
    """A llama-style decoder: attention then a gated MLP in every layer,
    tied or untied LM head."""

    d_model: int
    d_ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int            # unpadded
    vocab_padded: int
    tied: bool
    itemsize: int         # bytes of a weight, an activation, a KV element
    norm_itemsize: int = 4

    # ------------------------------------------------------------- weights
    def layer_params(self) -> int:
        E, H, G, D, F = (self.d_model, self.heads, self.kv_heads,
                         self.head_dim, self.d_ff)
        return E * H * D + 2 * E * G * D + H * D * E + 3 * E * F

    def head_params(self, vocab: int) -> int:
        return vocab * self.d_model * (1 if self.tied else 2)

    def served_weight_bytes(self) -> int:
        """Bytes of the served weights as the program holds them: padded
        vocabulary, norm gains in their own dtype."""
        norms = (2 * self.layers + 1) * self.d_model * self.norm_itemsize
        return ((self.layers * self.layer_params()
                 + self.head_params(self.vocab_padded)) * self.itemsize
                + norms)

    def step_weight_bytes(self) -> int:
        """Weight bytes one step must read: every layer and the LM head,
        unpadded (the embedding lookup reads a few rows, counted apart)."""
        return (self.layers * self.layer_params()
                + self.vocab * self.d_model) * self.itemsize

    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.itemsize

    # ------------------------------------------------------------ per step
    def token_flops(self) -> int:
        """Projection and MLP operations of one token through all layers."""
        return 2 * self.layers * self.layer_params()

    def attn_flops(self, keys: int) -> int:
        """Scores and weighted sum of one query against ``keys`` keys, all
        layers."""
        return 4 * self.layers * self.heads * self.head_dim * keys

    def logits_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def decode_step(self, contexts: list[int]) -> tuple[int, int]:
        """Operations and bytes of one decode launch whose active slots
        hold ``contexts`` cache rows before the step (one new token each,
        attending ``c + 1`` keys)."""
        flops = sum(self.token_flops() + self.attn_flops(c + 1)
                    + self.logits_flops() for c in contexts)
        kv = self.kv_bytes_per_token()
        nbytes = (self.step_weight_bytes()
                  + sum(c * kv + kv for c in contexts)        # read, write
                  + len(contexts) * (self.d_model * self.itemsize
                                     + self.vocab * 4))       # embed, logits
        return flops, nbytes

    def prefill_chunk(self, tokens: int, start: int) -> tuple[int, int]:
        """Operations and bytes of one prefill launch of ``tokens`` prompt
        tokens at positions ``start`` onwards (``start`` rows cached);
        only the last position reaches the LM head."""
        keys = sum(start + i + 1 for i in range(tokens))
        flops = (tokens * self.token_flops()
                 + 4 * self.layers * self.heads * self.head_dim * keys
                 + self.logits_flops())
        kv = self.kv_bytes_per_token()
        nbytes = (self.step_weight_bytes() + start * kv + tokens * kv
                  + tokens * self.d_model * self.itemsize + self.vocab * 4)
        return flops, nbytes


def decoder(conf: dict) -> Decoder:
    """The :class:`Decoder` of a serving configuration file."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[conf["dtype"]]
    norm = {"bfloat16": 2, "float32": 4}[conf.get("norm_dtype", "float32")]
    return Decoder(
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        layers=conf["num_hidden_layers"], heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab=conf["vocab_size"],
        vocab_padded=conf["assumed"].get("vocab_padded", conf["vocab_size"]),
        tied=conf["tie_word_embeddings"], itemsize=item, norm_itemsize=norm)


# ------------------------------------------------------------ contractions
@dataclasses.dataclass(frozen=True)
class Contraction:
    spec: str
    flops: int
    nbytes: int


def contraction(spec: str, dims: dict, itemsize: int, *,
                gram: bool = False) -> Contraction:
    """Operations and bytes of one pairwise contraction ``spec``
    (``"ab,bc->ac"``): ``2 * prod(all modes)`` operations; each operand
    read once, the output written once.  ``gram``: both operands are one
    tensor (``X X^T``), read once."""
    ins, out = spec.split("->")
    ops = ins.split(",")
    modes = set("".join(ops)) | set(out)
    flops = 2 * math.prod(dims[m] for m in modes)
    distinct = ops[:1] if gram else ops
    elems = sum(math.prod(dims[m] for m in o) for o in distinct)
    elems += math.prod(dims[m] for m in out)
    return Contraction(spec, flops, elems * itemsize)


def hooi_contractions(n: int, ranks, n_iter: int, itemsize: int = 4
                      ) -> list[Contraction]:
    """Every contraction of one ``hooi()`` call on an ``n``-cube, in the
    pairwise order the algorithm needs (multi-operand products taken in
    their cheapest order, the big tensor contracted first).

    HOSVD init: three mode grams of T.  Each sweep: ``T x3 C``, then
    ``x2 B`` and its gram; ``(T x3 C) x1 A`` and its gram; ``T x1 A``,
    then ``x2 B`` and its gram.  Then the core ``T x1 A x2 B x3 C`` and
    the reconstruction ``G x3 C x2 B x1 A``.  Eigendecompositions and
    the error norm are not contractions and are not counted."""
    i, j, k = ranks
    d = {"m": n, "n": n, "p": n, "q": n, "i": i, "j": j, "k": k}
    c = lambda s: contraction(s, d, itemsize)  # noqa: E731
    g = lambda s: contraction(s, d, itemsize, gram=True)  # noqa: E731
    init = [g("mnp,qnp->mq"), g("mnp,mqp->nq"), g("mnp,mnq->pq")]
    sweep = [c("mnp,pk->mnk"), c("mnk,nj->mjk"), g("mjk,qjk->mq"),
             c("mnk,mi->ink"), g("ink,iqk->nq"),
             c("mnp,mi->inp"), c("inp,nj->ijp"), g("ijp,ijq->pq")]
    core = [c("mnp,mi->inp"), c("inp,nj->ijp"), c("ijp,pk->ijk")]
    recon = [c("ijk,pk->ijp"), c("ijp,nj->inp"), c("inp,mi->mnp")]
    return init + sweep * n_iter + core + recon
