"""Operations and bytes a decode step of a sparse decoder needs, from the
configuration's own shapes.

Conventions as in :mod:`bench.counts`: a matmul of ``m x k`` by ``k x n``
is ``2 m k n`` operations; reductions, softmax, top-k and norms are not
counted; padding, gathers, pool copies and whole-view reads are never
work.  On top of those, for the expert layer:

* only the (token, held expert) pairs the router chose are operations;
* each held expert at least one token chose (``touched``) has its
  weights read once per launch; an untouched expert costs nothing;
* a sliding-window layer reads at most ``window - 1`` cached rows per
  token, a full layer the whole context.
"""

from __future__ import annotations

import dataclasses

__all__ = ["SparseDecoder", "sparse_decoder"]


@dataclasses.dataclass(frozen=True)
class SparseDecoder:
    """A decoder whose every layer is attention then a sparse SwiGLU."""

    d_model: int
    d_expert: int
    layers: int
    sliding_layers: int
    window: int
    heads: int
    kv_heads: int
    head_dim: int
    n_experts: int        # published: the router's width
    vocab: int
    itemsize: int

    def attn_params(self) -> int:
        E, H, G, D = self.d_model, self.heads, self.kv_heads, self.head_dim
        return E * H * D + 2 * E * G * D + H * D * E

    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert

    def kv_row_bytes(self) -> int:
        """K and V of one token in one layer."""
        return 2 * self.kv_heads * self.head_dim * self.itemsize

    def cached_rows(self, c: int) -> int:
        """Cached rows one new token attends over all layers, ``c`` rows
        cached before it."""
        full = self.layers - self.sliding_layers
        return full * c + self.sliding_layers * min(c, self.window - 1)

    # ------------------------------------------------------------ kernel
    def experts(self, tokens: int, touched: int, held: int
                ) -> tuple[int, int]:
        """Operations and bytes of the expert kernel over all layers of
        one launch: ``tokens`` tokens, ``touched`` (layer, held expert)
        pairs with a token, ``held`` routed (token, held expert) pairs.
        Bytes: each touched expert's weights once, and each layer's
        token rows read and written."""
        flops = 2 * held * self.expert_params()
        nbytes = (touched * self.expert_params()
                  + 2 * self.layers * tokens * self.d_model) * self.itemsize
        return flops, nbytes

    # -------------------------------------------------------------- step
    def decode_step(self, contexts: list[int], touched: int, held: int
                    ) -> tuple[int, int]:
        """Operations and bytes of one decode launch whose active slots
        hold ``contexts`` cache rows before the step (one new token each),
        with the expert kernel's ``touched`` and ``held`` as in
        :meth:`experts`."""
        E, L, V = self.d_model, self.layers, self.vocab
        n = len(contexts)
        kf, kb = self.experts(n, touched, held)
        keys = sum(self.cached_rows(c) + L for c in contexts)
        flops = (n * L * 2 * (self.attn_params() + E * self.n_experts)
                 + 4 * self.heads * self.head_dim * keys
                 + n * 2 * E * V + kf)
        nbytes = (L * (self.attn_params() + E * self.n_experts) * self.itemsize
                  + V * E * self.itemsize                     # head
                  + kb
                  + sum(self.cached_rows(c) for c in contexts)
                  * self.kv_row_bytes()                       # KV read
                  + n * L * self.kv_row_bytes()               # KV write
                  + n * (E * self.itemsize + V * 4))          # embed, logits
        return flops, nbytes


def sparse_decoder(conf: dict) -> SparseDecoder:
    """The :class:`SparseDecoder` of a ``serving_moe`` configuration."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[conf["dtype"]]
    return SparseDecoder(
        d_model=conf["hidden_size"], d_expert=conf["moe_intermediate_size"],
        layers=conf["num_hidden_layers"],
        sliding_layers=conf["layer_types"].count("sliding_attention"),
        window=conf["sliding_window"], heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        n_experts=conf["num_experts_published"], vocab=conf["vocab_size"],
        itemsize=item)
