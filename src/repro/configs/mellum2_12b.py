"""mellum2-12b-a2.5b — sparse experts in every layer, window and full attention.

[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]  28L d_model=2304
32H (GQA kv=4) head_dim=128, vocab=98304, untied head, RMSNorm eps 1e-6.
Every layer sparse: 64 experts of width 896, top-8, renormalised, no
shared expert.  ``layer_types``: three ``sliding_attention`` layers
(window 1024, plain RoPE theta 500000) then one ``full_attention``
layer (YaRN: factor 16 over 8192 original positions, beta 32/1,
attention factor 1.2773), seven times.  The MTP head is not modelled.
"""

from repro.configs.base import LayerSpec, ModelConfig, MoEConfig, YaRN

#: the full layers' rope scaling, as published
FULL_YARN = YaRN(factor=16.0, original_max_positions=8192, beta_fast=32.0,
                 beta_slow=1.0, attention_factor=1.2772588722239782)


def make_config() -> ModelConfig:
    sliding = LayerSpec(mixer="attn", ff="moe", window=1024)
    full = LayerSpec(mixer="attn", ff="moe", yarn=FULL_YARN)
    return ModelConfig(
        arch_id="mellum2-12b-a2.5b",
        d_model=2304,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        d_ff=7168,            # published dense width; no layer is dense
        vocab_size=98_304,
        pattern=(sliding, sliding, sliding, full),
        n_periods=7,
        moe=MoEConfig(n_experts=64, top_k=8, d_expert=896),
        rope_theta=500_000.0,
        rms_eps=1e-6,
        max_seq_len=131_072,
    )
