"""deepseek-moe-16b [moe]: fine-grained experts, 2 shared + 64 routed top-6.

[arXiv:2401.06066; hf:deepseek-ai/deepseek-moe-16b-base] — 28L d_model=2048
16H x 128 (MHA, kv=16) vocab=102400 untied, rope theta 10000, RMSNorm eps
1e-6, 4096 positions.  Layer 0 is a dense FFN of width 10944; the other 27
are MoE layers: 64 routed experts of width 1408 and 2 shared ones (one FFN
of width 2 x 1408).

Routing as published: softmax over the 64 router logits, the top 6 gates
kept as they are (no renormalisation), no capacity and so no dropped token;
a sequence-level balance loss with alpha 0.001 and no router z-loss.

``EP4``, ``get_config("deepseek-moe-16b-ep4")``, is one chip's share of a
stated deployment (``base.share``): one chip of a four-chip v5e host that
runs one pipeline stage of the dense layer and 4 MoE layers, the four chips
dividing each MoE layer's experts (16 each, expert parallelism 4) and the
vocabulary (25,600 ids each).  It holds experts 0-15; the router scores all 64.
"""
from repro.configs.base import LayerSpec, ModelConfig, MoEConfig, share

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    family="moe",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,  # dense layer-0 width; expert width is moe.d_expert
    vocab_size=102400,
    layer_pattern=(LayerSpec("ga", "moe"),),
    first_k_dense=1,
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        n_shared=2,
        d_expert=1408,
        norm_topk=False,
        router_aux_weight=0.001,
        router_z_weight=0.0,
    ),
    tied_embeddings=False,
    act="silu",
)

EP4 = share(
    CONFIG, name="deepseek-moe-16b-ep4", moe_layers=4, experts=(0, 16),
    vocab_size=102400 // 4,
)
