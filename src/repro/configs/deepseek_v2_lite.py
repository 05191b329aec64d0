"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H d_ff_expert=1408
vocab=102400, MLA kv_lora=512, MoE 64 routed experts top-6 + 2 shared,
first layer dense FFN (d_ff=10944). [arXiv:2405.04434; hf]

Routing as published: softmax over the 64 experts, greedy top-6, the
gates not renormalised (``norm_topk_prob: false``, scaling factor 1).
Serving runs the dropless expert layer (models/moe.moe_layer): no
capacity, so no routed token is dropped.  ``held_experts`` /
``expert_offset`` give one device's share of an expert-parallel layer
(bench/configs/deepseek_v2_lite.json holds 8 of the 64).

SLA2 runs in MLA **latent space** (models/mla.py): scores are computed with
W_uk absorbed into the query, the router pools latent keys (pooling commutes
with the linear decompression), and the linear branch's phi-features live on
the 576-dim latent — the KV cache stays at rank+rope per token."""
from repro.models.mla import MLAConfig
from repro.models.moe import MoEConfig
from repro.models.transformer import ModelConfig


def config(**overrides):
    kw = dict(
        name="deepseek_v2_lite", family="moe",
        n_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        head_dim=192,                       # qk head dim (nope 128 + rope 64)
        d_ff=10944,                         # layer-0 dense FFN
        vocab_size=102400,
        layer_kinds=("mla_moe",), first_kinds=("mla_dense",),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                      v_head_dim=128, q_lora_rank=0),
        moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                      num_shared=2),
        rope_theta=10_000.0, tie_embeddings=False,
        mechanism="sla2", max_target_len=524288, ep_axis="model",
    )
    kw.update(overrides)
    return ModelConfig(**kw)


def smoke_config(**overrides):
    kw = dict(
        name="deepseek_v2_lite_smoke", family="moe",
        n_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=24,
        d_ff=128, vocab_size=256,
        layer_kinds=("mla_moe",), first_kinds=("mla_dense",),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32, num_shared=2),
        tie_embeddings=False, mechanism="sla2", block_q=32, block_k=16,
        k_frac=0.25, max_target_len=512, loss_chunk=64, dtype="float32",
        q_chunk=4, ep_axis=None,
    )
    kw.update(overrides)
    return ModelConfig(**kw)
