"""llama4-maverick-400b-a17b [moe]: 48L d_model=5120 40H (GQA kv=8)
d_ff=8192 vocab=202048, MoE 128 experts top-1, interleaved dense/MoE
layers (+1 always-on shared expert), early-fusion multimodal (text path
here). [hf:meta-llama/Llama-4 family]

The top-1 gate is renormalised (``norm_topk_prob``), so the routed expert
enters with weight 1; the published gate, a sigmoid of the router logit,
is not modelled."""
from repro.models.moe import MoEConfig
from repro.models.transformer import ModelConfig


def config(**overrides):
    kw = dict(
        name="llama4_maverick_400b", family="moe",
        n_layers=48, d_model=5120, num_heads=40, num_kv_heads=8,
        head_dim=128, d_ff=8192, vocab_size=202048,
        layer_kinds=("dense", "moe"),
        moe=MoEConfig(num_experts=128, top_k=1, d_ff_expert=8192,
                      num_shared=1, capacity_factor=1.25,
                      norm_topk_prob=True),
        rope_theta=500_000.0, tie_embeddings=False,
        mechanism="sla2", max_target_len=524288, ep_axis="model",
    )
    kw.update(overrides)
    return ModelConfig(**kw)


def smoke_config(**overrides):
    kw = dict(
        name="llama4_maverick_smoke", family="moe",
        n_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, layer_kinds=("dense", "moe"),
        moe=MoEConfig(num_experts=8, top_k=1, d_ff_expert=64, num_shared=1,
                      norm_topk_prob=True),
        tie_embeddings=False, mechanism="sla2", block_q=32, block_k=16,
        k_frac=0.25, max_target_len=512, loss_chunk=64, dtype="float32",
        q_chunk=4, ep_axis=None,
    )
    kw.update(overrides)
    return ModelConfig(**kw)
