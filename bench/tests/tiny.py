"""Small overrides that let the cells run on the CPU in tests: the same
harness, engines and references at smoke widths.  The kernels' jnp
gather paths serve on the CPU."""

DIT = {"config": {"num_layers": 2, "dim": 64, "num_heads": 2, "head_dim": 32,
                  "ffn_dim": 128, "text_len": 16, "latent_tokens": 256,
                  "freq_dim": 32,
                  "sla2": {"block_q": 32, "block_k": 16, "k_frac": 0.25,
                           "quant_bits": "int8"}}}
LM = {"config": {"num_hidden_layers": 2, "hidden_size": 96,
                 "num_attention_heads": 6, "num_key_value_heads": 2,
                 "head_dim": 16, "intermediate_size": 192, "vocab_size": 512,
                 "sla2": {"block_q": 32, "block_k": 16, "k_frac": 0.25},
                 "max_position_embeddings": 512}}
LONGDOC = dict(LM, traffic={
    "clients": 3, "engine": {"max_slots": 2, "max_len": 320},
    "sizes": {"prompt": {"dist": "uniform", "lo": 100, "hi": 256},
              "output": {"dist": "uniform", "lo": 16, "hi": 48}},
    "check": {"min_tokens": 300}, "trace_seconds": 1})
# Smoke-size limits, set like the cells' own from readings at this size
# (CPU, seeds 1-3): DiT program 0.0051-0.0070 against the fp8 control's
# 0.066-0.073; LM (served tokens sampled to 300; 13 runs of seeds 1-9,
# 2**31 + 99 and 2**31 + 4242, alone and 8 at once) program 0-0.124
# against the control's 0.268-0.609.
DIT["limits"] = {"limits": {"displacement_rel_l2": 0.03}}
LONGDOC["limits"] = {"limits": {"logit_gap": 0.2}}
CELLS = {"wan_dit_1_3b.denoise_backlog": DIT,
         "internlm2_20b.longdoc_backlog": LONGDOC}
