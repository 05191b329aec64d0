"""Model FLOPs of one engine step of the paged MLA + expert LM, from the
configuration's sizes and what the step served: one prefill chunk
(offset, tokens), the decode rows (context length t of each) and the
(row, expert) pairs the held experts computed (the engine's counter).

Per token and layer, MLA projections: q 2 * d * H * (dn + dr), latent
2 * d * (r + dr), W_uk absorbed into q 2 * H * dn * r, values
decompressed 2 * H * r * dv, output 2 * H * dv * d.  FFN: the dense
layers' gated MLP 6 * d * I; each expert layer's router 2 * d * E and
shared experts 6 * d * (n_shared * ff); each routed pair 6 * d * ff.
Attention in latent space (d_lat = r + dr wide keys, r wide values):
prefill exact causal, 2 * H * (d_lat + r) per visible key; decode SLA2,
2 * H * (d_lat + r) per kept token plus the linear branch 2 * H * (d_lat
* r + d_lat).  The head (2 * d * vocab) runs for each decode row and once
per prefill chunk (the logits of its last token).
"""


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["kv_lora_rank"], cfg["v_head_dim"])


def per_token(cfg) -> float:
    """Projections and FFNs of one token over the stack, routed experts
    not counted."""
    d, h, dn, dr, r, dv = _dims(cfg)
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    mla = (2.0 * d * h * (dn + dr) + 2.0 * d * (r + dr) + 2.0 * h * dn * r
           + 2.0 * h * r * dv + 2.0 * h * dv * d)
    ff = cfg["moe_intermediate_size"]
    expert_fixed = (2.0 * d * cfg["router_num_experts"]
                    + 6.0 * d * cfg["n_shared_experts"] * ff)
    return (cfg["num_hidden_layers"] * mla
            + n_dense * 6.0 * d * cfg["intermediate_size"]
            + n_moe * expert_fixed)


def step_flops(cfg: dict, prefill, decode_rows, k_sel: int,
               moe_rows: int) -> float:
    d, h, dn, dr, r, dv = _dims(cfg)
    d_lat = r + dr
    layers = cfg["num_hidden_layers"]
    bk = cfg["sla2"]["block_k"]
    tok = per_token(cfg)
    head = 2.0 * d * cfg["vocab_size"]
    total = 6.0 * d * cfg["moe_intermediate_size"] * moe_rows
    if prefill:
        off, n = prefill
        keys = n * off + n * (n + 1) / 2.0
        total += n * tok + layers * 2.0 * h * (d_lat + r) * keys + head
    for t in decode_rows:
        n_tok = min(t, min(k_sel, (t - 1) // bk + 1) * bk)
        total += tok + head + layers * (2.0 * h * (d_lat + r) * n_tok
                                        + 2.0 * h * (d_lat * r + d_lat))
    return total
