"""Model FLOPs of one denoise step of one video (a request-step), from
the configuration's sizes.  Per latent token and layer:
    self-attention projections   2 * 4 * d * H * dh
    cross-attention q and o      2 * 2 * d * H * dh
    MLP                          2 * 2 * d * d_ff
    SLA2 sparse branch           4 * H * dh * k_sel * block_k
    SLA2 linear branch           phi(K)^T V per key and phi(Q) state per
                                 query: 2 * 2 * H * dh * dh
    cross-attention scores, PV   4 * H * dh * text_len
plus, per token, the patch embedding and output projection (2 * 2 * d *
in_dim), and per video and layer the text K/V projections
(2 * 2 * text_len * d * H * dh).  Router pooling and norms are not
counted.
"""


def flops_per_request_step(cfg: dict) -> float:
    d, h, dh = cfg["dim"], cfg["num_heads"], cfg["head_dim"]
    n, m = cfg["latent_tokens"], cfg["text_len"]
    s = cfg["sla2"]
    k_sel = max(1, round(s["k_frac"] * (n // s["block_k"])))
    hd = h * dh
    per_tok = (8 * d * hd + 4 * d * hd + 4 * d * cfg["ffn_dim"]
               + 4 * hd * k_sel * s["block_k"] + 4 * h * dh * dh
               + 4 * hd * m)
    per_layer_video = 4 * m * d * hd
    return float(cfg["num_layers"] * (n * per_tok + per_layer_video)
                 + n * 4 * d * cfg["in_dim"])
