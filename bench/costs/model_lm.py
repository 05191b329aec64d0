"""Model FLOPs of one engine step of the paged LM, from the
configuration's sizes and what the step served: one prefill chunk (offset,
tokens) and the decode rows (context length t of each).

Per token and layer: projections 2 * d * (H + 2 * Hkv) * dh + 2 * H * dh *
d, SwiGLU MLP 3 * 2 * d * d_ff.  Attention: prefill exact causal, 4 * H *
dh per visible key; decode SLA2, 4 * H * dh per kept token plus the linear
branch 2 * H * (dh * dh + dh).  The head (2 * d * vocab) runs for each
decode row and once per prefill chunk (the logits of its last token).
"""


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def per_token_dense(cfg) -> float:
    d, h, hkv, dh, ff, _, _ = _dims(cfg)
    return 2.0 * d * (h + 2 * hkv) * dh + 2.0 * h * dh * d + 6.0 * d * ff


def step_flops(cfg: dict, prefill, decode_rows, k_sel: int) -> float:
    d, h, hkv, dh, ff, n_layers, vocab = _dims(cfg)
    bk = cfg["sla2"]["block_k"]
    dense = per_token_dense(cfg)
    head = 2.0 * d * vocab
    total = 0.0
    if prefill:
        off, n = prefill
        keys = n * off + n * (n + 1) / 2.0
        total += n_layers * (n * dense + 4.0 * h * dh * keys) + head
    for t in decode_rows:
        n_tok = min(t, min(k_sel, (t - 1) // bk + 1) * bk)
        total += n_layers * (dense + 4.0 * h * dh * n_tok
                             + 2.0 * h * (dh * dh + dh)) + head
    return total
