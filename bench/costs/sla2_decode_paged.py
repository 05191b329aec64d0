"""``sla2_decode_paged_<q>_kv_<kv>``: one SLA2 decode step of a batch of
slots over the page pool (one pallas_call per layer and engine step).

Work of the algorithm for one decoding row at context length t (t tokens
cached, the new one included), with Hkv KV heads of H / Hkv query heads,
keeping n_sel = min(k_sel, t // block_k + 1) pages:
    bytes       the kept K and V pages (bf16): 2 * n_sel * block_k * Hkv *
                d * 2; the slot's linear totals h (d x d) and z (d) per KV
                head in f32: Hkv * (d * d + d) * 4; q in and o out (bf16):
                2 * H * d * 2
    operations  softmax branch over the kept tokens: 4 * H * d * n_tok;
                linear branch: phi(q) against the totals, 2 * H * (d * d
                + d), and the kept complete blocks taken out of them,
                4 * H * d * n_tok
Decode reads far more bytes than it computes on, so the bandwidth bounds
it; the bf16 peak bounds the operations.
"""
PEAK_OPS = "flops_bf16"
KERNEL = "sla2_decode_paged"


def per_row(t: int, *, heads: int, kv_heads: int, d: int, block_k: int,
            k_sel: int) -> tuple:
    n_sel = min(k_sel, (t - 1) // block_k + 1)
    n_tok = min(t, n_sel * block_k)
    nbytes = (2.0 * n_sel * block_k * kv_heads * d * 2
              + kv_heads * (d * d + d) * 4.0 + 2.0 * heads * d * 2)
    ops = 8.0 * heads * d * n_tok + 2.0 * heads * (d * d + d)
    return ops, nbytes


def ideal_s(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks[PEAK_OPS], nbytes / peaks["hbm_bw"])
