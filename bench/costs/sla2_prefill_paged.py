"""``sla2_prefill_paged_kv_<kv>``: one prefill chunk of one slot, exact
causal attention of the chunk's queries over the slot's cached pages and
the chunk itself (one pallas_call per layer and engine step).

Work of the algorithm for a chunk of n queries at offset o (tokens already
cached), H query heads and Hkv KV heads of size d:
    operations  QK^T and PV over the visible keys:
                4 * H * d * sum_{i < n} (o + i + 1)
    bytes       K and V of the o + n visible tokens read once (bf16):
                2 * (o + n) * Hkv * d * 2; q read and o written (bf16):
                2 * n * H * d * 2
"""
PEAK_OPS = "flops_bf16"
KERNEL = "sla2_prefill_paged"


def per_call(offset: int, n: int, *, heads: int, kv_heads: int,
             d: int) -> tuple:
    keys = n * offset + n * (n + 1) / 2.0
    ops = 4.0 * heads * d * keys
    nbytes = 2.0 * (offset + n) * kv_heads * d * 2 + 2.0 * n * heads * d * 2
    return ops, nbytes


def ideal_s(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks[PEAK_OPS], nbytes / peaks["hbm_bw"])
