"""``sla2_sparse_fwd_<bits>``: the SLA2 sparse branch over routed blocks
(bidirectional), one pallas_call per group of (batch x head) rows.

Work of the algorithm for one (batch, head) row of N query tokens, each
query block keeping k_sel key blocks of block_k keys:
    operations  QK^T and PV over the kept keys: 4 * N * k_sel * block_k * d
    bytes       Q, K, V read and O written once in bf16: 4 * N * d * 2,
                plus the f32 log-sum-exp row: 4 * N
The dots run in int8 under QAT (both operands quantized per tile), so the
int8 peak bounds the operations.  Exponentials and the quantization are
not counted.
"""
PEAK_OPS = "ops_int8"
KERNEL = "sla2_sparse_fwd"


def per_row(n: int, d: int, block_k: int, k_sel: int) -> tuple:
    """(operations, bytes) for one (batch, head) row."""
    return 4.0 * n * k_sel * block_k * d, 4.0 * n * d * 2 + 4.0 * n


def ideal_s(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks[PEAK_OPS], nbytes / peaks["hbm_bw"])
