"""``moe_experts_<dtype>``: the grouped gated FFN of the experts a chip
holds (kernels/moe_experts.py), one pallas_call per expert layer and
program.

Work of the algorithm for the calls in which the held experts compute
``rows`` (row, expert) pairs, ``active`` (expert, layer) pairs having at
least one, each expert d wide in and out and ff wide inside:
    bytes       each active expert's weights read once (bf16):
                active * 3 * d * ff * 2; the rows read and written
                (bf16): 2 * rows * d * 2
    operations  gate, up and down projections: 6 * rows * d * ff
Decode routes a few rows to each expert, so the weights' bytes bound it;
the bf16 peak bounds the operations.
"""
PEAK_OPS = "flops_bf16"
KERNEL = "moe_experts"


def per_calls(rows: int, active: int, *, d: int, ff: int) -> tuple:
    """(operations, bytes) of calls that compute ``rows`` pairs over
    ``active`` experts in all."""
    return 6.0 * rows * d * ff, active * 3.0 * d * ff * 2 + 4.0 * rows * d


def ideal_s(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks[PEAK_OPS], nbytes / peaks["hbm_bw"])
