"""Jitted wrappers around the SLA2 Pallas kernels.

``sparse_attention_op`` is the custom-VJP boundary: Pallas forward (possibly
low-bit, per QAT) + Pallas full-precision backward (paper Algorithm 3).

``sla2_block_sparse`` is the full SLA2 operator in kernel mode:

    router indices  ->  sparse branch (Pallas)  ->  linear branch over the
    complement (jnp block-state math, autodiff)  ->  alpha combine.

The linear branch uses the *complement trick* (beyond-paper optimization,
DESIGN.md Sec. 2): instead of accumulating h_j over the ~(1-k%) unselected
blocks per row as in Algorithm 2 lines 19-20, we compute the (prefix-)total
state once and *subtract* the k% selected blocks — O(k% T_m T_n) instead of
O((1-k%) T_m T_n) block additions, a ~30x reduction at 97% sparsity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import sla2 as sla2lib
from repro.core import router as routerlib
from repro.core.block_sparse import linear_branch  # complement-trick O_l
from repro.core.quant import smooth_k

_EPS = 1e-12

# ---------------------------------------------------------------------------
# Shared kernel utilities
# ---------------------------------------------------------------------------
# These are used *inside* Pallas kernel bodies (sla2_fwd / sla2_bwd /
# sla2_decode_paged).  The kernel modules import them from here, so this
# module must not import the kernel entry points at module scope — those
# imports live inside the functions that need them.

NEG_INF = -1e30
INT8_MAX = 127.0
FP8_MAX = 448.0


def quantize_tile(x, bits: str):
    """Per-tile symmetric quantization; returns (codes, scale)."""
    ax = jnp.max(jnp.abs(x))
    if bits == "int8":
        s = jnp.maximum(ax / INT8_MAX, 1e-8)
        q = jnp.clip(jnp.round(x / s), -INT8_MAX, INT8_MAX).astype(jnp.int8)
        return q, s
    if bits == "fp8":
        s = jnp.maximum(ax / FP8_MAX, 1e-12)
        return (x / s).astype(jnp.float8_e4m3fn), s
    raise ValueError(bits)


def qdot(a, a_s, b, b_s, *, transpose_b: bool):
    """Low-bit matmul with fp32 dequantized result."""
    if transpose_b:
        dim_nums = (((1,), (1,)), ((), ()))
    else:
        dim_nums = (((1,), (0,)), ((), ()))
    if a.dtype == jnp.int8:
        out = jax.lax.dot_general(a, b, dim_nums,
                                  preferred_element_type=jnp.int32)
        return out.astype(jnp.float32) * (a_s * b_s)
    out = jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                              dim_nums, preferred_element_type=jnp.float32)
    return out * (a_s * b_s)


# ---------------------------------------------------------------------------
# Page-pool storage quantization (the serving ``kv_quant`` knob)
# ---------------------------------------------------------------------------
# The paged KV pool can store its pages low-bit: codes in int8 / fp8-e4m3
# with one fp32 scale per TOKEN ROW (page, kv head, row).  Per-row scales —
# not per-page scalars — because pages are written one token row at a time
# (chunked prefill, decode insertion): each row is quantized exactly once at
# write time and never requantized, so swap round-trips and copy-on-write
# page copies are bit-exact within the quantized representation.  The same
# dequant formula (codes.astype(f32) * scale[..., None]) is used by the jnp
# gather oracle and inside the Pallas kernels, so fused-vs-gather parity on
# a quantized pool is as tight as on fp32.

KV_QUANT_MODES = ("none", "int8", "fp8")


def kv_pool_dtype(kv_quant: str):
    """Storage dtype of the K/V (and SLA2 pooled-key) page arrays."""
    if kv_quant == "int8":
        return jnp.int8
    if kv_quant == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(kv_quant)


def quantize_rows(x, kv_quant: str):
    """Per-row symmetric quantization over the LAST axis; returns
    (codes, scale) with ``scale.shape == x.shape[:-1]`` (f32)."""
    x = x.astype(jnp.float32)
    ax = jnp.max(jnp.abs(x), axis=-1)
    if kv_quant == "int8":
        s = jnp.maximum(ax / INT8_MAX, 1e-8)
        q = jnp.clip(jnp.round(x / s[..., None]), -INT8_MAX,
                     INT8_MAX).astype(jnp.int8)
        return q, s
    if kv_quant == "fp8":
        s = jnp.maximum(ax / FP8_MAX, 1e-12)
        return (x / s[..., None]).astype(jnp.float8_e4m3fn), s
    raise ValueError(kv_quant)


def dequant_rows(codes, scale):
    """Inverse of ``quantize_rows`` — THE dequant formula, shared verbatim
    by the gather oracle and the in-kernel dequant tiles."""
    return codes.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Mosaic layout rules shared by the kernel wrappers
# ---------------------------------------------------------------------------
# Scalar-prefetch operands live in SMEM (1 MiB per core on v5e), where
# Mosaic pads the last dim of a multi-dimensional array to 128 words.  The
# kernels therefore prefetch flat 1-D tables.  The sparse-branch forward
# and backward tables grow with heads x sequence; where one outgrows this
# budget, the wrapper splits its leading (B*H) axis over several calls.
SMEM_PREFETCH_BYTES = 512 * 1024
# Row statistics (running max / sum) are kept lane-replicated in 2-D
# (rows, STAT_LANES) VMEM scratch, the layout Mosaic tiles natively.
STAT_LANES = 128


def pack_selection(idx, valid):
    """Fold routed block ids and their {0,1} validity flags into one flat
    int32 table (``2 * idx + valid``): one SMEM word per routed entry."""
    return (idx.astype(jnp.int32) * 2 + valid.astype(jnp.int32)).reshape(-1)


def row_groups(rows: int, words_per_row: int) -> list[tuple[int, int]]:
    """Split ``rows`` into equal ``(start, size)`` groups, as few as the
    scalar-prefetch budget allows: each group's tables
    (``words_per_row`` int32 words per row) fit SMEM_PREFETCH_BYTES."""
    cap = max(1, SMEM_PREFETCH_BYTES // (4 * words_per_row))
    g = max(d for d in range(1, min(rows, cap) + 1) if rows % d == 0)
    return [(s, g) for s in range(0, rows, g)]


# The sparse-branch forward copies each grid step's routed K and V tiles
# into double-buffered VMEM scratch (v5e's default scoped VMEM limit is
# 16 MiB); these buffers stay within this budget.  Its loop over a step's
# tiles is unrolled, so the tile count is also capped to bound code size.
VMEM_KV_BUFFER_BYTES = 4 * 1024 * 1024
MAX_KV_TILES_PER_STEP = 64


def kv_tiles_per_step(k_sel: int, block_k: int, d: int, dtype) -> int:
    """Routed key blocks one sparse-forward grid step walks (G): all
    ``k_sel`` where their double-buffered K and V tiles fit
    VMEM_KV_BUFFER_BYTES and MAX_KV_TILES_PER_STEP, else the fewest equal
    chunks that do."""
    tile_bytes = 2 * 2 * block_k * d * jnp.dtype(dtype).itemsize
    cap = max(1, min(MAX_KV_TILES_PER_STEP,
                     VMEM_KV_BUFFER_BYTES // tile_bytes))
    chunks = -(-k_sel // cap)
    return -(-k_sel // chunks)


def stat_col(row):
    """A lane-dense ``(1, n)`` row of per-row statistics as the ``(n, 1)``
    column that broadcasts against an ``(n, x)`` tile."""
    return row.reshape(row.shape[-1], 1)


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: every Pallas entry point
    falls back to interpret mode off-TPU (CPU CI, tests, smoke benches) and
    compiled mode on TPU, unless the caller forces a choice."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


# ---------------------------------------------------------------------------
# custom-VJP sparse branch
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def sparse_attention_op(q, k, v, idx, valid,
                        block_q: int, block_k: int, causal: bool,
                        quant_bits: str, prefix_len: int = 0):
    """Sparse branch O_s + LSE. q/k/v: (BH, N, d); idx/valid: (BH, T_m, K_sel).

    In quant mode K is smoothed inside the op (SageAttention colmean shift;
    softmax-invariant, so the identity backward through the smoothing is the
    exact gradient — see kernels/ref.py docstring)."""
    o, lse, _ = _sparse_fwd_impl(q, k, v, idx, valid, block_q, block_k,
                                 causal, quant_bits, prefix_len)
    return o, lse


def _sparse_fwd_impl(q, k, v, idx, valid, block_q, block_k, causal,
                     quant_bits, prefix_len):
    from repro.kernels.sla2_fwd import sparse_flash_fwd
    k_used = smooth_k(k) if quant_bits != "none" else k
    o, lse = sparse_flash_fwd(
        q, k_used, v, idx, valid.astype(jnp.int32),
        block_q=block_q, block_k=block_k, causal=causal,
        prefix_len=prefix_len, quant_bits=quant_bits)
    return o, lse, k_used


def _sparse_vjp_fwd(q, k, v, idx, valid, block_q, block_k, causal,
                    quant_bits, prefix_len):
    o, lse, k_used = _sparse_fwd_impl(q, k, v, idx, valid, block_q, block_k,
                                      causal, quant_bits, prefix_len)
    return (o, lse), (q, k_used, v, idx, valid, o, lse)


def _sparse_vjp_bwd(block_q, block_k, causal, quant_bits, prefix_len, res,
                    cts):
    from repro.kernels.sla2_bwd import sparse_flash_bwd
    q, k_used, v, idx, valid, o, lse = res
    do, _ = cts  # no gradient path through LSE (aux output)
    dq, dk, dv = sparse_flash_bwd(
        q, k_used, v, idx, valid.astype(jnp.int32), o, lse, do,
        block_q=block_q, block_k=block_k, causal=causal,
        prefix_len=prefix_len)
    zi = jnp.zeros_like(idx)
    zv = jnp.zeros_like(valid)
    return dq, dk, dv, zi, zv


sparse_attention_op.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


# ---------------------------------------------------------------------------
# full SLA2 operator (kernel mode)
# ---------------------------------------------------------------------------

def sla2_block_sparse(params: dict, q, k, v, cfg, *, mask_c=None):
    """SLA2 Eq. 13 with Pallas sparse branch. q/k/v: (B, H, N, D)."""
    b, h_num, n, d = q.shape
    rcfg = cfg.router
    flat = lambda x: x.reshape(b * h_num, *x.shape[2:])
    qf, kf, vf = flat(q), flat(k), flat(v)

    with jax.named_scope("sla2.router"):
        idx, valid = routerlib.route_indices(
            params.get("router", {}), qf, kf, rcfg)

    with jax.named_scope("sla2.sparse"):
        o_s, lse = sparse_attention_op(
            qf, kf, vf, idx, valid, rcfg.block_q, rcfg.block_k, rcfg.causal,
            cfg.quant_bits, rcfg.prefix_len)
    with jax.named_scope("sla2.linear"):
        o_l, den = linear_branch(
            qf, kf, vf, idx, valid, block_q=rcfg.block_q,
            block_k=rcfg.block_k, causal=rcfg.causal,
            prefix_len=rcfg.prefix_len)

    with jax.named_scope("sla2.combine"):
        t_m = n // rcfg.block_q
        a_blocks = sla2lib.alpha_for_blocks(params, t_m, h_num)  # (H, T_m)
        a_tok = jnp.repeat(a_blocks, rcfg.block_q, axis=-1)       # (H, N)
        a_tok = jnp.broadcast_to(a_tok[None], (b, h_num, n)).reshape(
            b * h_num, n, 1)
        # empty complement => sparse only
        a_eff = jnp.where(den > _EPS, a_tok, 1.0)
        o = (a_eff * o_s.astype(jnp.float32)
             + (1.0 - a_eff) * o_l.astype(jnp.float32)).astype(q.dtype)
        o = o.reshape(b, h_num, n, d)
    aux = {"idx": idx, "valid": valid, "lse": lse.reshape(b, h_num, n)}
    return o, aux
