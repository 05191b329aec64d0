"""Pallas TPU forward kernel for the SLA2 sparse branch (paper Algorithm 2).

Design (TPU adaptation of the paper's CUDA kernel):

  * grid = (B*H, T_m, K_sel): the router's Top-k selection is materialised as
    an index array ``idx[bh, i, jj] -> j`` (sorted ascending) which is fed to
    Pallas, packed with its validity flags into one flat table
    (``ops.pack_selection``), as a *scalar-prefetch* operand.  The K/V
    BlockSpec index_maps read it, so K/V tiles of unselected blocks are
    never fetched from HBM: both compute and memory traffic scale with
    (1 - sparsity).  A table too large for SMEM splits B*H over several
    calls (``ops.row_groups``).
  * online softmax state (m, l, acc) lives in VMEM scratch and persists over
    the innermost jj axis; the output block (and the lane-dense LSE row) is
    written once at jj == K_sel - 1.
  * QAT low-bit mode quantizes tiles on the fly: per-tile symmetric INT8 for
    Q/K (K is pre-smoothed outside the kernel), fixed-scale INT8 for the
    post-exp P tile (values in (0, 1]) and per-tile INT8 for V, so both
    matmuls run INT8xINT8->INT32 on the MXU.  FP8 (e4m3) variant included.
  * causal mode masks the straddling (diagonal) tiles in-register; fully
    visible tiles skip the mask.  Invalid (padding) index entries are skipped
    via ``pl.when`` — their DMA reads duplicate an already-selected block, so
    they cost no extra HBM traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (FP8_MAX, INT8_MAX, NEG_INF,  # noqa: F401
                               STAT_LANES, default_interpret, pack_selection,
                               qdot as _qdot, quantize_tile as _quantize_tile,
                               row_groups)


def _fwd_kernel(sel_ref,                 # scalar prefetch
                q_ref, k_ref, v_ref,     # inputs
                o_ref, lse_ref,          # outputs
                acc, m_i, l_i,           # VMEM scratch
                *, block_q: int, block_k: int, t_m: int, k_sel: int,
                causal: bool, prefix_len: int, quant_bits: str,
                sm_scale: float, kv_len: int):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    sel = sel_ref[(bh * t_m + i) * k_sel + jj]
    j = sel >> 1
    is_valid = (sel & 1) == 1

    @pl.when(is_valid)
    def _step():
        q = q_ref[0].astype(jnp.float32)   # (b_q, d)
        k = k_ref[0].astype(jnp.float32)   # (b_k, d)
        if quant_bits == "none":
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
        else:
            q_c, q_s = _quantize_tile(q, quant_bits)
            k_c, k_s = _quantize_tile(k, quant_bits)
            s = _qdot(q_c, q_s, k_c, k_s, transpose_b=True) * sm_scale

        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            vis = rows >= cols
            if prefix_len:
                vis = jnp.logical_or(vis, cols < prefix_len)
            s = jnp.where(vis, s, NEG_INF)
        if kv_len:
            # ragged last block: keys past the true length are padding
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_i[:, :1]                 # (b_q, 1) of the replicated lanes
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
        corr = jnp.exp(jnp.where(m_prev > NEG_INF * 0.5, m_prev, m_safe)
                       - m_safe)
        l_new = l_i[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        l_i[...] = jnp.broadcast_to(l_new, l_i.shape)

        v = v_ref[0].astype(jnp.float32)
        if quant_bits == "none":
            o_tmp = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        elif quant_bits == "int8":
            # P in [0, 1]: fixed scale 1/127 keeps full int8 range
            p_c = jnp.round(p * INT8_MAX).astype(jnp.int8)
            v_c, v_s = _quantize_tile(v, "int8")
            o_tmp = _qdot(p_c, 1.0 / INT8_MAX, v_c, v_s, transpose_b=False)
        else:  # fp8
            p_c, p_s = _quantize_tile(p, "fp8")
            v_c, v_s = _quantize_tile(v, "fp8")
            o_tmp = _qdot(p_c, p_s, v_c, v_s, transpose_b=False)

        acc[...] = acc[...] * corr + o_tmp
        m_i[...] = jnp.broadcast_to(m_new, m_i.shape)

    @pl.when(jj == k_sel - 1)
    def _finalize():
        l_safe = jnp.maximum(l_i[:, :1], 1e-20)
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)
        m = m_i[:, :1]
        lse = jnp.where(m > NEG_INF * 0.5, m + jnp.log(l_safe), NEG_INF)
        # lane-dense (1, b_q) row of the (BH, 1, N_q) LSE output
        lse_ref[0] = lse.reshape(1, block_q).astype(lse_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "prefix_len",
                     "quant_bits", "interpret", "kv_len"))
def sparse_flash_fwd(q, k, v, idx, valid, *, block_q: int, block_k: int,
                     causal: bool, prefix_len: int = 0,
                     quant_bits: str = "none",
                     interpret: bool | None = None,
                     kv_len: int = 0):
    """Block-sparse flash attention forward.

    q        : (BH, N_q, d)
    k, v     : (BH, N_kv, d)
    idx      : (BH, T_m, K_sel) int32 selected kv-block ids (sorted asc)
    valid    : (BH, T_m, K_sel) int32 {0,1} padding flags
    kv_len   : true key/value length when the sequence is ragged (padded to
               a block_k multiple); keys at positions >= kv_len are masked
               in-register.  0 (default) means all n_kv keys are real.
    returns  : o_s (BH, N_q, d), lse (BH, N_q)
    """
    interpret = default_interpret(interpret)
    bh, n_q, d = q.shape
    n_kv = k.shape[1]
    t_m = n_q // block_q
    k_sel = idx.shape[-1]
    sm_scale = 1.0 / (d ** 0.5)
    if kv_len and kv_len >= n_kv:
        kv_len = 0          # nothing to mask: every key is real
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, t_m=t_m, k_sel=k_sel,
        causal=causal, prefix_len=prefix_len, quant_bits=quant_bits,
        sm_scale=sm_scale, kv_len=kv_len)
    sel = pack_selection(idx, valid).reshape(bh, -1)
    # the routed-index table is prefetched into SMEM: many heads x long
    # sequences split B*H over several calls, each reading its rows of
    # q/k/v in place (row0 offsets the index maps; nothing is sliced)
    outs = [_fwd_call(kernel, sel[row0:row0 + rows].reshape(-1), q, k, v,
                      row0=row0, rows=rows, block_q=block_q,
                      block_k=block_k, t_m=t_m, k_sel=k_sel,
                      interpret=interpret,
                      name=f"sla2_sparse_fwd_{quant_bits}")
            for row0, rows in row_groups(bh, t_m * k_sel)]
    o = jnp.concatenate([o for o, _ in outs])
    lse = jnp.concatenate([lse for _, lse in outs])
    return o, lse.reshape(bh, n_q)


def _fwd_call(kernel, sel, q, k, v, *, row0, rows, block_q, block_k, t_m,
              k_sel, interpret, name):
    """One pallas_call over rows [row0, row0 + rows) of q/k/v."""
    _, n_q, d = q.shape

    def kv_block(b, i, jj, sel):
        return (row0 + b, sel[(b * t_m + i) * k_sel + jj] >> 1, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, t_m, k_sel),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, i, jj, sel: (row0 + b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, jj, sel: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, jj, sel: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, n_q, d), q.dtype),
            jax.ShapeDtypeStruct((rows, 1, n_q), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(sel, q, k, v)
