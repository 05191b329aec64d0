"""Pallas TPU forward kernel for the SLA2 sparse branch (paper Algorithm 2).

Design (TPU adaptation of the paper's CUDA kernel):

  * grid = (B*H, T_m, ceil(K_sel / G)): one grid step per query block walks
    G of its routed key blocks in an inner loop (G = K_sel where the
    buffers fit, so the last axis is usually 1).  The router's Top-k
    selection is materialised as an index array ``idx[bh, i, jj] -> j``
    (sorted ascending), packed with its validity flags into one flat table
    (``ops.pack_selection``) and fed to Pallas as a *scalar-prefetch*
    operand.  A table too large for SMEM splits B*H over several calls
    (``ops.row_groups``).
  * K and V stay in HBM (``pl.ANY``).  Each step copies its G routed
    block_k x d tiles into double-buffered VMEM scratch (2, G, block_k, d)
    with one DMA semaphore per tile: the step starts the copies of the
    next grid step into the other slot before computing, and waits on a
    tile's K and V copies just before that tile's math (a wait in the
    middle of it stalls the vector pipeline).  Tiles of unselected blocks
    are never fetched, so compute and memory traffic scale with
    (1 - sparsity).  The cross-step prefetch needs the grid walked in
    order, so every axis is "arbitrary".
  * G follows the shapes (``ops.kv_tiles_per_step``): all K_sel blocks
    where both buffers fit ``ops.VMEM_KV_BUFFER_BYTES`` and
    ``ops.MAX_KV_TILES_PER_STEP``, else the fewest equal chunks that do;
    the last chunk's surplus entries are invalid.  The loop over a step's
    G tiles is unrolled.
  * online softmax state: acc lives in VMEM scratch; m and l are carried
    through the loop and kept in scratch across the chunk axis.  The
    output block (and the lane-dense LSE row) is written once, after the
    last chunk.
  * QAT low-bit mode quantizes tiles on the fly: per-tile symmetric INT8 for
    Q/K (K is pre-smoothed outside the kernel; Q once per grid step),
    fixed-scale INT8 for the post-exp P tile (values in (0, 1]) and
    per-tile INT8 for V, so both matmuls run INT8xINT8->INT32 on the MXU.
    FP8 (e4m3) variant included.
  * causal mode masks the straddling (diagonal) tiles in-register; fully
    visible tiles skip the mask.  Invalid (padding) index entries start no
    copy, wait on none and do no compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (FP8_MAX, INT8_MAX, NEG_INF,  # noqa: F401
                               STAT_LANES, default_interpret,
                               kv_tiles_per_step, pack_selection,
                               qdot as _qdot, quantize_tile as _quantize_tile,
                               row_groups)


def _fwd_kernel(sel_ref,                 # scalar prefetch
                q_ref, k_hbm, v_hbm,     # inputs (K, V left in HBM)
                o_ref, lse_ref,          # outputs
                k_buf, v_buf, sems,      # routed K/V tiles and their DMAs
                acc, m_i, l_i,           # VMEM scratch
                *, row0: int, n_rows: int, block_q: int, block_k: int,
                t_m: int, k_sel: int, g: int, n_chunks: int, causal: bool,
                prefix_len: int, quant_bits: str, sm_scale: float,
                kv_len: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    c = pl.program_id(2)
    slot = ((b * t_m + i) * n_chunks + c) % 2

    def entry(b, i, c, jj):
        """(block id, valid) of the jj-th tile of grid step (b, i, c); the
        last chunk's entries past k_sel are invalid."""
        e = c * g + jj
        sel = sel_ref[(b * t_m + i) * k_sel + jnp.minimum(e, k_sel - 1)]
        return sel >> 1, jnp.logical_and(e < k_sel, (sel & 1) == 1)

    def copies(b, j, slot, jj):
        keys = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        return (pltpu.make_async_copy(k_hbm.at[row0 + b, keys],
                                      k_buf.at[slot, jj], sems.at[0, slot, jj]),
                pltpu.make_async_copy(v_hbm.at[row0 + b, keys],
                                      v_buf.at[slot, jj], sems.at[1, slot, jj]))

    def start(b, i, c, slot):
        for jj in range(g):
            j, ok = entry(b, i, c, jj)

            @pl.when(ok)
            def _():
                for cp in copies(b, j, slot, jj):
                    cp.start()

    @pl.when((b == 0) & (i == 0) & (c == 0))
    def _first():
        start(b, i, c, slot)

    # prefetch the next grid step's tiles behind this step's compute
    last_c = c == n_chunks - 1
    last_i = i == t_m - 1
    nb = jnp.where(last_c & last_i, b + 1, b)

    @pl.when(nb < n_rows)
    def _prefetch():
        start(nb, jnp.where(last_c, jnp.where(last_i, 0, i + 1), i),
              jnp.where(last_c, 0, c + 1), 1 - slot)

    @pl.when(c == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q = q_ref[0].astype(jnp.float32)        # (b_q, d)
    if quant_bits != "none":
        q_c, q_s = _quantize_tile(q, quant_bits)

    def tile(jj, carry):
        j, ok = entry(b, i, c, jj)

        def _step(carry):
            m_prev, l_prev = carry          # (b_q, 1) running max and sum
            k_copy, v_copy = copies(b, j, slot, jj)
            # both waits before the tile's math: a wait inside it stalls
            # the vector pipeline
            k_copy.wait()
            v_copy.wait()
            k = k_buf[slot, jj].astype(jnp.float32)   # (b_k, d)
            if quant_bits == "none":
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
            else:
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

            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
            p = jnp.exp(s - m_safe)
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
            corr = jnp.exp(jnp.where(m_prev > NEG_INF * 0.5, m_prev, m_safe)
                           - m_safe)
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)

            v = v_buf[slot, jj].astype(jnp.float32)
            if quant_bits == "none":
                o_tmp = jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            elif quant_bits == "int8":
                # P in [0, 1]: fixed scale 1/127 keeps full int8 range
                p_c = jnp.round(p * INT8_MAX).astype(jnp.int8)
                v_c, v_s = _quantize_tile(v, "int8")
                o_tmp = _qdot(p_c, 1.0 / INT8_MAX, v_c, v_s,
                              transpose_b=False)
            else:  # fp8
                p_c, p_s = _quantize_tile(p, "fp8")
                v_c, v_s = _quantize_tile(v, "fp8")
                o_tmp = _qdot(p_c, p_s, v_c, v_s, transpose_b=False)

            acc[...] = acc[...] * corr + o_tmp
            return m_new, l_new

        return jax.lax.cond(ok, _step, lambda carry: carry, carry)

    # m and l ride the unrolled loop in registers; the scratch keeps them
    # (lane-replicated) across the chunk axis
    m, l = jax.lax.fori_loop(0, g, tile, (m_i[:, :1], l_i[:, :1]),
                             unroll=True)
    m_i[...] = jnp.broadcast_to(m, m_i.shape)
    l_i[...] = jnp.broadcast_to(l, l_i.shape)

    @pl.when(last_c)
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
    if kv_len and kv_len >= n_kv:
        kv_len = 0          # nothing to mask: every key is real
    wider = max(k.dtype, v.dtype, key=lambda t: jnp.dtype(t).itemsize)
    g = kv_tiles_per_step(k_sel, block_k, d, wider)
    static = dict(block_q=block_q, block_k=block_k, t_m=t_m, k_sel=k_sel,
                  g=g, n_chunks=-(-k_sel // g), causal=causal,
                  prefix_len=prefix_len, quant_bits=quant_bits,
                  sm_scale=1.0 / (d ** 0.5), kv_len=kv_len)
    sel = pack_selection(idx, valid).reshape(bh, -1)
    # the routed-index table is prefetched into SMEM: many heads x long
    # sequences split B*H over several calls, each reading its rows of
    # q/k/v in place (row0 offsets the index maps and the K/V copies;
    # nothing is sliced)
    outs = [_fwd_call(sel[row0:row0 + rows].reshape(-1), q, k, v,
                      row0=row0, n_rows=rows, interpret=interpret,
                      name=f"sla2_sparse_fwd_{quant_bits}", **static)
            for row0, rows in row_groups(bh, t_m * k_sel)]
    o = jnp.concatenate([o for o, _ in outs])
    lse = jnp.concatenate([lse for _, lse in outs])
    return o, lse.reshape(bh, n_q)


def _fwd_call(sel, q, k, v, *, row0, n_rows, interpret, name, **static):
    """One pallas_call over rows [row0, row0 + n_rows) of q/k/v."""
    _, n_q, d = q.shape
    block_q, block_k, g = static["block_q"], static["block_k"], static["g"]
    kernel = functools.partial(_fwd_kernel, row0=row0, n_rows=n_rows,
                               **static)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rows, static["t_m"], static["n_chunks"]),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, i, c, sel: (row0 + b, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, c, sel: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, c, sel: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, g, block_k, d), k.dtype),
            pltpu.VMEM((2, g, block_k, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2, g)),
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, n_q, d), q.dtype),
            jax.ShapeDtypeStruct((n_rows, 1, n_q), jnp.float32),
        ],
        # the next step's K/V copies start in this one: walk in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name=name,
    )(sel, q, k, v)
