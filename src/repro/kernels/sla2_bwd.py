"""Pallas TPU backward kernels for the SLA2 sparse branch (paper Algorithm 3).

Per the paper's QAT design the backward is always full precision, recomputing
P from the original (smoothed) Q/K and the forward LSE.

Two kernels:

* ``_dq_kernel`` — grid (BH, T_m, K_sel), the same routed-index structure as
  the forward: dQ_i accumulates over the row's selected blocks in VMEM
  scratch and is written once.

* ``_dkv_kernel`` — the scatter direction.  TPU Pallas has no atomics, so we
  make the writes *monotonic* instead: the (i, jj) -> j routed pairs are
  counting-sorted by j (cheap jnp argsort outside the kernel, O(T_m K_sel)
  ints), giving ``js[bh, p]`` / ``is_[bh, p]``, packed into one flat SMEM
  table (``(j * T_m + i) * 2 + valid`` per pair).  The grid is (BH, P)
  and the dK/dV output BlockSpec follows j; consecutive grid
  steps that share j hit the same resident VMEM block, so accumulating into
  the output ref is race-free by construction.  On the first visit of each j
  the block is zeroed; kv blocks never selected by any row are zeroed outside
  the kernel.  This replaces the paper's CUDA atomic-add pattern with a
  TPU-native revisit schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (NEG_INF, default_interpret, pack_selection,
                               row_groups, stat_col)


# ---------------------------------------------------------------------------
# dQ
# ---------------------------------------------------------------------------

def _dq_kernel(sel_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
               dq_ref,
               dq_acc,
               *, block_q: int, block_k: int, t_m: int, k_sel: int,
               causal: bool, prefix_len: int, sm_scale: float):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    sel = sel_ref[(bh * t_m + i) * k_sel + jj]     # ops.pack_selection
    j = sel >> 1
    is_valid = (sel & 1) == 1

    @pl.when(is_valid)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = stat_col(lse_ref[0]).astype(jnp.float32)   # (b_q, 1)
        dd = stat_col(dd_ref[0]).astype(jnp.float32)     # (b_q, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            vis = rows >= cols
            if prefix_len:
                vis = jnp.logical_or(vis, cols < prefix_len)
            s = jnp.where(vis, s, NEG_INF)
        lse_safe = jnp.where(lse > NEG_INF * 0.5, lse, 0.0)
        p = jnp.exp(s - lse_safe)
        p = jnp.where((s > NEG_INF * 0.5) & (lse > NEG_INF * 0.5), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd) * sm_scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jj == k_sel - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# dK / dV
# ---------------------------------------------------------------------------

def _dkv_kernel(pair_ref,
                q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                dk_ref, dv_ref,
                *, block_q: int, block_k: int, t_m: int, n_pairs: int,
                causal: bool, prefix_len: int, sm_scale: float):
    bh = pl.program_id(0)
    p_ = pl.program_id(1)

    pair = pair_ref[bh * n_pairs + p_]     # (j * t_m + i) * 2 + valid
    j = (pair >> 1) // t_m
    i = (pair >> 1) % t_m
    is_valid = (pair & 1) == 1
    prev = pair_ref[bh * n_pairs + jnp.maximum(p_ - 1, 0)]
    first = jnp.logical_or(p_ == 0, (prev >> 1) // t_m != j)

    @pl.when(first)
    def _zero():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    @pl.when(is_valid)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = stat_col(lse_ref[0]).astype(jnp.float32)   # (b_q, 1)
        dd = stat_col(dd_ref[0]).astype(jnp.float32)     # (b_q, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            vis = rows >= cols
            if prefix_len:
                vis = jnp.logical_or(vis, cols < prefix_len)
            s = jnp.where(vis, s, NEG_INF)
        lse_safe = jnp.where(lse > NEG_INF * 0.5, lse, 0.0)
        p = jnp.exp(s - lse_safe)
        p = jnp.where((s > NEG_INF * 0.5) & (lse > NEG_INF * 0.5), p, 0.0)
        dv_ref[0] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd) * sm_scale
        dk_ref[0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def sort_pairs(idx: jax.Array, valid: jax.Array):
    """Counting-sort routed (i, jj) pairs by kv block id.

    idx, valid: (BH, T_m, K_sel).  Returns (js, is_, vs) each (BH, P) with
    P = T_m * K_sel, sorted ascending by j (invalid pairs keep their j, which
    duplicates a real selected block of the same row — harmless since they
    are skipped, and they never introduce a visit to an unselected block)."""
    bh, t_m, k_sel = idx.shape
    p = t_m * k_sel
    js = idx.reshape(bh, p)
    is_ = jnp.broadcast_to(jnp.arange(t_m, dtype=jnp.int32)[:, None],
                           (t_m, k_sel)).reshape(1, p)
    is_ = jnp.broadcast_to(is_, (bh, p))
    vs = valid.reshape(bh, p).astype(jnp.int32)
    order = jnp.argsort(js, axis=-1, stable=True)
    take = lambda x: jnp.take_along_axis(x, order, axis=-1)
    return take(js).astype(jnp.int32), take(is_).astype(jnp.int32), take(vs)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "prefix_len",
                     "interpret"))
def sparse_flash_bwd(q, k, v, idx, valid, o, lse, do, *, block_q: int,
                     block_k: int, causal: bool, prefix_len: int = 0,
                     interpret: bool | None = None):
    """Backward of the sparse branch. Returns (dq, dk, dv).

    Always full precision (QAT backward); `lse`/`o` come from the (possibly
    low-bit) forward.  `k` must be the same (smoothed) tensor the forward saw.
    """
    interpret = default_interpret(interpret)
    bh, n_q, d = q.shape
    n_kv = k.shape[1]
    t_m, t_n = n_q // block_q, n_kv // block_k
    k_sel = idx.shape[-1]
    sm_scale = 1.0 / (d ** 0.5)

    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # lane-dense (BH, 1, N_q) statistics: one (1, b_q) row per query block
    lse_r = lse.reshape(bh, 1, n_q)
    dd_r = dd.reshape(bh, 1, n_q)
    validi = valid.astype(jnp.int32)

    # ---- dQ ----
    dq_kernel = functools.partial(
        _dq_kernel, block_q=block_q, block_k=block_k, t_m=t_m, k_sel=k_sel,
        causal=causal, prefix_len=prefix_len, sm_scale=sm_scale)
    sel = pack_selection(idx, validi).reshape(bh, -1)
    dq_parts = []
    # scalar-prefetch tables live in SMEM: split B*H over calls that read
    # their rows of every operand in place (row0 offsets the index maps)
    for row0, rows in row_groups(bh, t_m * k_sel):
        def kv_block(b, i, jj, sel, row0=row0):
            return (row0 + b, sel[(b * t_m + i) * k_sel + jj] >> 1, 0)
        q_block = lambda b, i, jj, sel, row0=row0: (row0 + b, i, 0)
        stat_block = lambda b, i, jj, sel, row0=row0: (row0 + b, 0, i)
        dq_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, t_m, k_sel),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, 1, block_q), stat_block),
                pl.BlockSpec((1, 1, block_q), stat_block),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, jj, sel: (b, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        )
        (dq,) = pl.pallas_call(
            dq_kernel,
            grid_spec=dq_spec,
            out_shape=[jax.ShapeDtypeStruct((rows, n_q, d), q.dtype)],
            interpret=interpret,
            name="sla2_sparse_bwd_dq",
        )(sel[row0:row0 + rows].reshape(-1), q, k, v, do, lse_r, dd_r)
        dq_parts.append(dq)
    dq = jnp.concatenate(dq_parts)

    # ---- dK / dV ----
    js, is_, vs = sort_pairs(idx, validi)
    p_total = js.shape[-1]
    pairs = (js * t_m + is_) * 2 + vs                 # one SMEM word a pair
    dkv_kernel = functools.partial(
        _dkv_kernel, block_q=block_q, block_k=block_k, t_m=t_m,
        n_pairs=p_total, causal=causal, prefix_len=prefix_len,
        sm_scale=sm_scale)
    dk_parts, dv_parts = [], []
    for row0, rows in row_groups(bh, p_total):
        def pair_at(b, p, pr):
            return pr[b * p_total + p] >> 1
        def q_block(b, p, pr, row0=row0):
            return (row0 + b, pair_at(b, p, pr) % t_m, 0)
        def kv_block(b, p, pr, row0=row0):
            return (row0 + b, pair_at(b, p, pr) // t_m, 0)
        def stat_block(b, p, pr, row0=row0):
            return (row0 + b, 0, pair_at(b, p, pr) % t_m)
        def out_block(b, p, pr):
            return (b, pair_at(b, p, pr) // t_m, 0)
        dkv_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, p_total),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, 1, block_q), stat_block),
                pl.BlockSpec((1, 1, block_q), stat_block),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), out_block),
                pl.BlockSpec((1, block_k, d), out_block),
            ],
        )
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid_spec=dkv_spec,
            out_shape=[
                jax.ShapeDtypeStruct((rows, n_kv, d), jnp.float32),
                jax.ShapeDtypeStruct((rows, n_kv, d), jnp.float32),
            ],
            interpret=interpret,
            name="sla2_sparse_bwd_dkv",
        )(pairs[row0:row0 + rows].reshape(-1), q, k, v, do, lse_r, dd_r)
        dk_parts.append(dk)
        dv_parts.append(dv)
    dk = jnp.concatenate(dk_parts)
    dv = jnp.concatenate(dv_parts)

    # zero kv blocks never visited by any valid pair
    visited = jax.vmap(
        lambda jr, vr: jnp.zeros((t_n,), jnp.int32).at[jr].add(vr)
    )(js, vs) > 0                                       # (BH, T_n)
    gate = jnp.repeat(visited, block_k, axis=-1)[..., None]
    dk = jnp.where(gate, dk, 0.0).astype(q.dtype)
    dv = jnp.where(gate, dv, 0.0).astype(q.dtype)
    return dq, dk, dv
