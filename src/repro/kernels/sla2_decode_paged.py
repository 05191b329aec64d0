"""Fused paged-attention Pallas decode kernel for SLA2 serving.

Design (mirrors ``sla2_fwd.py``'s scalar-prefetch structure, applied to the
serving page pool):

  * The continuous-batching engine keeps K/V in a shared pool of physical
    pages ``(P, Hkv, bk, Dh)``; a host-side page table maps each slot's
    logical blocks to physical pages.  The jnp reference decode
    (``models/attention._sla2_decode_paged``) materialises copies twice per
    step — ``_gather_blocks`` builds a ``(B, Hkv, K_sel, bk, Dh)`` copy of
    every routed page before the softmax/einsum chain — so HBM traffic is
    ~3x the bytes actually needed (gather write + re-read, then PV re-read).

  * This kernel reads the routed pages DIRECTLY from the pool: the physical
    page ids selected by the router arrive as a
    ``pltpu.PrefetchScalarGridSpec`` scalar-prefetch operand, and the K/V
    BlockSpec index_maps resolve logical->physical through them, so each
    selected page is DMA'd exactly once and unselected pages are never
    touched.  Grid = ``(B * Hkv, K_sel)``: one program row per (slot,
    kv-head), iterating over that row's routed pages.

  * GQA: the kv head's whole query group (``n_rep`` query heads) rides in
    one ``(n_rep, Dh)`` q tile, so the QK^T / PV matmuls batch the group on
    the MXU and the routed pages are fetched once per KV head, not once per
    query head.

  * Online softmax state (m, l, acc) lives in VMEM scratch across the
    innermost ``jj`` axis (same recurrence as ``sla2_fwd._fwd_kernel``).

  * The LINEAR branch rides the same memory pass: SLA2 decode evaluates
    O_l over the complement of the selected blocks via the complement trick
    (running totals h_tot/z_tot minus the selected complete blocks), and the
    subtraction term needs exactly the K/V tiles the sparse branch already
    has in VMEM — phi(q)·phi(k_jk)·v_jk is accumulated into scratch
    alongside the softmax state, instead of a second gather + einsum chain.

  * The alpha-sigmoid combine (Eq. 13, last-block alpha at decode) is fused
    into the finalize step, so the kernel writes the *final* attention
    output: one HBM traversal per decoded token end to end.

  * QAT low-bit mode reuses the per-tile INT8/FP8 path of ``sla2_fwd``
    (Q/K per-tile symmetric, P fixed-scale, V per-tile); the linear branch
    stays fp32, per the paper's QAT design (only the sparse branch is
    quantized).

``sla2_decode_verify`` extends the same grid from one query row per
(slot, kv head) to ``W = draft_len + 1`` rows — the multi-token verify
pass of self-speculative decoding (draft W-1 tokens with the linear
branch, verify the whole window in one sparse paged pass).  Each window
row rides its own routed pages / length / effective linear totals, so the
position-level mask is simultaneously the causal intra-window mask; see
docs/speculative.md.

``dense_decode_fused`` / ``dense_decode_verify`` are the DENSE
(``mechanism='full'`` — and the dense-decoding ``sla`` / ``sparse_only``
baselines) counterparts: the same ``(B*Hkv, W, pages)`` grid family with
the page-table row itself as the scalar-prefetch operand — every mapped
page streams through one online softmax per (slot, kv head, window row),
the sliding-window / prefix-LM masks fold into the position mask, and
``W > 1`` gives non-SLA2 stacks the multi-token verify window speculative
decoding needs.

``paged_flash_prefill`` is the chunked-prefill counterpart: exact causal
flash attention of one slot's chunk over its paged history, with the page
table as the scalar-prefetch operand — replacing the ``_gather_pages``
materialisation of a contiguous ``(B, maxP*bk, Dh)`` per-slot view.
Sliding-window layers ride the same kernel: the window constraint is one
more in-register mask term, and pages entirely below every query's window
start are skipped via the validity prefetch flags.

All entry points run compiled on TPU and fall back to interpret mode on
CPU (``ops.default_interpret``).

Sharded serving (``EngineConfig.mesh``) wraps these five entry points in
``shard_map`` — decode/verify split the slot (batch) axis, prefill the
KV-head axis, with the page pool replicated into every shard's body —
see ``distributed/shard_paged.ENTRY_AXES``; the kernels themselves are
mesh-agnostic and always see full pools plus a shard of rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (INT8_MAX, NEG_INF, STAT_LANES,
                               default_interpret, qdot as _qdot,
                               quantize_tile as _quantize_tile, stat_col)


# ---------------------------------------------------------------------------
# Fused decode: sparse flash + linear complement correction + alpha combine
# ---------------------------------------------------------------------------

def _decode_kernel(*refs, block_k: int, wdw: int, k_sel: int, hkv: int,
                   quant_bits: str, kv_quant: str, sm_scale: float):
    """Shared decode/verify kernel body over grid ``(B*Hkv, W, K_sel)``.

    ``W`` is the query-window axis: single-token decode runs it at 1, the
    speculative multi-token verify at ``draft_len + 1`` rows per slot.  Each
    (g, w) program row owns its own routed pages, length ``t_new`` and
    linear totals, so the per-position causal mask (``cols < t``) doubles as
    the intra-window causal mask — window token w+1 sits at position t_w and
    is invisible to row w's queries.

    The scalar-prefetch tables are flat (one SMEM word per routed entry):
    ``phys`` holds the routed physical page ids and ``sel`` packs the
    logical block id with the valid / complete flags
    (``(jlog * 2 + valid) * 2 + complete``).

    With ``kv_quant != 'none'`` the K/V pool holds low-bit codes and two
    extra operands carry the per-row scales, prefetched by the SAME routed
    physical page id as the K/V tiles; the tiles are dequantized in
    registers (codes * scale, ops.dequant_rows' formula) before the MXU
    dots."""
    if kv_quant == "none":
        (phys_ref, sel_ref, tnew_ref,                           # SMEM
         q_ref, k_ref, v_ref, h_ref, z_ref, a_ref,              # in
         o_ref,                                                 # out
         acc, m_i, l_i, lnum, lden) = refs                      # VMEM
        ks_ref = vs_ref = None
    else:
        (phys_ref, sel_ref, tnew_ref,
         q_ref, k_ref, v_ref, ks_ref, vs_ref, h_ref, z_ref, a_ref,
         o_ref,
         acc, m_i, l_i, lnum, lden) = refs
    g = pl.program_id(0)           # slot * Hkv + kv head
    w = pl.program_id(1)           # query row within the verify window
    jj = pl.program_id(2)          # routed-page index

    @pl.when(jj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)
        lnum[...] = jnp.zeros_like(lnum)
        lden[...] = jnp.zeros_like(lden)

    sel = sel_ref[(g * wdw + w) * k_sel + jj]
    is_valid = ((sel >> 1) & 1) == 1
    j = sel >> 2                   # logical block id (for positions)
    t = tnew_ref[(g // hkv) * wdw + w]   # row length incl. this token

    @pl.when(is_valid)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)     # (n_rep, Dh)
        k = k_ref[0, 0].astype(jnp.float32)     # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        if kv_quant != "none":
            # in-register dequant of the pool codes (per token row)
            k = k * _head_scale(ks_ref, g % hkv)
            v = v * _head_scale(vs_ref, g % hkv)
        if quant_bits == "none":
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
        else:
            q_c, q_s = _quantize_tile(q, quant_bits)
            k_c, k_s = _quantize_tile(k, quant_bits)
            s = _qdot(q_c, q_s, k_c, k_s, transpose_b=True) * sm_scale

        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(cols < t, s, NEG_INF)     # ragged page tail

        m_prev = m_i[:, :1]                     # (n_rep, 1) of the lanes
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
        corr = jnp.exp(jnp.where(m_prev > NEG_INF * 0.5, m_prev, m_safe)
                       - m_safe)
        l_new = l_i[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        l_i[...] = jnp.broadcast_to(l_new, l_i.shape)
        if quant_bits == "none":
            o_tmp = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        elif quant_bits == "int8":
            p_c = jnp.round(p * INT8_MAX).astype(jnp.int8)
            v_c, v_s = _quantize_tile(v, "int8")
            o_tmp = _qdot(p_c, 1.0 / INT8_MAX, v_c, v_s, transpose_b=False)
        else:  # fp8
            p_c, p_s = _quantize_tile(p, "fp8")
            v_c, v_s = _quantize_tile(v, "fp8")
            o_tmp = _qdot(p_c, p_s, v_c, v_s, transpose_b=False)
        acc[...] = acc[...] * corr + o_tmp
        m_i[...] = jnp.broadcast_to(m_new, m_i.shape)

        # linear-branch correction: this page is a selected COMPLETE block,
        # so its phi(k).v / phi(k) mass must leave the complement totals.
        # The tiles are already resident — no second gather.  fp32 always.
        @pl.when((sel & 1) == 1)
        def _linear_sub():
            qf = jax.nn.softmax(q, axis=-1)      # phi(q), (n_rep, Dh)
            kf = jax.nn.softmax(k, axis=-1)      # phi(k), (bk, Dh)
            ls = jax.lax.dot_general(
                qf, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (n_rep, bk)
            lnum[...] += jax.lax.dot_general(
                ls, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            lden[...] += ls.sum(axis=-1, keepdims=True)

    @pl.when(jj == k_sel - 1)
    def _finalize():
        l_safe = jnp.maximum(l_i[:, :1], 1e-20)
        o_s = acc[...] / l_safe
        qf = jax.nn.softmax(q_ref[0, 0].astype(jnp.float32), axis=-1)
        den_tot = (qf * z_ref[0, 0]).sum(axis=-1, keepdims=True)  # (n_rep,1)
        num = jax.lax.dot_general(
            qf, h_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) - lnum[...]
        den = den_tot - lden[:, :1]
        # relative empty-complement threshold (cancellation residuals != 0)
        den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)
        o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)
        a = jax.nn.sigmoid(stat_col(a_ref[0]).astype(jnp.float32))
        a_eff = jnp.where(den > 0, a, 1.0)                      # (n_rep, 1)
        o_ref[0, 0] = (a_eff * o_s + (1.0 - a_eff) * o_l).astype(o_ref.dtype)


def _head_scale(scale_ref, h):
    """The per-row scales of kv head ``h`` from a ``(1, Hkv, bk)`` block
    of the scale pool, as a ``(bk, 1)`` column.  The block spans every kv
    head because a one-head ``(1, 1, bk)`` block breaks Mosaic's tiling
    rule on the last two dims."""
    return stat_col(scale_ref[0, pl.ds(h, 1), :])


def _scale_spec(block_k, hkv, page_of):
    """BlockSpec of a ``(P, Hkv, bk)`` scale pool: the whole page row of
    the physical page ``page_of(*grid_indices, *prefetch_refs)``."""
    return pl.BlockSpec((1, hkv, block_k),
                        lambda *a: (page_of(*a), 0, 0))


def _call_decode_kernel(q, k_pages, v_pages, phys, jlog, valid, complete,
                        t_new, h_tot, z_tot, alpha, *, block_k: int,
                        quant_bits: str, kv_quant: str,
                        k_scale, v_scale, interpret: bool | None):
    """Shared pallas_call wrapper for decode (W=1) and verify (W=k+1).

    Window-shaped operands: q (B, Hkv, W, n_rep, Dh); phys/jlog/valid/
    complete (B, Hkv, W, K_sel); t_new (B, W); h_tot (B, Hkv, W, Dh, Dh);
    z_tot (B, Hkv, W, Dh); alpha (B, Hkv, n_rep) — alpha is shared across
    the window (decode always uses the last query block's alpha).
    With ``kv_quant != 'none'``, k_scale/v_scale (P, Hkv, bk) ride two
    extra operands whose BlockSpecs resolve through the same routed
    physical page id as K/V, so scales are prefetched with the pages.
    Returns o (B, Hkv, W, n_rep, Dh) f32."""
    interpret = default_interpret(interpret)
    b, hkv, wdw, n_rep, dh = q.shape
    k_sel = phys.shape[-1]
    bk = block_k
    g_tot = b * hkv
    sm_scale = 1.0 / (dh ** 0.5)

    # flat scalar-prefetch tables (SMEM pads a 2-D table's rows to 128
    # words): routed page ids, and the packed logical id / valid /
    # complete flags, in (slot, kv head, window row, routed page) order
    phys_t = phys.reshape(-1).astype(jnp.int32)
    sel_t = ((jlog.astype(jnp.int32) * 2 + valid.astype(jnp.int32)) * 2
             + complete.astype(jnp.int32)).reshape(-1)
    q_f = q.reshape(g_tot, wdw, n_rep, dh)
    h_f = h_tot.reshape(g_tot, wdw, dh, dh)
    z_f = z_tot.reshape(g_tot, wdw, 1, dh)          # lane-dense rows
    a_f = alpha.reshape(g_tot, 1, n_rep)

    def page(g, w, jj, ph, se, tn):
        return ph[(g * wdw + w) * k_sel + jj]

    page_spec = pl.BlockSpec(
        (1, 1, bk, dh),
        lambda g, w, jj, ph, se, tn: (page(g, w, jj, ph, se, tn), g % hkv,
                                      0, 0))
    row_spec = lambda shape: pl.BlockSpec(
        (1, 1) + shape, lambda g, w, jj, *_: (g, w, 0, 0))
    in_specs = [row_spec((n_rep, dh)), page_spec, page_spec]
    operands = [q_f, k_pages, v_pages]
    if kv_quant != "none":
        in_specs += [_scale_spec(bk, hkv, page)] * 2
        operands += [k_scale, v_scale]
    in_specs += [
        row_spec((dh, dh)),
        row_spec((1, dh)),
        pl.BlockSpec((1, 1, n_rep), lambda g, w, jj, *_: (g, 0, 0)),
    ]
    operands += [h_f, z_f, a_f]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g_tot, wdw, k_sel),
        in_specs=in_specs,
        out_specs=[row_spec((n_rep, dh))],
        scratch_shapes=[
            pltpu.VMEM((n_rep, dh), jnp.float32),           # acc
            pltpu.VMEM((n_rep, STAT_LANES), jnp.float32),   # m_i
            pltpu.VMEM((n_rep, STAT_LANES), jnp.float32),   # l_i
            pltpu.VMEM((n_rep, dh), jnp.float32),           # lnum
            pltpu.VMEM((n_rep, STAT_LANES), jnp.float32),   # lden
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_k=bk, wdw=wdw, k_sel=k_sel, hkv=hkv,
        quant_bits=quant_bits, kv_quant=kv_quant, sm_scale=sm_scale)
    (o,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((g_tot, wdw, n_rep, dh),
                                        jnp.float32)],
        interpret=interpret,
        name=f"sla2_decode_paged_{quant_bits}_kv_{kv_quant}",
    )(phys_t, sel_t, t_new.astype(jnp.int32).reshape(-1), *operands)
    return o.reshape(b, hkv, wdw, n_rep, dh)


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "quant_bits", "kv_quant", "interpret"))
def sla2_decode_fused(q, k_pages, v_pages, phys, jlog, valid, complete,
                      t_new, h_tot, z_tot, alpha, *, block_k: int,
                      quant_bits: str = "none", kv_quant: str = "none",
                      k_scale=None, v_scale=None,
                      interpret: bool | None = None):
    """Fused SLA2 paged decode step (the W=1 case of the verify grid).

    q        : (B, Hkv, n_rep, Dh) — the new token's queries, grouped by
               kv head (GQA group rides one MXU tile)
    k_pages  : (P, Hkv, bk, Dh) shared physical page pool (bf16/f32 — or
               int8/fp8 codes when ``kv_quant != 'none'``, with
               k_scale/v_scale (P, Hkv, bk) f32 per-row scales dequantized
               in registers)
    v_pages  : (P, Hkv, bk, Dh)
    phys     : (B, Hkv, K_sel) int32 routed PHYSICAL page ids (0 = trash
               page for invalid entries; skipped, costs no extra traffic)
    jlog     : (B, Hkv, K_sel) int32 routed LOGICAL block ids (positions)
    valid    : (B, Hkv, K_sel) int32 {0,1}
    complete : (B, Hkv, K_sel) int32 {0,1} — selected block is complete,
               i.e. its state is inside h_tot/z_tot and must be subtracted
    t_new    : (B,) int32 per-slot token count INCLUDING the new token
    h_tot    : (B, Hkv, Dh, Dh) f32 complement totals over complete blocks
    z_tot    : (B, Hkv, Dh) f32
    alpha    : (B, Hkv, n_rep) f32 alpha LOGITS (decode uses the last
               query block's alpha; sigmoid is fused into the combine)
    returns  : o (B, Hkv, n_rep, Dh) f32 — final combined attention output
    """
    o = _call_decode_kernel(
        q[:, :, None], k_pages, v_pages, phys[:, :, None], jlog[:, :, None],
        valid[:, :, None], complete[:, :, None], t_new[:, None],
        h_tot[:, :, None], z_tot[:, :, None], alpha,
        block_k=block_k, quant_bits=quant_bits, kv_quant=kv_quant,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return o[:, :, 0]


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "quant_bits", "kv_quant", "interpret"))
def sla2_decode_verify(q, k_pages, v_pages, phys, jlog, valid, complete,
                       t_new, h_tot, z_tot, alpha, *, block_k: int,
                       quant_bits: str = "none", kv_quant: str = "none",
                       k_scale=None, v_scale=None,
                       interpret: bool | None = None):
    """Fused multi-token SLA2 paged verify — the speculative-decoding
    target pass over a draft window of W = draft_len + 1 tokens per slot.

    Same scalar-prefetch page-table structure as ``sla2_decode_fused``,
    with the grid extended from one query row per (slot, kv head) to W rows
    — grid ``(B*Hkv, W, K_sel)``.  Each window row w carries its own routed
    pages, its own length ``t_new[b, w]`` (the position-level mask
    ``cols < t_new`` is therefore also the causal intra-window mask: window
    token w+1 sits at position t_new[w] and is invisible to row w) and its
    own *effective* linear totals — the caller accumulates the totals of
    blocks that complete INSIDE the window into per-row h/z, since the
    cache totals are only committed after host-side acceptance.

    q        : (B, Hkv, W, n_rep, Dh) window queries per kv head
    phys     : (B, Hkv, W, K_sel) int32 routed physical page ids per row
    jlog     : (B, Hkv, W, K_sel) int32 routed logical block ids per row
    valid    : (B, Hkv, W, K_sel) int32 {0,1}
    complete : (B, Hkv, W, K_sel) int32 {0,1} — selected block complete AT
               THIS ROW (inside the row's effective totals)
    t_new    : (B, W) int32 per-row token count incl. the row's token
    h_tot    : (B, Hkv, W, Dh, Dh) f32 per-row effective complement totals
    z_tot    : (B, Hkv, W, Dh) f32
    alpha    : (B, Hkv, n_rep) f32 alpha logits (shared across the window)
    returns  : o (B, Hkv, W, n_rep, Dh) f32
    """
    return _call_decode_kernel(
        q, k_pages, v_pages, phys, jlog, valid, complete, t_new,
        h_tot, z_tot, alpha, block_k=block_k, quant_bits=quant_bits,
        kv_quant=kv_quant, k_scale=k_scale, v_scale=v_scale,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Fused DENSE paged decode / verify (mechanism='full' and the dense-decoding
# sla / sparse_only baselines): online softmax over the page-table pages
# ---------------------------------------------------------------------------

def _dense_decode_kernel(*refs, block_k: int, wdw: int, max_p: int,
                         hkv: int, window, prefix_len: int, quant_bits: str,
                         kv_quant: str, sm_scale: float):
    """Dense decode/verify kernel body over grid ``(B*Hkv, W, maxP)``.

    Unlike the SLA2 kernel there is no router: every visible page of the
    slot streams through the online softmax.  Pages with no position
    visible to a row (beyond its length — or, with a sliding window,
    wholly below its window start) are masked to the TRASH page in
    ``phys`` by the caller and flagged invisible (``phys * 2 + visible``
    per flat prefetch word): the repeated trash index collapses to one
    resident block (no per-page DMA) and the flag skips their compute.
    The per-row position mask ``cols < t`` doubles as the
    causal intra-window mask exactly as in the SLA2 verify grid;
    ``window``/``prefix_len`` fold the sliding-window and prefix-LM
    constraints into the same in-register mask.

    ``quant_bits`` is the QAT tile path the SLA2 decode kernel already has
    (Q/K per-tile symmetric, P fixed-scale int8 / per-tile fp8, V
    per-tile), now shared by the dense family; ``kv_quant`` dequantizes
    low-bit pool codes in registers via the per-row scales prefetched
    through the same physical page id as K/V."""
    if kv_quant == "none":
        (phys_ref, tnew_ref,                                   # SMEM
         q_ref, k_ref, v_ref,                                  # in
         o_ref,                                                # out
         acc, m_i, l_i) = refs                                 # VMEM
        ks_ref = vs_ref = None
    else:
        (phys_ref, tnew_ref,
         q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref,
         acc, m_i, l_i) = refs
    g = pl.program_id(0)           # slot * Hkv + kv head
    w = pl.program_id(1)           # query row within the verify window
    p = pl.program_id(2)           # logical page of the slot's history
    b = g // hkv

    @pl.when(p == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    t = tnew_ref[b * wdw + w]      # row length incl. this window token

    # phys packs the page id with its visibility flag (phys * 2 + valid)
    @pl.when((phys_ref[(b * wdw + w) * max_p + p] & 1) == 1)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)     # (n_rep, Dh)
        k = k_ref[0, 0].astype(jnp.float32)     # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        if kv_quant != "none":
            # in-register dequant of the pool codes (per token row)
            k = k * _head_scale(ks_ref, g % hkv)
            v = v * _head_scale(vs_ref, g % hkv)
        if quant_bits == "none":
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
        else:
            q_c, q_s = _quantize_tile(q, quant_bits)
            k_c, k_s = _quantize_tile(k, quant_bits)
            s = _qdot(q_c, q_s, k_c, k_s, transpose_b=True) * sm_scale

        cols = p * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        vis = cols < t
        if window is not None:
            sw = cols >= t - window
            if prefix_len:
                sw = jnp.logical_or(sw, cols < prefix_len)
            vis = jnp.logical_and(vis, sw)
        s = jnp.where(vis, s, NEG_INF)

        m_prev = m_i[:, :1]                     # (n_rep, 1) of the lanes
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        pr = jnp.exp(s - m_safe)
        pr = jnp.where(s > NEG_INF * 0.5, pr, 0.0)
        corr = jnp.exp(jnp.where(m_prev > NEG_INF * 0.5, m_prev, m_safe)
                       - m_safe)
        l_new = l_i[:, :1] * corr + pr.sum(axis=-1, keepdims=True)
        l_i[...] = jnp.broadcast_to(l_new, l_i.shape)
        if quant_bits == "none":
            o_tmp = jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        elif quant_bits == "int8":
            p_c = jnp.round(pr * INT8_MAX).astype(jnp.int8)
            v_c, v_s = _quantize_tile(v, "int8")
            o_tmp = _qdot(p_c, 1.0 / INT8_MAX, v_c, v_s, transpose_b=False)
        else:  # fp8
            p_c, p_s = _quantize_tile(pr, "fp8")
            v_c, v_s = _quantize_tile(v, "fp8")
            o_tmp = _qdot(p_c, p_s, v_c, v_s, transpose_b=False)
        acc[...] = acc[...] * corr + o_tmp
        m_i[...] = jnp.broadcast_to(m_new, m_i.shape)

    @pl.when(p == max_p - 1)
    def _finalize():
        l_safe = jnp.maximum(l_i[:, :1], 1e-20)
        o_ref[0, 0] = (acc[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "window", "prefix_len", "quant_bits",
                     "kv_quant", "interpret"))
def dense_decode_verify(q, k_pages, v_pages, page_table, t_new, *,
                        block_k: int, window: int | None = None,
                        prefix_len: int = 0, quant_bits: str = "none",
                        kv_quant: str = "none", k_scale=None, v_scale=None,
                        interpret: bool | None = None):
    """Fused dense paged decode over a W-token window — the non-SLA2 leg of
    the paged kernel family, sharing the ``(B*Hkv, W, pages)`` grid shape
    of ``sla2_decode_verify`` with the page-table row replacing the routed
    page ids as the scalar-prefetch operand.

    q          : (B, Hkv, W, n_rep, Dh) window queries grouped by kv head
                 (the GQA group rides one MXU tile, as in the SLA2 kernel)
    k_pages    : (P, Hkv, bk, Dh) shared physical page pool
    v_pages    : (P, Hkv, bk, Dh)
    page_table : (B, maxP) int32 — logical block -> physical page per slot
                 (0 = trash page for unmapped entries; masked by position)
    t_new      : (B, W) int32 per-row token count INCLUDING the row's token
                 — the position mask ``cols < t_new`` is simultaneously the
                 causal intra-window mask
    window     : static sliding-window size (None = full causal); folded
                 into the position mask as ``cols >= t_new - window``
    prefix_len : static prefix-LM length (prefix tokens visible through
                 the window)
    returns    : o (B, Hkv, W, n_rep, Dh) f32

    Grid ``(B*Hkv, W, maxP)``: each (slot, kv head, row) streams the
    slot's logical pages through one online softmax.  Pages with no
    position visible to the row (beyond its length, or wholly below its
    window start) are masked to the trash page in the per-row ``phys``
    prefetch operand — the repeated index elides their DMA, so a
    sliding-window layer's page traffic scales with the window, not the
    context — and their compute is skipped via the visibility flags."""
    interpret = default_interpret(interpret)
    b, hkv, wdw, n_rep, dh = q.shape
    max_p = page_table.shape[1]
    bk = block_k
    g_tot = b * hkv
    sm_scale = 1.0 / (dh ** 0.5)

    t_new = t_new.astype(jnp.int32)
    pages = jnp.arange(max_p, dtype=jnp.int32)
    vis_any = pages[None, None, :] * bk < t_new[:, :, None]
    if window is not None:
        w_ok = (pages[None, None, :] + 1) * bk > t_new[:, :, None] - window
        if prefix_len:
            w_ok = w_ok | (pages[None, None, :] * bk < prefix_len)
        vis_any = vis_any & w_ok
    # per-row physical ids with invisible pages pointed at the trash page:
    # masking the TABLE (not just the compute) is what saves the traffic.
    # One flat SMEM word per (slot, row, page): phys * 2 + visible.
    phys = jnp.where(vis_any, page_table.astype(jnp.int32)[:, None, :], 0)
    phys_t = (phys * 2 + vis_any.astype(jnp.int32)).reshape(-1)

    def page(g, w, p, ph, tn):
        return ph[((g // hkv) * wdw + w) * max_p + p] >> 1

    page_spec = pl.BlockSpec(
        (1, 1, bk, dh),
        lambda g, w, p, ph, tn: (page(g, w, p, ph, tn), g % hkv, 0, 0))
    row_spec = pl.BlockSpec((1, 1, n_rep, dh),
                            lambda g, w, p, ph, tn: (g, w, 0, 0))
    in_specs = [row_spec, page_spec, page_spec]
    operands = [q.reshape(g_tot, wdw, n_rep, dh), k_pages, v_pages]
    if kv_quant != "none":
        in_specs += [_scale_spec(bk, hkv, page)] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g_tot, wdw, max_p),
        in_specs=in_specs,
        out_specs=[row_spec],
        scratch_shapes=[
            pltpu.VMEM((n_rep, dh), jnp.float32),           # acc
            pltpu.VMEM((n_rep, STAT_LANES), jnp.float32),   # m_i
            pltpu.VMEM((n_rep, STAT_LANES), jnp.float32),   # l_i
        ],
    )
    kernel = functools.partial(
        _dense_decode_kernel, block_k=bk, wdw=wdw, max_p=max_p, hkv=hkv,
        window=window, prefix_len=prefix_len, quant_bits=quant_bits,
        kv_quant=kv_quant, sm_scale=sm_scale)
    (o,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((g_tot, wdw, n_rep, dh),
                                        jnp.float32)],
        interpret=interpret,
        name=f"dense_decode_paged_{quant_bits}_kv_{kv_quant}",
    )(phys_t, t_new.reshape(-1), *operands)
    return o.reshape(b, hkv, wdw, n_rep, dh)


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "window", "prefix_len", "quant_bits",
                     "kv_quant", "interpret"))
def dense_decode_fused(q, k_pages, v_pages, page_table, t_new, *,
                       block_k: int, window: int | None = None,
                       prefix_len: int = 0, quant_bits: str = "none",
                       kv_quant: str = "none", k_scale=None, v_scale=None,
                       interpret: bool | None = None):
    """Fused dense paged decode step — the W=1 case of
    ``dense_decode_verify`` (one query row per slot and kv head).

    q        : (B, Hkv, n_rep, Dh) the new token's queries per kv head
    t_new    : (B,) int32 per-slot token count INCLUDING the new token
    returns  : o (B, Hkv, n_rep, Dh) f32

    ``quant_bits`` enables the QAT tile path (previously SLA2-only);
    ``kv_quant`` + k_scale/v_scale read a low-bit pool with in-register
    dequant.  Replaces the jnp ``_gather_pages`` dense decode (which
    materialises a contiguous (B, Hkv, maxP*bk, Dh) per-slot copy every
    step) for ``mechanism='full'`` serving; the gather path stays as the
    parity oracle (see ``models/attention.decode_step_paged``)."""
    o = dense_decode_verify(
        q[:, :, None], k_pages, v_pages, page_table, t_new[:, None],
        block_k=block_k, window=window, prefix_len=prefix_len,
        quant_bits=quant_bits, kv_quant=kv_quant,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return o[:, :, 0]


# ---------------------------------------------------------------------------
# Paged chunked-prefill flash (replaces the _gather_pages per-slot view)
# ---------------------------------------------------------------------------

def _prefill_kernel(*refs, block_k: int, max_p: int, chunk: int,
                    window, prefix_len: int, kv_quant: str,
                    sm_scale: float):
    """Prefill kernel body over grid ``(Hkv, maxP)``; see
    ``paged_flash_prefill``."""
    if kv_quant == "none":
        (phys_ref, vpg_ref, off_ref,                              # SMEM
         q_ref, k_ref, v_ref,                                     # in
         o_ref,                                                   # out
         acc, m_i, l_i) = refs                                    # VMEM
        ks_ref = vs_ref = None
    else:
        (phys_ref, vpg_ref, off_ref,
         q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref,
         acc, m_i, l_i) = refs
    hh = pl.program_id(0)          # kv head
    p = pl.program_id(1)           # logical page of this slot's history

    @pl.when(p == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    @pl.when(vpg_ref[p] == 1)
    def _step():
        q = q_ref[0].astype(jnp.float32)        # (n_rep * C, Dh)
        k = k_ref[0, 0].astype(jnp.float32)     # (bk, Dh)
        if kv_quant != "none":
            # in-register dequant of the pool codes (per token row)
            k = k * _head_scale(ks_ref, hh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        n_rows = q.shape[0]
        # row r of the GQA-stacked q tile is chunk position r % chunk
        rows = off_ref[0] + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, block_k), 0) % chunk
        cols = p * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, block_k), 1)
        vis = rows >= cols
        if window is not None:
            # no prefix exemption needed here: the unconditional
            # `vis |= cols < prefix_len` below already restores prefix
            # columns ((causal & (sw | prefix)) | prefix == (causal & sw)
            # | prefix)
            vis = jnp.logical_and(vis, cols >= rows - window + 1)
        if prefix_len:
            vis = jnp.logical_or(vis, cols < prefix_len)
        s = jnp.where(vis, s, NEG_INF)

        m_prev = m_i[:, :1]                     # (rows, 1) of the lanes
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        pr = jnp.exp(s - m_safe)
        pr = jnp.where(s > NEG_INF * 0.5, pr, 0.0)
        corr = jnp.exp(jnp.where(m_prev > NEG_INF * 0.5, m_prev, m_safe)
                       - m_safe)
        l_new = l_i[:, :1] * corr + pr.sum(axis=-1, keepdims=True)
        l_i[...] = jnp.broadcast_to(l_new, l_i.shape)
        v = v_ref[0, 0].astype(jnp.float32)
        if kv_quant != "none":
            v = v * _head_scale(vs_ref, hh)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_i[...] = jnp.broadcast_to(m_new, m_i.shape)

    @pl.when(p == max_p - 1)
    def _finalize():
        l_safe = jnp.maximum(l_i[:, :1], 1e-20)
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "n_rep", "window", "prefix_len",
                     "kv_quant", "interpret"))
def paged_flash_prefill(q, k_pages, v_pages, page_row, *, offset,
                        block_k: int, n_rep: int,
                        window: int | None = None, prefix_len: int = 0,
                        kv_quant: str = "none", k_scale=None, v_scale=None,
                        interpret: bool | None = None):
    """Causal flash attention of ONE slot's prefill chunk over its paged
    history, reading K/V pages straight from the pool.

    q        : (H, C, Dh) the chunk's queries (all query heads)
    k_pages  : (P, Hkv, bk, Dh) shared page pool; Hkv = H // n_rep
    v_pages  : (P, Hkv, bk, Dh)
    page_row : (maxP,) int32 — the slot's page-table row (0 = unmapped;
               unmapped pages are causally invisible so the trash page read
               is masked)
    offset   : scalar int32 — tokens of this slot already cached; the
               chunk's queries sit at positions [offset, offset + C)
    window   : static sliding-window size (None = full causal) — one more
               in-register mask term, ``cols >= rows - window + 1``
    returns  : o (H, C, Dh) f32

    Grid = (Hkv, maxP): program (h, p) streams logical page p of the slot
    through the online softmax of kv head h, with the GQA group's n_rep
    query heads stacked into one (n_rep*C, Dh) q tile — each page is
    fetched once per KV head, not once per query head (same grouping as
    the decode kernel).  The page table is the scalar-prefetch operand
    resolving logical -> physical, so no contiguous per-slot K/V view is
    ever materialised; pages beyond the chunk's last visible position —
    and, with a sliding window, pages wholly below every chunk query's
    window start — are skipped via the validity prefetch flags.
    """
    interpret = default_interpret(interpret)
    h, c, dh = q.shape
    hkv = h // n_rep
    max_p = page_row.shape[0]
    bk = block_k
    sm_scale = 1.0 / (dh ** 0.5)

    offset = jnp.asarray(offset, jnp.int32)
    # pages whose first token could be visible to any query of the chunk
    pages = jnp.arange(max_p, dtype=jnp.int32)
    vpg = pages * bk < offset + c
    if window is not None:
        # the widest window belongs to the FIRST chunk query (position
        # offset): pages ending at or below offset - window + 1 are
        # invisible to every query — unless the prefix keeps them live
        w_ok = (pages + 1) * bk > offset - window + 1
        if prefix_len:
            w_ok = w_ok | (pages * bk < prefix_len)
        vpg = vpg & w_ok
    # invisible pages point at the trash page: the repeated index elides
    # their DMA (not just their compute, which the vpg flags skip)
    phys_row = jnp.where(vpg, page_row.astype(jnp.int32), 0)
    vpg = vpg.astype(jnp.int32)
    off_arr = offset.reshape(1)
    q_g = q.reshape(hkv, n_rep * c, dh)      # group-stacked query tile

    grid = (hkv, max_p)
    kernel = functools.partial(
        _prefill_kernel, block_k=bk, max_p=max_p, chunk=c,
        window=window, prefix_len=prefix_len, kv_quant=kv_quant,
        sm_scale=sm_scale)
    page_spec = pl.BlockSpec((1, 1, bk, dh),
                             lambda hh, p, ph, vp, of: (ph[p], hh, 0, 0))
    in_specs = [
        pl.BlockSpec((1, n_rep * c, dh),
                     lambda hh, p, ph, vp, of: (hh, 0, 0)),
        page_spec,      # K pages
        page_spec,      # V pages
    ]
    operands = [q_g, k_pages, v_pages]
    if kv_quant != "none":
        in_specs += [_scale_spec(bk, hkv,
                                 lambda hh, p, ph, vp, of: ph[p])] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n_rep * c, dh),
                         lambda hh, p, ph, vp, of: (hh, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_rep * c, dh), jnp.float32),
            pltpu.VMEM((n_rep * c, STAT_LANES), jnp.float32),
            pltpu.VMEM((n_rep * c, STAT_LANES), jnp.float32),
        ],
    )
    (o,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hkv, n_rep * c, dh), jnp.float32)],
        interpret=interpret,
        name=f"sla2_prefill_paged_kv_{kv_quant}",
    )(phys_row, vpg, off_arr, *operands)
    return o.reshape(h, c, dh)
