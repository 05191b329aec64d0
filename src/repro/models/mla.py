"""Multi-head Latent Attention (DeepSeek-V2) with SLA2 in latent space.

MLA compresses K/V into a shared latent ``c_kv = x W_dkv`` (rank r) plus a
shared RoPE key ``k_r``; per-head keys/values are linear decompressions
``K_h = c_kv W_uk^h``, ``V_h = c_kv W_uv^h``.

**SLA2 integration (TPU-native adaptation, DESIGN.md §2):** instead of
decompressing K/V and routing in head space, we absorb ``W_uk`` into the
query and run SLA2 entirely in latent space:

    q_tilde_h = [ q_nope_h W_uk^{h,T} ,  q_rope_h ]   in R^{r + d_r}
    k_tilde   = [ rmsnorm(c_kv)       ,  k_rope   ]   shared across heads
    s_h       = q_tilde_h . k_tilde  ==  q_h . K_h    (exactly)

so the sparse branch scores are *identical* to decompressed MLA, the router
pools latent keys (pooling commutes with the decompression since it is
linear), the linear branch's phi-features live on the 576-dim latent, and
the attention "values" are the latents themselves — the per-head value
decompression ``W_uv`` is applied once to the (r-dim) attention output.
This keeps the KV cache at r + d_r per token (MLA's whole point) while the
SLA2 block mask still prunes ~97% of score/PV work.

Used by ``deepseek-v2-lite``; plugs into transformer.py as the attention of
the ``mla_*`` layer kinds.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import masks as masklib
from repro.core import sla2 as sla2lib
from repro.core.attention import phi
from repro.core.router import RouterConfig
from repro.core.sla2 import SLA2Config
from repro.kernels import ops
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """MLA projection geometry: latent rank, nope/rope query split, value
    head dim, and the optional q-LoRA rank (0 = dense q projection)."""
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 0          # 0 => dense q projection (V2-Lite)

    @property
    def qk_head_dim(self) -> int:
        """Per-head query/key width: content (nope) + rotary dims."""
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_dim(self) -> int:
        """The SLA2 working dimension — compressed K/V latent plus the
        shared rope key; one latent-page row stores this many values."""
        return self.kv_lora_rank + self.qk_rope_dim


def init_mla(key, d_model: int, num_heads: int, mcfg: MLAConfig,
             *, mechanism: str, sla2_cfg: Optional[SLA2Config],
             n_q_blocks: int, dtype=jnp.float32) -> dict:
    """Initialise one MLA layer: down/up latent projections, q projection
    (dense or LoRA), output projection, and — for mechanism 'sla2' — the
    latent-space SLA2 router/alpha parameters."""
    ks = jax.random.split(key, 8)
    h = num_heads
    std = d_model ** -0.5
    p = {
        "w_dkv": L.truncated_normal(
            ks[0], (d_model, mcfg.kv_lora_rank + mcfg.qk_rope_dim), dtype, std),
        "kv_norm": L.init_rmsnorm(mcfg.kv_lora_rank, dtype),
        "w_uk": L.truncated_normal(
            ks[1], (mcfg.kv_lora_rank, h * mcfg.qk_nope_dim), dtype,
            mcfg.kv_lora_rank ** -0.5),
        "w_uv": L.truncated_normal(
            ks[2], (mcfg.kv_lora_rank, h * mcfg.v_head_dim), dtype,
            mcfg.kv_lora_rank ** -0.5),
        "w_o": L.truncated_normal(
            ks[3], (h * mcfg.v_head_dim, d_model), dtype,
            (h * mcfg.v_head_dim) ** -0.5),
    }
    if mcfg.q_lora_rank:
        p["w_dq"] = L.truncated_normal(ks[4], (d_model, mcfg.q_lora_rank),
                                       dtype, std)
        p["q_norm"] = L.init_rmsnorm(mcfg.q_lora_rank, dtype)
        p["w_uq"] = L.truncated_normal(
            ks[5], (mcfg.q_lora_rank, h * mcfg.qk_head_dim), dtype,
            mcfg.q_lora_rank ** -0.5)
    else:
        p["w_q"] = L.truncated_normal(ks[4], (d_model, h * mcfg.qk_head_dim),
                                      dtype, std)
    if mechanism == "sla2":
        p["sla2"] = sla2lib.init_sla2_params(
            ks[6], head_dim=mcfg.latent_dim, num_heads=h,
            n_q_blocks=n_q_blocks, cfg=sla2_cfg, dtype=dtype)
    return p


def _latent_qk(params: dict, mcfg: MLAConfig, num_heads: int, x, positions):
    """Project to latent-space queries/keys.

    Returns q_tilde (B, H, N, r+d_r), k_tilde (B, N, r+d_r)."""
    b, n, _ = x.shape
    h = num_heads
    if mcfg.q_lora_rank:
        q = L.rmsnorm(params["q_norm"], x @ params["w_dq"]) @ params["w_uq"]
    else:
        q = x @ params["w_q"]
    q = q.reshape(b, n, h, mcfg.qk_head_dim)
    q_nope = q[..., : mcfg.qk_nope_dim]
    q_rope = L.apply_rope(q[..., mcfg.qk_nope_dim:], positions)

    ckv_full = x @ params["w_dkv"]
    c_kv = L.rmsnorm(params["kv_norm"], ckv_full[..., : mcfg.kv_lora_rank])
    k_rope = L.apply_rope(ckv_full[..., mcfg.kv_lora_rank:], positions)

    # absorb W_uk into q:  q_abs_h = q_nope_h @ W_uk^{h,T}  (B, N, H, r)
    w_uk = params["w_uk"].reshape(mcfg.kv_lora_rank, h, mcfg.qk_nope_dim)
    q_abs = jnp.einsum("bnhd,rhd->bnhr", q_nope, w_uk)
    q_t = jnp.concatenate([q_abs, q_rope], axis=-1)       # (B, N, H, r+d_r)
    k_t = jnp.concatenate([c_kv, k_rope], axis=-1)        # (B, N, r+d_r)
    return q_t.transpose(0, 2, 1, 3), k_t, c_kv


def mla_forward(params: dict, x: jax.Array, positions, *, mcfg: MLAConfig,
                num_heads: int, mechanism: str,
                sla2_cfg: Optional[SLA2Config]) -> jax.Array:
    """Full-sequence MLA attention. x: (B, N, d_model)."""
    b, n, _ = x.shape
    h = num_heads
    q_t, k_t, c_kv = _latent_qk(params, mcfg, h, x, positions)
    # scores must match decompressed MLA: scale by sqrt(qk_head_dim)
    scale_fix = jnp.sqrt(mcfg.latent_dim / mcfg.qk_head_dim).astype(q_t.dtype)
    q_t = q_t * scale_fix  # sla2/full divide by sqrt(latent_dim)

    k_bh = jnp.broadcast_to(k_t[:, None], (b, h, n, k_t.shape[-1]))
    v_bh = jnp.broadcast_to(c_kv[:, None], (b, h, n, c_kv.shape[-1]))
    if mechanism == "sla2":
        o_lat = sla2lib.sla2_attention(params["sla2"], q_t, k_bh, v_bh,
                                       sla2_cfg)
    else:  # dense latent attention
        d_lat = q_t.shape[-1]
        s = jnp.einsum("bhnd,bhmd->bhnm", q_t.astype(jnp.float32),
                       k_bh.astype(jnp.float32)) / jnp.sqrt(d_lat)
        cm = masklib.token_causal_mask(n, n)
        s = jnp.where(cm, s, masklib.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhnm,bhmd->bhnd", p,
                           v_bh.astype(jnp.float32)).astype(x.dtype)
    # decompress values per head:  o_h = o_lat_h @ W_uv^h
    w_uv = params["w_uv"].reshape(mcfg.kv_lora_rank, h, mcfg.v_head_dim)
    o = jnp.einsum("bhnr,rhv->bnhv", o_lat.astype(jnp.float32),
                   w_uv.astype(jnp.float32))
    o = o.reshape(b, n, h * mcfg.v_head_dim).astype(x.dtype)
    return o @ params["w_o"]


# ---------------------------------------------------------------------------
# Decode with the latent block cache
# ---------------------------------------------------------------------------

def init_mla_cache(mcfg: MLAConfig, num_heads: int, batch: int, max_len: int,
                   block_k: int, dtype=jnp.bfloat16) -> dict:
    """Static latent decode cache: raw latents, per-block pooled router
    keys, the linear totals, and the incremental current-block stats."""
    t_n = max_len // block_k
    d_lat = mcfg.latent_dim
    return {
        "k_lat": jnp.zeros((batch, max_len, d_lat), dtype),   # [c_kv; k_rope]
        "pooled_k": jnp.zeros((batch, t_n, d_lat), jnp.float32),
        "h_tot": jnp.zeros((batch, d_lat, mcfg.kv_lora_rank), jnp.float32),
        "z_tot": jnp.zeros((batch, d_lat), jnp.float32),
        "blk_h": jnp.zeros((batch, d_lat, mcfg.kv_lora_rank), jnp.float32),
        "blk_z": jnp.zeros((batch, d_lat), jnp.float32),
        "blk_ksum": jnp.zeros((batch, d_lat), jnp.float32),
        "length": jnp.zeros((), jnp.int32),
    }


def mla_prefill(params: dict, x: jax.Array, positions, cache: dict, *,
                mcfg: MLAConfig, num_heads: int, mechanism: str,
                sla2_cfg: Optional[SLA2Config]):
    """Full-sequence forward + populate the latent cache (N % block_k == 0)."""
    b, n, _ = x.shape
    out = mla_forward(params, x, positions, mcfg=mcfg, num_heads=num_heads,
                      mechanism=mechanism, sla2_cfg=sla2_cfg)
    _, k_t, c_kv = _latent_qk(params, mcfg, num_heads, x, positions)
    bk = sla2_cfg.router.block_k if sla2_cfg else 64
    t_full = n // bk
    cache = dict(cache)
    cache["k_lat"] = jax.lax.dynamic_update_slice(
        cache["k_lat"], k_t.astype(cache["k_lat"].dtype), (0, 0, 0))
    kb = k_t.reshape(b, t_full, bk, -1).astype(jnp.float32)
    cache["pooled_k"] = jax.lax.dynamic_update_slice(
        cache["pooled_k"], kb.mean(axis=-2), (0, 0, 0))
    kf = phi(kb)
    vb = c_kv.reshape(b, t_full, bk, -1).astype(jnp.float32)
    cache["h_tot"] = jnp.einsum("btkd,btkr->bdr", kf, vb)
    cache["z_tot"] = kf.sum(axis=(1, 2))
    cache["length"] = jnp.asarray(n, jnp.int32)
    return out, cache


def mla_decode_step(params: dict, x_t: jax.Array, cache: dict, *,
                    mcfg: MLAConfig, num_heads: int, k_frac: float,
                    block_k: int):
    """One-token MLA-SLA2 decode. x_t: (B, 1, d_model)."""
    b = x_t.shape[0]
    h = num_heads
    d_lat, r = mcfg.latent_dim, mcfg.kv_lora_rank
    bk = block_k
    t = cache["length"]
    positions = jnp.broadcast_to(t[None], (b, 1))
    q_t, k_new, c_new = _latent_qk(params, mcfg, h, x_t, positions)
    scale_fix = jnp.sqrt(d_lat / mcfg.qk_head_dim).astype(jnp.float32)
    q1 = q_t[:, :, 0].astype(jnp.float32) * scale_fix      # (B, H, d_lat)

    cache = dict(cache)
    cache["k_lat"] = jax.lax.dynamic_update_slice(
        cache["k_lat"], k_new.astype(cache["k_lat"].dtype), (0, t, 0))
    t_new = t + 1
    cache["length"] = t_new
    max_len = cache["k_lat"].shape[1]
    t_n = max_len // bk
    cur_blk = (t_new - 1) // bk

    # --- incremental block stats (reset at block start) ---
    k1 = k_new[:, 0].astype(jnp.float32)                   # (B, d_lat)
    at_start = ((t_new - 1) % bk) == 0
    blk_ksum = jnp.where(at_start, 0.0, cache["blk_ksum"]) + k1
    kf1 = phi(k1)
    blk_h = jnp.where(at_start, 0.0, cache["blk_h"]) \
        + kf1[:, :, None] * c_new[:, 0].astype(jnp.float32)[:, None, :]
    blk_z = jnp.where(at_start, 0.0, cache["blk_z"]) + kf1
    fill = ((t_new - 1) % bk) + 1
    cache["pooled_k"] = jax.lax.dynamic_update_slice(
        cache["pooled_k"], (blk_ksum / fill)[:, None], (0, cur_blk, 0))
    completed = (t_new % bk) == 0
    cache["h_tot"] = cache["h_tot"] + jnp.where(completed, blk_h, 0.0)
    cache["z_tot"] = cache["z_tot"] + jnp.where(completed, blk_z, 0.0)
    cache["blk_ksum"], cache["blk_h"], cache["blk_z"] = blk_ksum, blk_h, blk_z

    # --- route over pooled latent keys ---
    sla2_p = params["sla2"]
    rp = sla2_p.get("router", {})
    qr, pk = q1, cache["pooled_k"]
    if rp:
        qr = qr @ rp["proj_q"].astype(jnp.float32)
        pk = pk @ rp["proj_k"].astype(jnp.float32)
    scores = jnp.einsum("bhd,btd->bht", qr, pk) / jnp.sqrt(d_lat)
    blk_ids = jnp.arange(t_n)
    scores = jnp.where(blk_ids[None, None, :] <= cur_blk, scores,
                       masklib.NEG_INF)
    scores = jnp.where(blk_ids[None, None, :] == cur_blk, jnp.inf, scores)
    k_sel = max(1, round(k_frac * t_n))
    top_vals, idx = jax.lax.top_k(scores, k_sel)           # (B, H, K_sel)
    valid = top_vals > masklib.NEG_INF * 0.5

    # --- sparse branch over gathered latent blocks ---
    k_blocks = cache["k_lat"].reshape(b, t_n, bk, d_lat)
    # union of per-head selections gathered per head: (B, H, K_sel, bk, d)
    kg = jnp.take_along_axis(
        k_blocks[:, None], idx[..., None, None], axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bhjkd->bhjk", q1, kg) / jnp.sqrt(d_lat)
    pos = idx[..., None] * bk + jnp.arange(bk)[None, None, None, :]
    vis = (pos < t_new) & valid[..., None]
    s = jnp.where(vis, s, masklib.NEG_INF)
    p = jax.nn.softmax(s.reshape(b, h, -1), axis=-1).reshape(s.shape)
    vg = kg[..., :r]  # values = c_kv part of the latent
    o_s = jnp.einsum("bhjk,bhjkr->bhr", p, vg)

    # --- linear branch: totals minus selected complete blocks ---
    # phi(q).h_j contracted over the gathered latent tiles directly
    # (phi(q).h_j = sum_k (phi(q).phi(k_jk)) c_jk) — no (d_lat x r)
    # per-block states are formed (they are 100s of GiB at decode_32k).
    complete_bound = cur_blk + jnp.where(completed, 1, 0)
    selc = (valid & (idx < complete_bound)).astype(jnp.float32)
    qf = phi(q1)                                     # (B, H, d_lat)
    kf_sel = phi(kg)                                 # (B, H, K_sel, bk, d)
    ls = jnp.einsum("bhd,bhjkd->bhjk", qf, kf_sel)
    ls = ls * selc[..., None]
    sub_num = jnp.einsum("bhjk,bhjkr->bhr", ls, vg)
    sub_den = ls.sum(axis=(-1, -2))
    den_tot = jnp.einsum("bhd,bd->bh", qf, cache["z_tot"])
    num = jnp.einsum("bhd,bdr->bhr", qf, cache["h_tot"]) - sub_num
    # relative empty-complement threshold (cancellation residuals are not 0)
    den = den_tot - sub_den
    den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)[..., None]
    o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    a = jax.nn.sigmoid(sla2_p["alpha_logit"].astype(jnp.float32))
    if a.shape[0] == 1 and h > 1:
        a = jnp.broadcast_to(a, (h, a.shape[1]))
    a_last = a[:, -1][None, :, None]
    a_eff = jnp.where(den > 0, a_last, 1.0)
    o_lat = a_eff * o_s + (1.0 - a_eff) * o_l              # (B, H, r)

    w_uv = params["w_uv"].reshape(r, h, mcfg.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
    o = o.reshape(b, 1, h * mcfg.v_head_dim).astype(x_t.dtype)
    return o @ params["w_o"], cache


# ---------------------------------------------------------------------------
# Paged serving: latent page pool
# ---------------------------------------------------------------------------
# MLA's paged cache stores the COMPRESSED latent [c_kv; k_rope] — one
# (block_k, latent_dim) tile per page in ``k_pages`` with a dummy kv-head
# axis of 1, so its shape lines up with the engine's page-axis bookkeeping
# (_PAGE_AXIS_FROM_END), and one pooled router latent per page in
# ``pooled_lat`` (P, latent_dim); the attention._PAGE_KEYS swap machinery
# carries both.  There is NO v_pages: the values are the c_kv slice of the
# latent (``lat[..., :kv_lora_rank]``), which is what makes the latent
# pool a fraction of a dense pool's bytes.  The gather-path jnp
# implementations below are the only implementations (no fused MLA page
# kernels yet) and serve as the oracle for any future kernel work.
#
# Every pool access goes through the accessors below (_lat_read,
# _store_lat_rows, _store_lat_pooled, _tot, _add_tot).  A layer of the
# transformer's scanned groups hands its mixer the group's STACKED pool
# and its ``layer`` index: each read is one gather at ``[layer, idx]`` and
# each write one scatter at ``[layer, phys, ...]`` into the carried
# stack, so no layer's pool is sliced out, converted or written back.  A
# prefix layer's own cache passes ``layer=None`` and is indexed as is.
#
# The stored layout is the one those gathers ask for.  A TPU tiles an
# array's two minor dims (sublanes x 128 lanes), and where they fit the
# tiles badly its compact layout moves another axis in: for a minor dim
# of 576 the PAGE axis goes onto the lanes, and a (1, d) pooled row gets
# one-sublane tiles.  Either way every page gather and row scatter needs
# the whole stack converted to another layout first.  So each latent row
# is stored padded to whole lanes (576 -> 640; the pad is never read) and
# the pooled latents carry no kv-head axis: each page is then a run of
# whole tiles, read and written in place.
_LANES = 128


def _ix(layer, *idx):
    """The index of ``idx`` in a pool leaf: into a layer's own cache when
    ``layer`` is None, else into the stacked pool at that layer, as ONE
    combined index (``a[layer, idx]``, never ``a[layer][idx]``, which
    would slice the layer out first)."""
    return idx if layer is None else (layer, *idx)


def _stored(d_lat: int) -> int:
    """The pool's stored width of a latent row: ``d_lat`` in whole lanes."""
    return -(-d_lat // _LANES) * _LANES


def _d_lat(cache: dict) -> int:
    """The latent width of a pool's rows (``z_tot`` holds it unpadded)."""
    return cache["z_tot"].shape[-1]


def _tot(cache: dict, name: str, layer):
    """One layer's per-slot linear totals (``h_tot``/``z_tot``)."""
    return cache[name] if layer is None else cache[name][layer]


def _add_tot(cache: dict, name: str, layer, add):
    """``name`` with ``add`` summed into one layer's totals, in place in
    the stacked pool."""
    if layer is None:
        return cache[name] + add
    return cache[name].at[layer].add(add)


def init_mla_paged_cache(mcfg: MLAConfig, num_pages: int, batch: int,
                         block_k: int, *, kv_quant: str = "none",
                         dtype=jnp.bfloat16) -> dict:
    """Latent page pool for one MLA layer: k_pages (P, 1, bk, d_lat
    stored in whole lanes) [+ per-row f32 scales when quantized], per-page
    pooled router latents (likewise stored), and the per-slot SLA2 linear
    totals h_tot/z_tot."""
    d_lat, r = mcfg.latent_dim, mcfg.kv_lora_rank
    d_st = _stored(d_lat)
    if kv_quant != "none":
        qdt = ops.kv_pool_dtype(kv_quant)
        cache = {
            "k_pages": jnp.zeros((num_pages, 1, block_k, d_st), qdt),
            "k_scale": jnp.zeros((num_pages, 1, block_k), jnp.float32),
            "pooled_lat": jnp.zeros((num_pages, d_st), qdt),
            "pooled_lat_scale": jnp.zeros((num_pages,), jnp.float32),
        }
    else:
        cache = {
            "k_pages": jnp.zeros((num_pages, 1, block_k, d_st), dtype),
            "pooled_lat": jnp.zeros((num_pages, d_st), jnp.float32),
        }
    cache.update({
        "h_tot": jnp.zeros((batch, d_lat, r), jnp.float32),
        "z_tot": jnp.zeros((batch, d_lat), jnp.float32),
    })
    return cache


def _lat_read(cache: dict, name: str, idx, layer=None):
    """``cache[name][idx]`` (at ``layer`` of a stacked pool) dequantized
    to f32 (the latent-pool twin of attention._kv_read; the scale
    broadcasts per row)."""
    out = cache[name][_ix(layer, idx)][..., :_d_lat(cache)]
    sk = {"k_pages": "k_scale", "pooled_lat": "pooled_lat_scale"}[name]
    if sk in cache:
        return ops.dequant_rows(out, cache[sk][_ix(layer, idx)])
    return out.astype(jnp.float32)


def _store_lat_rows(cache: dict, kv_quant: str, phys, rows, lat_new,
                    layer=None):
    """Write latent token rows at ``[phys, :, rows]`` (of ``layer``),
    quantizing exactly once at write time.  ``lat_new``: (..., 1, d_lat)
    with leading shape == phys/rows.  Returns (cache, lat_eff) where
    lat_eff is the f32 value a page read observes — block states derive
    from THESE so prefill-time state matches decode-time recompute from
    pages."""
    at = _ix(layer, phys, slice(None), rows)
    at_lat = at + (slice(0, _d_lat(cache)),)
    if kv_quant == "none":
        cache["k_pages"] = cache["k_pages"].at[at_lat].set(
            lat_new.astype(cache["k_pages"].dtype))
        return cache, lat_new.astype(jnp.float32)
    k_c, k_s = ops.quantize_rows(lat_new, kv_quant)
    cache["k_pages"] = cache["k_pages"].at[at_lat].set(k_c)
    cache["k_scale"] = cache["k_scale"].at[at].set(k_s)
    return cache, ops.dequant_rows(k_c, k_s)


def _store_lat_pooled(cache: dict, kv_quant: str, phys, pooled, keep,
                      layer=None):
    """Write pooled router latents (f32, (..., d_lat)) at pages ``phys``
    (of ``layer``); rows where ``keep`` is False retain the existing page
    content (the masked-write idiom of the trash-page scheme)."""
    at = _ix(layer, phys)
    at_lat = at + (slice(0, _d_lat(cache)),)
    if kv_quant == "none":
        cache["pooled_lat"] = cache["pooled_lat"].at[at_lat].set(
            jnp.where(keep[..., None],
                      pooled.astype(cache["pooled_lat"].dtype),
                      cache["pooled_lat"][at_lat]))
        return cache
    codes, scale = ops.quantize_rows(pooled, kv_quant)
    cache["pooled_lat"] = cache["pooled_lat"].at[at_lat].set(
        jnp.where(keep[..., None], codes, cache["pooled_lat"][at_lat]))
    cache["pooled_lat_scale"] = cache["pooled_lat_scale"].at[at].set(
        jnp.where(keep, scale, cache["pooled_lat_scale"][at]))
    return cache


def mla_prefill_chunk_paged(params: dict, x: jax.Array, cache: dict, *,
                            mcfg: MLAConfig, num_heads: int, block_k: int,
                            kv_quant: str = "none", page_row, offset,
                            chunk_len, slot, layer=None):
    """Prefill one chunk of ONE slot's prompt into the latent page pool.

    Mirrors attention.chunk_prefill_paged: exact dense latent attention
    over the slot's gathered pages (prefill is exact even for sla2 — the
    sparse/linear split applies to decode), K/V rows land at
    ``page_row[pos // bk]``, and the chunk's complete blocks fold into the
    per-slot linear totals (reset when ``offset == 0``).  x: (1, C,
    d_model); ``layer``: this layer's index when ``cache`` is a scanned
    group's stacked pool (read and written in place there), None for a
    layer's own cache.  Returns (y, cache)."""
    _, c, _ = x.shape
    h = num_heads
    bk = block_k
    d_lat, r = mcfg.latent_dim, mcfg.kv_lora_rank
    max_p = page_row.shape[0]
    positions = (offset + jnp.arange(c))[None]
    q_t, k_t, _ = _latent_qk(params, mcfg, h, x, positions)
    scale_fix = jnp.sqrt(d_lat / mcfg.qk_head_dim).astype(jnp.float32)
    q = q_t.astype(jnp.float32) * scale_fix             # (1, H, C, d_lat)

    tok_pos = offset + jnp.arange(c)
    valid_t = jnp.arange(c) < chunk_len
    logical = jnp.minimum(tok_pos // bk, max_p - 1)
    phys = jnp.where(valid_t, page_row[logical], 0)
    rows = tok_pos % bk
    cache = dict(cache)
    cache, k_eff = _store_lat_rows(cache, kv_quant, phys, rows,
                                   k_t[0][:, None], layer)  # (C, 1, d_lat)

    # --- exact dense latent attention: chunk queries over history + chunk --
    g = _lat_read(cache, "k_pages", page_row[None], layer)  # (1,maxP,1,bk,d)
    k_all = g.reshape(1, max_p * bk, d_lat)
    s = jnp.einsum("bhnd,bmd->bhnm", q, k_all) / jnp.sqrt(d_lat)
    vis = masklib.token_causal_mask(c, max_p * bk, offset)
    s = jnp.where(vis, s, masklib.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhnm,bmr->bhnr", p, k_all[..., :r])
    with jax.named_scope("mla.uv"):
        w_uv = params["w_uv"].reshape(r, h, mcfg.v_head_dim)
        o = jnp.einsum("bhnr,rhv->bnhv", o_lat, w_uv.astype(jnp.float32))
        o = o.reshape(1, c, h * mcfg.v_head_dim).astype(x.dtype)

    # --- SLA2 block states for the chunk's blocks (from the page-read
    # view k_eff, so decode-time recompute from pages agrees exactly) ---
    t_c = c // bk
    kb = k_eff[:, 0].astype(jnp.float32).reshape(t_c, bk, d_lat)
    w = valid_t.reshape(t_c, bk).astype(jnp.float32)
    pooled = (kb * w[..., None]).sum(1) \
        / jnp.maximum(w.sum(1), 1.0)[:, None]           # (t_c, d_lat)
    blk_ids = jnp.minimum(offset // bk + jnp.arange(t_c), max_p - 1)
    has_tok = w.sum(1) > 0
    phys_blk = jnp.where(has_tok, page_row[blk_ids], 0)
    cache = _store_lat_pooled(cache, kv_quant, phys_blk, pooled, has_tok,
                              layer)
    complete = w.sum(1) == bk
    kf = phi(kb) * w[..., None]
    vb = kb[..., :r] * w[..., None]
    h_add = (jnp.einsum("tkd,tkr->tdr", kf, vb)
             * complete[:, None, None]).sum(0)
    z_add = (kf.sum(1) * complete[:, None]).sum(0)
    fresh = offset == 0
    at = _ix(layer, slot)
    cache["h_tot"] = cache["h_tot"].at[at].set(
        jnp.where(fresh, 0.0, cache["h_tot"][at]) + h_add)
    cache["z_tot"] = cache["z_tot"].at[at].set(
        jnp.where(fresh, 0.0, cache["z_tot"][at]) + z_add)
    return o @ params["w_o"], cache


def mla_decode_step_paged(params: dict, x_t: jax.Array, cache: dict, *,
                          mcfg: MLAConfig, num_heads: int, k_frac: float,
                          block_k: int, kv_quant: str = "none", page_table,
                          lengths, active, layer=None):
    """Batched one-token MLA-SLA2 decode over the latent page pool.

    The paged twin of ``mla_decode_step``: the current block's stats are
    recomputed from page content instead of carried incrementally (so a
    swapped-in or preempted slot needs no extra state), routing is per q
    head over the pooled latent pages, and the linear branch subtracts the
    routed complete blocks from the slot totals.  x_t: (B, 1, d_model);
    ``active`` rows gate every cache write (inactive rows hit the trash
    page); ``layer`` as in ``mla_prefill_chunk_paged``."""
    b = x_t.shape[0]
    h = num_heads
    bk = block_k
    d_lat, r = mcfg.latent_dim, mcfg.kv_lora_rank
    t_n = page_table.shape[1]
    positions = lengths[:, None]
    q_t, k_new, _ = _latent_qk(params, mcfg, h, x_t, positions)
    scale_fix = jnp.sqrt(d_lat / mcfg.qk_head_dim).astype(jnp.float32)
    q1 = q_t[:, :, 0].astype(jnp.float32) * scale_fix   # (B, H, d_lat)

    cur_blk = lengths // bk
    phys_w = jnp.where(
        active, jnp.take_along_axis(page_table, cur_blk[:, None], 1)[:, 0], 0)
    rows = lengths % bk
    cache = dict(cache)
    cache, _ = _store_lat_rows(cache, kv_quant, phys_w, rows,
                               k_new[:, 0][:, None], layer)
    t_new = lengths + 1

    # --- current-block stats recomputed from pages ---
    kblk = _lat_read(cache, "k_pages", phys_w, layer)[:, 0]  # (B,bk,d_lat)
    in_blk = (cur_blk[:, None] * bk + jnp.arange(bk)[None, :]) < t_new[:, None]
    w = in_blk.astype(jnp.float32)[..., None]           # (B, bk, 1)
    pooled_cur = (kblk * w).sum(1) / jnp.maximum(w.sum(1), 1.0)
    cache = _store_lat_pooled(cache, kv_quant, phys_w, pooled_cur, active,
                              layer)
    completed = (t_new % bk) == 0
    kf_cur = phi(kblk) * w
    h_cur = jnp.einsum("bkd,bkr->bdr", kf_cur, kblk[..., :r] * w)
    z_cur = kf_cur.sum(1)
    upd = completed & active
    cache["h_tot"] = _add_tot(cache, "h_tot", layer,
                              jnp.where(upd[:, None, None], h_cur, 0.0))
    cache["z_tot"] = _add_tot(cache, "z_tot", layer,
                              jnp.where(upd[:, None], z_cur, 0.0))

    with jax.named_scope("sla2.router"):
        # --- route per q head over pooled latent pages ---
        sla2_p = params["sla2"]
        rp = sla2_p.get("router", {})
        qr = q1
        # (B, T, d_lat)
        pk = _lat_read(cache, "pooled_lat", page_table, layer)
        if rp:
            qr = qr @ rp["proj_q"].astype(jnp.float32)
            pk = pk @ rp["proj_k"].astype(jnp.float32)
        scores = jnp.einsum("bhd,btd->bht", qr, pk) / jnp.sqrt(d_lat)
        blk_ids = jnp.arange(t_n)
        allowed = blk_ids[None, None, :] <= cur_blk[:, None, None]
        scores = jnp.where(allowed, scores, masklib.NEG_INF)
        scores = jnp.where(blk_ids[None, None, :] == cur_blk[:, None, None],
                           jnp.inf, scores)
        k_sel = max(1, round(k_frac * t_n))
        top_vals, idx = jax.lax.top_k(scores, k_sel)        # (B, H, K_sel)
        valid = top_vals > masklib.NEG_INF * 0.5
        pt = jnp.broadcast_to(page_table[:, None, :], (b, h, t_n))
        phys_sel = jnp.where(valid, jnp.take_along_axis(pt, idx, axis=2), 0)
        complete_bound = cur_blk + jnp.where(completed, 1, 0)
        selc = (valid & (idx < complete_bound[:, None, None])) \
            .astype(jnp.float32)

    with jax.named_scope("sla2.sparse"):
        # --- sparse branch over gathered latent pages ---
        # (B, H, K_sel, bk, d_lat)
        kg = _lat_read(cache, "k_pages", phys_sel, layer)[..., 0, :, :]
        s = jnp.einsum("bhd,bhjkd->bhjk", q1, kg) / jnp.sqrt(d_lat)
        pos = idx[..., None] * bk + jnp.arange(bk)[None, None, None, :]
        vis = (pos < t_new[:, None, None, None]) & valid[..., None]
        s = jnp.where(vis, s, masklib.NEG_INF)
        p = jax.nn.softmax(s.reshape(b, h, -1), axis=-1).reshape(s.shape)
        vg = kg[..., :r]
        o_s = jnp.einsum("bhjk,bhjkr->bhr", p, vg)

    with jax.named_scope("sla2.linear"):
        # --- linear branch: totals minus selected complete blocks ---
        qf = phi(q1)
        kf_sel = phi(kg)
        ls = jnp.einsum("bhd,bhjkd->bhjk", qf, kf_sel) * selc[..., None]
        sub_num = jnp.einsum("bhjk,bhjkr->bhr", ls, vg)
        sub_den = ls.sum(axis=(-1, -2))
        den_tot = jnp.einsum("bhd,bd->bh", qf, _tot(cache, "z_tot", layer))
        num = jnp.einsum("bhd,bdr->bhr", qf, _tot(cache, "h_tot", layer)) \
            - sub_num
        den = den_tot - sub_den
        den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)[..., None]
        o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    with jax.named_scope("sla2.combine"):
        a = jax.nn.sigmoid(sla2_p["alpha_logit"].astype(jnp.float32))
        if a.shape[0] == 1 and h > 1:
            a = jnp.broadcast_to(a, (h, a.shape[1]))
        a_last = a[:, -1][None, :, None]
        a_eff = jnp.where(den > 0, a_last, 1.0)
        o_lat = a_eff * o_s + (1.0 - a_eff) * o_l           # (B, H, r)

    with jax.named_scope("mla.uv"):
        w_uv = params["w_uv"].reshape(r, h, mcfg.v_head_dim)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
        o = o.reshape(b, 1, h * mcfg.v_head_dim).astype(x_t.dtype)
    return o @ params["w_o"], cache


def mla_decode_window_paged(params: dict, x_w: jax.Array, cache: dict, *,
                            mcfg: MLAConfig, num_heads: int, k_frac: float,
                            block_k: int, kv_quant: str = "none", page_table,
                            lengths, active, window_len, layer=None):
    """Verify pass of speculative decoding over the latent pool: W query
    rows per slot with all block state TRANSIENT (the paged twin of
    attention._sla2_decode_window with per-q-head routing) — pooled keys
    for span blocks are computed per row from page content, each row's
    linear totals add span blocks completing earlier in the window, and
    nothing is committed: ``mla_commit_window`` follows host acceptance.
    x_w: (B, W, d_model); ``layer`` as in ``mla_prefill_chunk_paged``;
    returns (y (B, W, d_model), cache)."""
    from repro.models.attention import window_span
    b, wdw, _ = x_w.shape
    h = num_heads
    bk = block_k
    d_lat, r = mcfg.latent_dim, mcfg.kv_lora_rank
    t_n = page_table.shape[1]
    n_span = window_span(wdw, bk)
    tok_pos = lengths[:, None] + jnp.arange(wdw)        # (B, W)
    q_t, k_new, _ = _latent_qk(params, mcfg, h, x_w, tok_pos)
    scale_fix = jnp.sqrt(d_lat / mcfg.qk_head_dim).astype(jnp.float32)
    q = q_t.astype(jnp.float32) * scale_fix             # (B, H, W, d_lat)

    valid_w = (jnp.arange(wdw)[None, :] < window_len[:, None]) \
        & active[:, None]
    logical = jnp.minimum(tok_pos // bk, t_n - 1)
    phys_w = jnp.where(valid_w,
                       jnp.take_along_axis(page_table, logical, 1), 0)
    rows = tok_pos % bk
    cache = dict(cache)
    cache, _ = _store_lat_rows(cache, kv_quant, phys_w, rows,
                               k_new[..., None, :], layer)
    t_new = tok_pos + 1                                 # (B, W)

    # --- transient stats for the blocks the window can touch ---
    blk0 = lengths // bk
    span_ids_raw = blk0[:, None] + jnp.arange(n_span)[None, :]  # (B, S)
    genuine = span_ids_raw < t_n
    span_ids = jnp.minimum(span_ids_raw, t_n - 1)
    span_phys = jnp.take_along_axis(page_table, span_ids, 1)
    # (B, S, bk, d_lat)
    kblk = _lat_read(cache, "k_pages", span_phys, layer)[:, :, 0]
    pos_blk = span_ids[:, :, None] * bk + jnp.arange(bk)    # (B,S,bk)
    msk = (pos_blk[:, None] < t_new[:, :, None, None]) \
        .astype(jnp.float32)                                # (B,W,S,bk)
    pooled_ws = jnp.einsum("bwsk,bskd->bwsd", msk, kblk) \
        / jnp.maximum(msk.sum(-1), 1.0)[..., None]
    kf_span = phi(kblk)
    h_span = jnp.einsum("bskd,bskr->bsdr", kf_span, kblk[..., :r])
    z_span = kf_span.sum(-2)                                # (B,S,d_lat)
    cmplt = (genuine[:, None]
             & ((span_ids[:, None] + 1) * bk <= t_new[:, :, None])) \
        .astype(jnp.float32)                                # (B,W,S)
    h_eff = _tot(cache, "h_tot", layer)[:, None] \
        + jnp.einsum("bws,bsdr->bwdr", cmplt, h_span)
    z_eff = _tot(cache, "z_tot", layer)[:, None] \
        + jnp.einsum("bws,bsd->bwd", cmplt, z_span)

    # --- route per row, per q head, transient pooled keys for the span ---
    sla2_p = params["sla2"]
    rp = sla2_p.get("router", {})
    qr = q
    pk = _lat_read(cache, "pooled_lat", page_table, layer)
    pw = pooled_ws
    if rp:
        qr = qr @ rp["proj_q"].astype(jnp.float32)
        pk = pk @ rp["proj_k"].astype(jnp.float32)
        pw = pw @ rp["proj_k"].astype(jnp.float32)
    scores = jnp.einsum("bhwd,btd->bwht", qr, pk) / jnp.sqrt(d_lat)
    s_span = jnp.einsum("bhwd,bwsd->bwhs", qr, pw) / jnp.sqrt(d_lat)
    blk_ids = jnp.arange(t_n)
    # cache pooled keys of span blocks are stale (committed only after
    # acceptance): overwrite their scores with the per-row transient ones
    for s_i in range(n_span):
        m = (blk_ids[None, None, None, :]
             == span_ids[:, s_i, None, None, None]) \
            & genuine[:, s_i, None, None, None]
        scores = jnp.where(m, s_span[:, :, :, s_i:s_i + 1], scores)
    cur_blk = (t_new - 1) // bk                             # (B, W)
    allowed = blk_ids[None, None, None, :] <= cur_blk[:, :, None, None]
    scores = jnp.where(allowed, scores, masklib.NEG_INF)
    scores = jnp.where(blk_ids[None, None, None, :]
                       == cur_blk[:, :, None, None], jnp.inf, scores)
    k_sel = max(1, round(k_frac * t_n))
    top_vals, idx = jax.lax.top_k(scores, k_sel)            # (B,W,H,K)
    valid = top_vals > masklib.NEG_INF * 0.5
    pt = jnp.broadcast_to(page_table[:, None, None, :], (b, wdw, h, t_n))
    phys_sel = jnp.where(valid, jnp.take_along_axis(pt, idx, axis=3), 0)
    completed = (t_new % bk) == 0
    complete_bound = cur_blk + jnp.where(completed, 1, 0)
    selc = (valid & (idx < complete_bound[:, :, None, None])) \
        .astype(jnp.float32)

    # --- sparse branch over gathered latent pages ---
    kg = _lat_read(cache, "k_pages", phys_sel, layer)[..., 0, :, :]
    qw = q.transpose(0, 2, 1, 3)                            # (B,W,H,d_lat)
    s = jnp.einsum("bwhd,bwhjkd->bwhjk", qw, kg) / jnp.sqrt(d_lat)
    pos = idx[..., None] * bk + jnp.arange(bk)              # (B,W,H,K,bk)
    vis = (pos < t_new[:, :, None, None, None]) & valid[..., None]
    s = jnp.where(vis, s, masklib.NEG_INF)
    p = jax.nn.softmax(s.reshape(b, wdw, h, -1), axis=-1).reshape(s.shape)
    vg = kg[..., :r]
    o_s = jnp.einsum("bwhjk,bwhjkr->bwhr", p, vg)

    # --- linear branch: per-row effective totals minus selected blocks ---
    qf = phi(qw)
    kf_sel = phi(kg)
    ls = jnp.einsum("bwhd,bwhjkd->bwhjk", qf, kf_sel) * selc[..., None]
    sub_num = jnp.einsum("bwhjk,bwhjkr->bwhr", ls, vg)
    sub_den = ls.sum(axis=(-1, -2))
    den_tot = jnp.einsum("bwhd,bwd->bwh", qf, z_eff)
    num = jnp.einsum("bwhd,bwdr->bwhr", qf, h_eff) - sub_num
    den = den_tot - sub_den
    den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)[..., None]
    o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    a = jax.nn.sigmoid(sla2_p["alpha_logit"].astype(jnp.float32))
    if a.shape[0] == 1 and h > 1:
        a = jnp.broadcast_to(a, (h, a.shape[1]))
    a_last = a[:, -1][None, None, :, None]
    a_eff = jnp.where(den > 0, a_last, 1.0)
    o_lat = a_eff * o_s + (1.0 - a_eff) * o_l               # (B,W,H,r)

    w_uv = params["w_uv"].reshape(r, h, mcfg.v_head_dim)
    o = jnp.einsum("bwhr,rhv->bwhv", o_lat, w_uv.astype(jnp.float32))
    o = o.reshape(b, wdw, h * mcfg.v_head_dim).astype(x_w.dtype)
    return o @ params["w_o"], cache


def mla_commit_window(cache: dict, *, mcfg: MLAConfig, block_k: int,
                      kv_quant: str = "none", page_table, lengths, accepted,
                      active, window: int) -> dict:
    """Commit the ACCEPTED prefix of a verify window into the latent block
    state (the MLA twin of attention.commit_paged_window): rewrite pooled
    router latents of the touched blocks masked to the new committed
    length, and fold blocks completing inside the accepted prefix into the
    per-slot linear totals.  Latent pages were written by the verify pass."""
    from repro.models.attention import window_span
    bk = block_k
    r = mcfg.kv_lora_rank
    t_n = page_table.shape[1]
    n_span = window_span(window, bk)
    new_len = lengths + accepted
    blk0 = lengths // bk
    span_ids_raw = blk0[:, None] + jnp.arange(n_span)[None, :]  # (B, S)
    genuine = span_ids_raw < t_n
    span_ids = jnp.minimum(span_ids_raw, t_n - 1)
    span_phys = jnp.take_along_axis(page_table, span_ids, 1)
    kblk = _lat_read(cache, "k_pages", span_phys)[:, :, 0]  # (B,S,bk,d_lat)
    pos_blk = span_ids[:, :, None] * bk + jnp.arange(bk)
    msk = (pos_blk < new_len[:, None, None]).astype(jnp.float32)
    live = genuine & active[:, None] & (accepted > 0)[:, None]
    has_tok = (msk.sum(-1) > 0) & live
    pooled = jnp.einsum("bsk,bskd->bsd", msk, kblk) \
        / jnp.maximum(msk.sum(-1), 1.0)[..., None]
    upd_phys = jnp.where(has_tok, span_phys, 0)
    cache = dict(cache)
    cache = _store_lat_pooled(cache, kv_quant, upd_phys, pooled, has_tok)
    newc = (live & ((span_ids + 1) * bk <= new_len[:, None])
            & ((span_ids + 1) * bk > lengths[:, None])).astype(jnp.float32)
    kf = phi(kblk)
    cache["h_tot"] = cache["h_tot"] \
        + jnp.einsum("bs,bskd,bskr->bdr", newc, kf, kblk[..., :r])
    cache["z_tot"] = cache["z_tot"] \
        + jnp.einsum("bs,bskd->bd", newc, kf)
    return cache
