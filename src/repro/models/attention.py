"""Model-level attention: projections, RoPE, GQA, mechanism dispatch, caches.

Mechanisms:
  * ``full``        — dense softmax attention (FlashAttn2-equivalent math)
  * ``sla2``        — the paper's sparse-linear attention (core/ + kernels/)
  * ``sla``         — SLA baseline (heuristic router + proj(O_l))
  * ``sparse_only`` — VSA/VMoBA-like block-sparse only

Decode keeps a *block cache*: raw K/V plus, for SLA2, the per-block router
keys (pooled K) and linear-branch states (h_j, z_j) with a running total, so
one decode step costs O(K_sel * b_k * d + d^2) regardless of context length —
this is what makes the 500k-token decode shape sub-quadratic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import attention as core_attn
from repro.core import masks as masklib
from repro.core import sla as slalib
from repro.core import sla2 as sla2lib
from repro.core.attention import phi
from repro.core.router import RouterConfig
from repro.core.sla2 import SLA2Config
from repro.kernels import ops
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    """Per-layer attention hyperparameters: projection geometry, mechanism
    selection, masking (causal / prefix-LM / sliding window), the SLA2
    router/quantization knobs, and the paged-serving path switches."""
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mechanism: str = "full"            # full | sla2 | sla | sparse_only
    causal: bool = True
    prefix_len: int = 0                # prefix-LM (PaliGemma)
    sliding_window: Optional[int] = None
    qk_norm: bool = False              # qwen3
    rope_theta: float = 10000.0
    use_rope: bool = True
    # SLA2 knobs
    block_q: int = 128
    block_k: int = 64
    k_frac: float = 0.05
    quant_bits: str = "int8"
    sla2_impl: str = "kernel"
    n_q_blocks: int = 32               # alpha table size at init
    # paged serving: 'fused' = Pallas page-table kernels (decode + chunked
    # prefill read K/V pages in place); 'gather' = jnp reference paths that
    # materialise per-slot copies (kept as the parity oracle); 'auto' =
    # fused on compiled backends, gather on CPU (where Pallas runs in
    # interpret mode and the XLA gather path is the faster proxy)
    paged_impl: str = "auto"
    decode_quant_bits: str = "none"    # fused decode QAT tile path
    # page-pool STORAGE dtype ('none' | 'int8' | 'fp8'): low-bit K/V (and
    # SLA2 pooled-key) pages with per-row f32 scales, quantized once at
    # write time and dequantized in registers inside the fused kernels (or
    # by the gather oracle) — halves/quarters pool bytes, swap traffic and
    # decode-step HBM reads.  Orthogonal to decode_quant_bits (the on-the-
    # fly QAT tile path inside the kernel's MXU dots).
    kv_quant: str = "none"
    # sharded serving: a jax.sharding.Mesh here routes every fused paged
    # entry through its shard_map wrapper (distributed/shard_paged) — slot
    # axis split for decode/verify, head axis for chunked prefill — so
    # each device runs the kernel over its local share.  None (default)
    # keeps single-device dispatch; the gather oracle is placed by GSPMD
    # alone either way.
    mesh: Optional[Any] = None

    def router_config(self) -> RouterConfig:
        """The SLA2 router view of this config (block sizes, top-k
        fraction, masking)."""
        return RouterConfig(
            block_q=self.block_q, block_k=self.block_k, k_frac=self.k_frac,
            causal=self.causal, prefix_len=self.prefix_len,
            sliding_window=self.sliding_window)

    def sla2_config(self) -> SLA2Config:
        """The core SLA2 config view (router + quantization + impl)."""
        return SLA2Config(router=self.router_config(),
                          quant_bits=self.quant_bits, impl=self.sla2_impl)


def init_attention(key, cfg: AttentionConfig, dtype=jnp.float32) -> dict:
    """Initialise one attention layer's params: QKV/output projections,
    optional qk-norms, and the mechanism's extra params (SLA2 router +
    alpha table, or the SLA baseline's output projection)."""
    ks = jax.random.split(key, 6)
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    std = d ** -0.5
    p = {
        "wq": L.truncated_normal(ks[0], (d, h * dh), dtype, std),
        "wk": L.truncated_normal(ks[1], (d, hkv * dh), dtype, std),
        "wv": L.truncated_normal(ks[2], (d, hkv * dh), dtype, std),
        "wo": L.truncated_normal(ks[3], (h * dh, d), dtype, (h * dh) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(dh, dtype)
        p["k_norm"] = L.init_rmsnorm(dh, dtype)
    if cfg.mechanism == "sla2":
        p["sla2"] = sla2lib.init_sla2_params(
            ks[4], head_dim=dh, num_heads=h, n_q_blocks=cfg.n_q_blocks,
            cfg=cfg.sla2_config(), dtype=dtype)
    elif cfg.mechanism == "sla":
        p["sla"] = slalib.init_sla_params(ks[5], head_dim=dh, dtype=dtype)
    return p


def _project_qkv(params, cfg: AttentionConfig, x, positions):
    if cfg.mesh is not None:
        # per-slot positions arrive split on a serving mesh; a split rope
        # operand would pull the projections onto one slot per device
        from repro.distributed.shard_paged import replicate
        positions = replicate(positions, cfg.mesh)
    b, n, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, n, h, dh)
    k = (x @ params["wk"]).reshape(b, n, hkv, dh)
    v = (x @ params["wv"]).reshape(b, n, hkv, dh)
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        q = L.apply_rope(q, positions, theta=cfg.rope_theta)
        k = L.apply_rope(k, positions, theta=cfg.rope_theta)
    if cfg.mesh is not None:
        q, k, v = (replicate(t, cfg.mesh) for t in (q, k, v))
    return q, k, v


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=1)


def _dense_masked_attention(q, k, v, cfg: AttentionConfig, q_offset: int = 0):
    """Dense attention with causal/prefix/sliding-window masks. (B,H,N,D)."""
    d = q.shape[-1]
    s = jnp.einsum("bhnd,bhmd->bhnm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(d)
    n_q, n_kv = q.shape[-2], k.shape[-2]
    mask = None
    if cfg.causal:
        mask = masklib.token_causal_mask(n_q, n_kv, q_offset, cfg.prefix_len)
    if cfg.sliding_window is not None:
        qi = jnp.arange(n_q) + q_offset
        kj = jnp.arange(n_kv)
        sw = kj[None, :] >= (qi[:, None] - cfg.sliding_window + 1)
        if cfg.prefix_len:
            sw = sw | (kj[None, :] < cfg.prefix_len)
        mask = sw if mask is None else (mask & sw)
    if mask is not None:
        s = jnp.where(mask, s, masklib.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhnm,bhmd->bhnd", p, v.astype(jnp.float32)).astype(q.dtype)


def attention_forward(params: dict, cfg: AttentionConfig, x: jax.Array,
                      positions: Optional[jax.Array] = None) -> jax.Array:
    """Training / prefill-style full-sequence attention. x: (B, N, d_model)."""
    b, n, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    q, k, v = _project_qkv(params, cfg, x, positions)
    # (B, N, H, Dh) -> (B, H, N, Dh)
    q = q.transpose(0, 2, 1, 3)
    k = _repeat_kv(k.transpose(0, 2, 1, 3), cfg.num_heads // cfg.num_kv_heads)
    v = _repeat_kv(v.transpose(0, 2, 1, 3), cfg.num_heads // cfg.num_kv_heads)

    if cfg.mechanism == "full":
        o = _dense_masked_attention(q, k, v, cfg)
    elif cfg.mechanism == "sla2":
        o = sla2lib.sla2_attention(params["sla2"], q, k, v, cfg.sla2_config())
    elif cfg.mechanism == "sla":
        scfg = slalib.SLAConfig(
            router=dataclasses.replace(cfg.router_config(), learnable=False),
            quant_bits="none")
        o = slalib.sla_attention(params["sla"], q, k, v, scfg)
    elif cfg.mechanism == "sparse_only":
        scfg = slalib.SLAConfig(
            router=dataclasses.replace(cfg.router_config(), learnable=False),
            quant_bits=cfg.quant_bits)
        o = slalib.sparse_only_attention(q, k, v, scfg)
    else:
        raise ValueError(cfg.mechanism)
    o = o.transpose(0, 2, 1, 3).reshape(b, n, -1)
    return o @ params["wo"]


# ---------------------------------------------------------------------------
# Decode cache
# ---------------------------------------------------------------------------

def init_cache(cfg: AttentionConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Block KV cache (+ SLA2 router/linear states). max_len % block_k == 0."""
    hkv, dh, bk = cfg.num_kv_heads, cfg.head_dim, cfg.block_k
    t_n = max_len // bk
    cache = {
        "k": jnp.zeros((batch, hkv, max_len, dh), dtype),
        "v": jnp.zeros((batch, hkv, max_len, dh), dtype),
        "length": jnp.zeros((), jnp.int32),
    }
    if cfg.mechanism == "sla2":
        cache.update({
            # router keys (block means); per-block linear states are NOT
            # cached — the complement trick recomputes the K_sel selected
            # blocks' (h_j, z_j) from the K/V tiles the sparse branch reads
            # anyway, so only the running totals over *complete* blocks are
            # kept: O(d^2) state instead of O(T_n d^2).
            "pooled_k": jnp.zeros((batch, hkv, t_n, dh), jnp.float32),
            "h_tot": jnp.zeros((batch, hkv, dh, dh), jnp.float32),
            "z_tot": jnp.zeros((batch, hkv, dh), jnp.float32),
        })
    return cache


def prefill_cache(params: dict, cfg: AttentionConfig, x: jax.Array,
                  cache: dict) -> tuple[jax.Array, dict]:
    """Run full-sequence attention AND populate the cache with the K/V (+
    SLA2 block states) of the prefix. x: (B, N, d_model); N % block_k == 0."""
    b, n, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    q, k, v = _project_qkv(params, cfg, x, positions)
    k_t = k.transpose(0, 2, 1, 3)  # (B, Hkv, N, Dh)
    v_t = v.transpose(0, 2, 1, 3)
    out = attention_forward(params, cfg, x, positions)

    max_len = cache["k"].shape[2]
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k_t.astype(cache["k"].dtype), (0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v_t.astype(cache["v"].dtype), (0, 0, 0, 0))
    cache["length"] = jnp.asarray(n, jnp.int32)
    if cfg.mechanism == "sla2":
        bk = cfg.block_k
        t_full = n // bk
        kb = k_t.reshape(b, cfg.num_kv_heads, t_full, bk, cfg.head_dim)
        vb = v_t.reshape(b, cfg.num_kv_heads, t_full, bk, cfg.head_dim)
        kf = phi(kb)
        h = jnp.einsum("bhjkd,bhjke->bhjde", kf, vb.astype(jnp.float32))
        z = kf.sum(axis=-2)
        pooled = kb.astype(jnp.float32).mean(axis=-2)
        cache["pooled_k"] = jax.lax.dynamic_update_slice(
            cache["pooled_k"], pooled.astype(cache["pooled_k"].dtype),
            (0, 0, 0, 0))
        cache["h_tot"] = h.sum(axis=2)
        cache["z_tot"] = z.sum(axis=2)
    return out, cache


def decode_step(params: dict, cfg: AttentionConfig, x_t: jax.Array,
                cache: dict) -> tuple[jax.Array, dict]:
    """One-token decode. x_t: (B, 1, d_model). Returns (o_t, new cache)."""
    b = x_t.shape[0]
    h, hkv, dh, bk = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.block_k)
    n_rep = h // hkv
    t = cache["length"]
    positions = jnp.broadcast_to(t[None], (b, 1))
    q, k_new, v_new = _project_qkv(params, cfg, x_t, positions)
    q = q.transpose(0, 2, 1, 3)              # (B, H, 1, Dh)
    k_new = k_new.transpose(0, 2, 1, 3)      # (B, Hkv, 1, Dh)
    v_new = v_new.transpose(0, 2, 1, 3)

    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, 0, t, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, 0, t, 0))
    t_new = t + 1
    cache["length"] = t_new

    max_len = cache["k"].shape[2]
    if cfg.mechanism == "sla2":
        o = _sla2_decode(params, cfg, q, cache, t_new)
    else:
        # dense decode over the cache (masked by length)
        k_all = _repeat_kv(cache["k"], n_rep).astype(q.dtype)
        v_all = _repeat_kv(cache["v"], n_rep).astype(q.dtype)
        s = jnp.einsum("bhqd,bhmd->bhqm", q.astype(jnp.float32),
                       k_all.astype(jnp.float32)) / jnp.sqrt(dh)
        pos_k = jnp.arange(max_len)
        vis = pos_k[None, None, None, :] < t_new
        if cfg.sliding_window is not None:
            sw = pos_k[None, None, None, :] >= (t_new - cfg.sliding_window)
            if cfg.prefix_len:
                sw = sw | (pos_k[None, None, None, :] < cfg.prefix_len)
            vis = vis & sw
        s = jnp.where(vis, s, masklib.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqm,bhmd->bhqd", p, v_all.astype(jnp.float32))
    o = o.astype(x_t.dtype).transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
    return o @ params["wo"], cache


# ---------------------------------------------------------------------------
# Paged decode cache (block-paged KV for continuous batching)
# ---------------------------------------------------------------------------
#
# KV lives in a pool of physical pages of ``block_k`` tokens each; a host-side
# page table maps (slot, logical block) -> physical page so slots of very
# different lengths share one pool instead of reserving max_len each.
# Physical page 0 is reserved as a trash page: writes from inactive slots and
# chunk padding land there, so every update stays a static-shape scatter.

def init_paged_cache(cfg: AttentionConfig, num_pages: int, batch: int,
                     dtype=jnp.bfloat16) -> dict:
    """Page pool for one attention layer (+ SLA2 per-page pooled keys and
    per-slot linear-branch totals).

    With ``cfg.kv_quant != 'none'`` the K/V pages (and, for SLA2, the
    pooled router keys) are stored as low-bit codes with per-row f32
    scales: ``k_scale``/``v_scale`` carry one scale per (page, kv head,
    token row), ``pooled_scale`` one per (page, kv head).  ``dtype`` then
    only applies to the unquantized layout."""
    hkv, dh, bk = cfg.num_kv_heads, cfg.head_dim, cfg.block_k
    if cfg.kv_quant != "none":
        qdt = ops.kv_pool_dtype(cfg.kv_quant)
        cache = {
            "k_pages": jnp.zeros((num_pages, hkv, bk, dh), qdt),
            "v_pages": jnp.zeros((num_pages, hkv, bk, dh), qdt),
            "k_scale": jnp.zeros((num_pages, hkv, bk), jnp.float32),
            "v_scale": jnp.zeros((num_pages, hkv, bk), jnp.float32),
        }
    else:
        cache = {
            "k_pages": jnp.zeros((num_pages, hkv, bk, dh), dtype),
            "v_pages": jnp.zeros((num_pages, hkv, bk, dh), dtype),
        }
    if cfg.mechanism == "sla2":
        if cfg.kv_quant != "none":
            cache.update({
                "pooled_pages": jnp.zeros(
                    (num_pages, hkv, dh), ops.kv_pool_dtype(cfg.kv_quant)),
                "pooled_scale": jnp.zeros((num_pages, hkv), jnp.float32),
            })
        else:
            cache["pooled_pages"] = jnp.zeros((num_pages, hkv, dh),
                                              jnp.float32)
        cache.update({
            "h_tot": jnp.zeros((batch, hkv, dh, dh), jnp.float32),
            "z_tot": jnp.zeros((batch, hkv, dh), jnp.float32),
        })
    return cache


# Page-granular swap helpers: the serving scheduler preempts a slot by
# copying its state out of the device pool (to a host swap pool) and later
# copying it back into freshly allocated pages.  A slot's state in one
# attention layer is (a) its physical K/V pages (+ SLA2 per-page pooled
# router keys), addressed by the slot's page-table row, and (b) its per-slot
# SLA2 linear-branch totals (h_tot, z_tot), addressed by the slot id.
# ``page_row`` may be padded with 0 (the trash page): extracting page 0
# copies garbage that is never read, and re-inserting at page 0 only
# rewrites the trash page — both harmless, so callers can keep a static
# (max_pages,) shape and the extract/insert functions jit-compile once.

_PAGE_KEYS = ("k_pages", "v_pages", "pooled_pages", "pooled_lat",
              "k_scale", "v_scale", "pooled_scale", "pooled_lat_scale")
# Per-slot state leaves: the SLA2 linear-branch totals plus the recurrent-
# mixer state checkpoints (ssm.py names them with an "s_" prefix).  One
# name list means the swap / prefix-snapshot / extract-insert machinery
# carries every cache kind without knowing which layer family wrote it —
# an SSM layer's paged cache is exactly a degenerate pool with no page
# keys and only these per-slot leaves.  ("s_win_*" verify-window buffers
# are deliberately absent: they are transient within one engine step.)
_SLOT_KEYS = ("h_tot", "z_tot", "s_state", "s_c", "s_n", "s_h", "s_m")

# page array -> its per-row scale array when the pool is quantized
_SCALE_OF = {"k_pages": "k_scale", "v_pages": "v_scale",
             "pooled_pages": "pooled_scale"}


def extract_paged_state(cache: dict, page_row, slot, lead: int = 0) -> dict:
    """Copy one slot's pages and per-slot states out of a layer cache.
    ``lead`` leading axes (e.g. the scanned group axis) are preserved."""
    ix = (slice(None),) * lead
    st = {k: cache[k][ix + (page_row,)] for k in _PAGE_KEYS if k in cache}
    st.update({k: cache[k][ix + (slot,)] for k in _SLOT_KEYS if k in cache})
    return st


def insert_paged_state(cache: dict, page_row, slot, state: dict,
                       lead: int = 0) -> dict:
    """Write a previously extracted slot state back into a layer cache at a
    (possibly different) page row / slot id.  Raises ValueError when the
    state carries a leaf the target cache does not have — inserting an MLA
    latent page into a dense pool (or an SSM checkpoint into an attention
    cache) is a scheduler bug, not a silent no-op."""
    ix = (slice(None),) * lead
    new = dict(cache)
    for k, v in state.items():
        if k not in cache:
            raise ValueError(
                f"state leaf {k!r} does not exist in the target cache "
                f"(has {sorted(cache)}): wrong cache kind for this insert")
        tgt = ix + ((page_row,) if k in _PAGE_KEYS else (slot,))
        new[k] = cache[k].at[tgt].set(jnp.asarray(v, cache[k].dtype))
    return new


def extract_slot_state(cache: dict, slot, lead: int = 0) -> dict:
    """Copy ONLY the per-slot keys (SLA2 linear totals h_tot/z_tot and/or
    the recurrent-mixer "s_*" state checkpoints) out of a layer cache —
    the O(d^2) prefix summary the serving prefix cache snapshots per trie
    node.  Empty dict for layer kinds without per-slot state."""
    ix = (slice(None),) * lead
    return {k: cache[k][ix + (slot,)] for k in _SLOT_KEYS if k in cache}


def insert_slot_state(cache: dict, slot, state: dict, lead: int = 0) -> dict:
    """Write an extracted per-slot state (see ``extract_slot_state``) back
    into a layer cache at ``slot`` — the O(1) linear-totals restore a
    prefix-cache hit performs instead of re-prefilling the prefix."""
    ix = (slice(None),) * lead
    new = dict(cache)
    for k, v in state.items():
        if k not in cache:
            raise ValueError(
                f"slot-state leaf {k!r} does not exist in the target cache "
                f"(has {sorted(cache)}): wrong cache kind for this insert")
        new[k] = cache[k].at[ix + (slot,)].set(jnp.asarray(v, cache[k].dtype))
    return new


def copy_paged_page(cache: dict, src, dst, lead: int = 0) -> dict:
    """Copy one physical page's contents (K/V + SLA2 pooled router key)
    onto another physical page — the device half of the serving engine's
    copy-on-write: a slot about to write a page it shares with the prefix
    cache first duplicates it into a private page."""
    ix = (slice(None),) * lead
    new = dict(cache)
    for k in _PAGE_KEYS:
        if k in cache:
            new[k] = cache[k].at[ix + (dst,)].set(cache[k][ix + (src,)])
    return new


# Backends where paged_impl='auto' resolves to the jnp gather reference:
# Pallas runs in interpret mode there, making the XLA gather path the
# faster proxy.  Everything else gets the fused page-table kernels.
AUTO_GATHER_BACKENDS = ("cpu",)

# The paged-attention dispatch table: for every (mechanism, phase) pair,
# the fused Pallas entry point in kernels/sla2_decode_paged and the jnp
# gather reference implementing it.  The dispatch sites below
# (chunk_prefill_paged / decode_step_paged / decode_window_paged) consult
# this table via use_fused(), and tools/gen_path_matrix.py renders the
# docs/paths.md support matrix from it — so the documented matrix cannot
# drift from the code without CI noticing.  Mechanisms 'sla' and
# 'sparse_only' decode densely over the cache (same math as 'full'), so
# they share the dense kernel family.
PAGED_PHASES = ("prefill", "decode", "verify")
_DENSE_PATHS = {
    "prefill": ("paged_flash_prefill", "_gather_pages + dense chunk attn"),
    "decode": ("dense_decode_fused", "_gather_pages + dense masked decode"),
    "verify": ("dense_decode_verify", "_gather_pages + dense window decode"),
}
PAGED_DISPATCH = {
    ("sla2", "prefill"): _DENSE_PATHS["prefill"],   # chunk attn is exact
    ("sla2", "decode"): ("sla2_decode_fused", "_sla2_decode_paged gather"),
    ("sla2", "verify"): ("sla2_decode_verify", "_sla2_decode_window gather"),
    **{(m, ph): _DENSE_PATHS[ph]
       for m in ("full", "sla", "sparse_only") for ph in PAGED_PHASES},
}


def resolve_paged_impl(cfg: AttentionConfig) -> str:
    """Resolve cfg.paged_impl: 'auto' picks the fused Pallas page-table
    kernels on compiled backends and the jnp gather reference on the
    AUTO_GATHER_BACKENDS (CPU, where Pallas interprets)."""
    if cfg.paged_impl != "auto":
        return cfg.paged_impl
    return ("gather" if jax.default_backend() in AUTO_GATHER_BACKENDS
            else "fused")


def fused_paged_entry(mechanism: str, phase: str):
    """Name of the fused Pallas entry point serving (mechanism, phase) on
    the paged path, or None when only the gather reference implements it.
    ``phase`` is one of PAGED_PHASES."""
    entry = PAGED_DISPATCH.get((mechanism, phase))
    return entry[0] if entry else None


def use_fused(cfg: AttentionConfig, phase: str) -> bool:
    """True when ``phase`` should run the fused Pallas paged path for this
    config — the resolved impl is 'fused' AND the dispatch table carries a
    fused entry point for the mechanism."""
    return (resolve_paged_impl(cfg) == "fused"
            and fused_paged_entry(cfg.mechanism, phase) is not None)


def fused_entry_fn(name: str, cfg: AttentionConfig):
    """The fused entry callable for ``name`` — wrapped in shard_map over
    ``cfg.mesh`` when a mesh is set (distributed/shard_paged splits the
    slot/head axis across the devices), the bare kernel otherwise.  The
    single composition point between the dispatch table and the sharded
    serving path."""
    from repro.kernels import sla2_decode_paged as KP
    fn = getattr(KP, name)
    if cfg.mesh is None:
        return fn
    from repro.distributed.shard_paged import wrap_entry
    return wrap_entry(name, fn, cfg.mesh)


def _gather_pages(pages, page_table):
    """pages (P, Hkv, bk, Dh), page_table (B, maxP) -> (B, Hkv, maxP*bk, Dh)
    contiguous per-slot view in logical order."""
    g = pages[page_table]                       # (B, maxP, Hkv, bk, Dh)
    b, mp, hkv, bk, dh = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, mp * bk, dh)


def _gather_blocks(pages, phys):
    """pages (P, Hkv, bk, Dh), phys (B, Hkv, K) per-kv-head physical page ids
    -> (B, Hkv, K, bk, Dh)."""
    return jax.vmap(lambda ph, pg: pg[ph], in_axes=(1, 1), out_axes=1)(
        phys, pages)


# -- dequant-aware pool accessors -------------------------------------------
# Every jnp read of a page array goes through these: on an unquantized pool
# they are plain f32 casts; on a quantized pool (cfg.kv_quant != 'none',
# i.e. the scale array is present) they apply THE dequant formula
# (ops.dequant_rows) — the same math the fused kernels run in registers, so
# the gather oracle stays the bit-for-bit parity reference.

def _kv_read(cache: dict, name: str, idx):
    """``cache[name][idx]`` dequantized to f32 (``idx`` indexes the page
    axis; any leading index shape works — the scale broadcasts per row)."""
    with jax.named_scope("lm.kv_read"):
        out = cache[name][idx]
        sk = _SCALE_OF[name]
        if sk in cache:
            return ops.dequant_rows(out, cache[sk][idx])
        return out.astype(jnp.float32)


def _kv_gather_pages(cache: dict, name: str, page_table):
    """Dequantizing ``_gather_pages``: contiguous (B, Hkv, maxP*bk, Dh) f32
    per-slot view of a (possibly quantized) page array."""
    g = _kv_read(cache, name, page_table)       # (B, maxP, Hkv, bk, Dh) f32
    b, mp, hkv, bk, dh = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, mp * bk, dh)


def _kv_gather_blocks(cache: dict, name: str, phys):
    """Dequantizing ``_gather_blocks``: (B, Hkv, K, bk, Dh) f32 from a
    (possibly quantized) page array and per-kv-head physical ids."""
    with jax.named_scope("lm.kv_read"):
        out = _gather_blocks(cache[name], phys).astype(jnp.float32)
        sk = _SCALE_OF[name]
        if sk in cache:
            out = out * _gather_blocks(cache[sk], phys)[..., None]
        return out


def _store_kv_rows(cache: dict, cfg: AttentionConfig, phys, rows,
                   k_new, v_new) -> dict:
    """Write token rows into the K/V pools at ``[phys, :, rows]`` — THE
    write-time quantization point: each row is quantized exactly once here
    (per-row symmetric, ops.quantize_rows) and never requantized, so swap
    round-trips and CoW copies of the codes + scales are bit-exact.
    ``k_new``/``v_new``: (..., Hkv, Dh) with leading shape == phys/rows.
    Returns (cache, k_eff, v_eff) where k_eff/v_eff are the f32 values a
    subsequent page read would observe (the quantize->dequantize round
    trip; the raw inputs when unquantized) — callers derive SLA2 block
    state from THESE so prefill-time state matches decode-time recompute
    from pages."""
    with jax.named_scope("lm.kv_write"):
        if cfg.kv_quant == "none":
            cache["k_pages"] = cache["k_pages"].at[phys, :, rows].set(
                k_new.astype(cache["k_pages"].dtype))
            cache["v_pages"] = cache["v_pages"].at[phys, :, rows].set(
                v_new.astype(cache["v_pages"].dtype))
            return cache, k_new, v_new
        k_c, k_s = ops.quantize_rows(k_new, cfg.kv_quant)
        v_c, v_s = ops.quantize_rows(v_new, cfg.kv_quant)
        cache["k_pages"] = cache["k_pages"].at[phys, :, rows].set(k_c)
        cache["v_pages"] = cache["v_pages"].at[phys, :, rows].set(v_c)
        cache["k_scale"] = cache["k_scale"].at[phys, :, rows].set(k_s)
        cache["v_scale"] = cache["v_scale"].at[phys, :, rows].set(v_s)
        return cache, ops.dequant_rows(k_c, k_s), ops.dequant_rows(v_c, v_s)


def _store_pooled(cache: dict, cfg: AttentionConfig, phys, pooled,
                  keep) -> dict:
    """Write pooled router keys (f32, (..., Hkv, Dh)) at pages ``phys``,
    quantizing per (page, kv head) when the pool is quantized; rows where
    ``keep`` (leading shape of phys) is False retain the existing page
    content (the masked-write idiom of the trash-page scheme)."""
    with jax.named_scope("lm.kv_write"):
        if cfg.kv_quant == "none":
            cache["pooled_pages"] = cache["pooled_pages"].at[phys].set(
                jnp.where(keep[..., None, None],
                          pooled.astype(cache["pooled_pages"].dtype),
                          cache["pooled_pages"][phys]))
            return cache
        codes, scale = ops.quantize_rows(pooled, cfg.kv_quant)
        cache["pooled_pages"] = cache["pooled_pages"].at[phys].set(
            jnp.where(keep[..., None, None], codes,
                      cache["pooled_pages"][phys]))
        cache["pooled_scale"] = cache["pooled_scale"].at[phys].set(
            jnp.where(keep[..., None], scale, cache["pooled_scale"][phys]))
        return cache


def chunk_prefill_paged(params: dict, cfg: AttentionConfig, x: jax.Array,
                        cache: dict, *, page_row, offset, chunk_len, slot):
    """Prefill one chunk of ONE slot's prompt into the page pool.

    x         : (1, C, d_model) chunk embeddings, padded to the chunk size;
    page_row  : (maxP,) int32 — the slot's page-table row;
    offset    : scalar int32 — tokens of this slot already in the cache
                (must be a multiple of block_k: the engine chunks in
                block_k multiples);
    chunk_len : scalar int32 — valid tokens in this chunk (<= C);
    slot      : scalar int32 — batch row owning the per-slot linear states.

    Chunk attention is computed exactly (dense softmax over cached history +
    the chunk itself, causal within the chunk) — prefill is exact even for
    sla2 models; the sparse/linear split applies to decode, where per-step
    cost matters.  Returns (y (1, C, d_model), cache)."""
    _, c, _ = x.shape
    h, hkv, dh, bk = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.block_k)
    n_rep = h // hkv
    max_p = page_row.shape[0]
    positions = (offset + jnp.arange(c))[None]          # (1, C)
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    # --- write the chunk's K/V into the slot's pages (padding -> trash) ---
    tok_pos = offset + jnp.arange(c)
    valid_t = jnp.arange(c) < chunk_len
    logical = jnp.minimum(tok_pos // bk, max_p - 1)
    phys = jnp.where(valid_t, page_row[logical], 0)
    rows = tok_pos % bk
    cache = dict(cache)
    # write-time quantization (kv_quant): rows are quantized exactly once
    # here; k_eff/v_eff are the values a page read observes (the
    # quantize->dequantize round trip), from which the SLA2 block state
    # below is derived so it matches decode-time recompute from pages
    cache, k_eff, v_eff = _store_kv_rows(cache, cfg, phys, rows,
                                         k_new[0], v_new[0])

    # --- exact attention: chunk queries over history + chunk ---
    if use_fused(cfg, "prefill"):
        # page-table-aware flash: the kernel's index maps resolve logical ->
        # physical through page_row, so K/V pages are read in place and the
        # contiguous (1, maxP*bk, Dh) per-slot view is never materialised;
        # sliding-window / prefix-LM masks fold into the kernel's
        # in-register mask (quantized pools dequantize tiles in registers)
        o = fused_entry_fn("paged_flash_prefill", cfg)(
            q.transpose(0, 2, 1, 3)[0], cache["k_pages"], cache["v_pages"],
            page_row, offset=offset, block_k=bk, n_rep=n_rep,
            window=cfg.sliding_window, prefix_len=cfg.prefix_len,
            kv_quant=cfg.kv_quant, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"))
        o = o.astype(x.dtype).transpose(1, 0, 2).reshape(1, c, h * dh)
    else:
        # jnp gather reference (parity oracle): dense masked attention over
        # the materialised (dequantized) per-slot view
        k_all = _repeat_kv(_kv_gather_pages(cache, "k_pages", page_row[None]),
                           n_rep)
        v_all = _repeat_kv(_kv_gather_pages(cache, "v_pages", page_row[None]),
                           n_rep)
        q_t = q.transpose(0, 2, 1, 3)                   # (1, H, C, Dh)
        s = jnp.einsum("bhnd,bhmd->bhnm", q_t.astype(jnp.float32),
                       k_all.astype(jnp.float32)) / jnp.sqrt(dh)
        n_kv = k_all.shape[2]
        vis = masklib.token_causal_mask(c, n_kv, offset, cfg.prefix_len)
        if cfg.sliding_window is not None:
            qi = jnp.arange(c) + offset
            kj = jnp.arange(n_kv)
            sw = kj[None, :] >= (qi[:, None] - cfg.sliding_window + 1)
            if cfg.prefix_len:
                sw = sw | (kj[None, :] < cfg.prefix_len)
            vis = vis & sw
        s = jnp.where(vis, s, masklib.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhnm,bhmd->bhnd", p, v_all.astype(jnp.float32))
        o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(1, c, h * dh)

    # --- SLA2 block states for the chunk's blocks ---
    if cfg.mechanism == "sla2":
        t_c = c // bk                                   # blocks in the chunk
        # block state from k_eff/v_eff — the page-read view — so a later
        # decode-time recompute from (quantized) pages agrees exactly
        kb = k_eff.reshape(t_c, bk, hkv, dh).transpose(0, 2, 1, 3)
        vb = v_eff.reshape(t_c, bk, hkv, dh).transpose(0, 2, 1, 3)
        w = valid_t.reshape(t_c, bk).astype(jnp.float32)
        wb = w[:, None, :, None]
        kb32, vb32 = kb.astype(jnp.float32), vb.astype(jnp.float32)
        pooled = (kb32 * wb).sum(-2) / jnp.maximum(wb.sum(-2), 1.0)
        blk_ids = jnp.minimum(offset // bk + jnp.arange(t_c), max_p - 1)
        has_tok = w.sum(-1) > 0
        phys_blk = jnp.where(has_tok, page_row[blk_ids], 0)
        cache = _store_pooled(cache, cfg, phys_blk, pooled, has_tok)
        complete = (w.sum(-1) == bk)[:, None, None, None]
        kf = phi(kb32) * wb
        h_add = (jnp.einsum("thkd,thke->thde", kf, vb32 * wb)
                 * complete).sum(0)
        z_add = (kf.sum(-2) * complete[..., 0]).sum(0)
        # first chunk of a (possibly recycled) slot: reset the linear totals
        fresh = offset == 0
        cache["h_tot"] = cache["h_tot"].at[slot].set(
            jnp.where(fresh, 0.0, cache["h_tot"][slot]) + h_add)
        cache["z_tot"] = cache["z_tot"].at[slot].set(
            jnp.where(fresh, 0.0, cache["z_tot"][slot]) + z_add)
    return o @ params["wo"], cache


def decode_step_paged(params: dict, cfg: AttentionConfig, x_t: jax.Array,
                      cache: dict, *, page_table, lengths, active):
    """Batched one-token decode with per-slot offsets over the page pool.

    x_t: (B, 1, d_model); page_table: (B, maxP) int32; lengths: (B,) int32 —
    tokens already cached per slot (the new token lands at lengths[b]);
    active: (B,) bool — inactive rows write to the trash page and produce
    garbage logits the engine ignores."""
    b = x_t.shape[0]
    h, hkv, dh, bk = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.block_k)
    n_rep = h // hkv
    positions = lengths[:, None]
    q, k_new, v_new = _project_qkv(params, cfg, x_t, positions)
    q = q.transpose(0, 2, 1, 3)                         # (B, H, 1, Dh)

    cur_blk = lengths // bk
    phys_w = jnp.where(
        active, jnp.take_along_axis(page_table, cur_blk[:, None], 1)[:, 0], 0)
    rows = lengths % bk
    cache = dict(cache)
    cache, _, _ = _store_kv_rows(cache, cfg, phys_w, rows,
                                 k_new[:, 0], v_new[:, 0])
    t_new = lengths + 1

    if cfg.mechanism == "sla2":
        o = _sla2_decode_paged(params, cfg, q, cache, page_table, phys_w,
                               t_new, active)
    elif use_fused(cfg, "decode"):
        # fused dense paged decode: every mapped page streams through one
        # online-softmax pass (sliding window / prefix in the position
        # mask) — no per-slot _gather_pages copy; quantized pools
        # dequantize K/V tiles in registers, and decode_quant_bits enables
        # the same QAT tile path the SLA2 decode kernel has
        o = fused_entry_fn("dense_decode_fused", cfg)(
            q[:, :, 0].reshape(b, hkv, n_rep, dh),
            cache["k_pages"], cache["v_pages"], page_table, t_new,
            block_k=bk, window=cfg.sliding_window,
            prefix_len=cfg.prefix_len, quant_bits=cfg.decode_quant_bits,
            kv_quant=cfg.kv_quant, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"))
        o = o.reshape(b, h, dh)[:, :, None, :]
    else:
        # jnp gather reference (parity oracle for the dense fused kernel)
        k_all = _repeat_kv(_kv_gather_pages(cache, "k_pages", page_table),
                           n_rep)
        v_all = _repeat_kv(_kv_gather_pages(cache, "v_pages", page_table),
                           n_rep)
        s = jnp.einsum("bhqd,bhmd->bhqm", q.astype(jnp.float32),
                       k_all.astype(jnp.float32)) / jnp.sqrt(dh)
        pos_k = jnp.arange(k_all.shape[2])
        vis = pos_k[None, :] < t_new[:, None]           # (B, S)
        if cfg.sliding_window is not None:
            sw = pos_k[None, :] >= (t_new[:, None] - cfg.sliding_window)
            if cfg.prefix_len:
                sw = sw | (pos_k[None, :] < cfg.prefix_len)
            vis = vis & sw
        s = jnp.where(vis[:, None, None, :], s, masklib.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqm,bhmd->bhqd", p, v_all.astype(jnp.float32))
    o = o.astype(x_t.dtype).transpose(0, 2, 1, 3).reshape(b, 1, h * dh)
    return o @ params["wo"], cache


def _sla2_decode_paged(params: dict, cfg: AttentionConfig, q, cache,
                       page_table, phys_w, t_new, active):
    """_sla2_decode with per-slot lengths and page-table indirection: router
    over per-page pooled keys, then either the fused Pallas paged-attention
    kernel (``paged_impl='fused'``: selected pages are read straight from
    the pool, sparse + linear-correction + alpha combine in one pass) or
    the jnp gather reference (``'gather'``: materialises page copies; kept
    as the parity oracle for the kernel)."""
    sla2_p = params["sla2"]
    b, h, _, dh = q.shape
    hkv = cfg.num_kv_heads
    n_rep = h // hkv
    bk = cfg.block_k
    t_n = page_table.shape[1]

    # --- block stats for each row's current block (trash page if inactive) --
    cur_blk = (t_new - 1) // bk
    kblk = _kv_read(cache, "k_pages", phys_w)            # (B, Hkv, bk, Dh)
    vblk = _kv_read(cache, "v_pages", phys_w)
    in_blk = (cur_blk[:, None] * bk + jnp.arange(bk)[None, :]) \
        < t_new[:, None]                                 # (B, bk)
    w = in_blk.astype(jnp.float32)[:, None, :, None]
    pooled_cur = (kblk * w).sum(-2) / jnp.maximum(w.sum(-2), 1.0)
    cache = _store_pooled(cache, cfg, phys_w, pooled_cur, active)
    completed = (t_new % bk) == 0
    kf_cur = phi(kblk) * w
    h_cur = jnp.einsum("bhkd,bhke->bhde", kf_cur, vblk * w)
    z_cur = kf_cur.sum(-2)
    upd = (completed & active)[:, None]
    cache["h_tot"] = cache["h_tot"] + jnp.where(upd[..., None, None], h_cur,
                                                0.0)
    cache["z_tot"] = cache["z_tot"] + jnp.where(upd[..., None], z_cur, 0.0)

    # --- route: group-shared over the slot's logical blocks ---
    with jax.named_scope("sla2.router"):
        rp = sla2_p.get("router", {})
        qr = q[:, :, 0].astype(jnp.float32)              # (B, H, Dh)
        pk = _kv_read(cache, "pooled_pages", page_table)  # (B, T_n, Hkv, Dh)
        pk = pk.transpose(0, 2, 1, 3)                    # (B, Hkv, T_n, Dh)
        if rp:
            qr = qr @ rp["proj_q"].astype(jnp.float32)
            pk = pk @ rp["proj_k"].astype(jnp.float32)
        qr_g = qr.reshape(b, hkv, n_rep, dh).mean(axis=2)
        scores = jnp.einsum("bhd,bhtd->bht", qr_g, pk) / jnp.sqrt(dh)
        blk_ids = jnp.arange(t_n)
        allowed = blk_ids[None, None, :] <= cur_blk[:, None, None]
        scores = jnp.where(allowed, scores, masklib.NEG_INF)
        scores = jnp.where(blk_ids[None, None, :] == cur_blk[:, None, None],
                           jnp.inf, scores)
        k_sel = max(1, round(cfg.k_frac * t_n))
        top_vals, idx = jax.lax.top_k(scores, k_sel)     # (B, Hkv, K_sel)
        valid = top_vals > masklib.NEG_INF * 0.5

        pt = jnp.broadcast_to(page_table[:, None, :], (b, hkv, t_n))
        phys_sel = jnp.where(valid, jnp.take_along_axis(pt, idx, axis=2), 0)
        complete_bound = cur_blk + jnp.where(completed, 1, 0)
        sel_complete = valid & (idx < complete_bound[:, None, None])

    if use_fused(cfg, "decode"):
        # fused Pallas kernel: one HBM traversal of the selected pages does
        # sparse flash + the linear complement subtraction + alpha combine
        logit = sla2_p["alpha_logit"][:, -1].astype(jnp.float32)
        if logit.shape[0] == 1 and h > 1:
            logit = jnp.broadcast_to(logit, (h,))
        alpha = jnp.broadcast_to(logit.reshape(1, hkv, n_rep),
                                 (b, hkv, n_rep))
        o = fused_entry_fn("sla2_decode_fused", cfg)(
            q[:, :, 0].reshape(b, hkv, n_rep, dh),
            cache["k_pages"], cache["v_pages"], phys_sel, idx,
            valid.astype(jnp.int32), sel_complete.astype(jnp.int32),
            t_new, cache["h_tot"], cache["z_tot"], alpha,
            block_k=bk, quant_bits=cfg.decode_quant_bits,
            kv_quant=cfg.kv_quant, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"))
        return o.reshape(b, h, dh)[:, :, None, :]

    # --- jnp gather reference: page-table indirection, gather, flash ---
    k_sel_blocks = _kv_gather_blocks(cache, "k_pages", phys_sel)
    v_sel_blocks = _kv_gather_blocks(cache, "v_pages", phys_sel)
    q_g = q[:, :, 0].astype(jnp.float32).reshape(b, hkv, n_rep, dh)
    s = jnp.einsum("bhgd,bhjkd->bhgjk", q_g, k_sel_blocks) / jnp.sqrt(dh)
    pos = idx[..., None] * bk + jnp.arange(bk)[None, None, None, :]
    vis = (pos < t_new[:, None, None, None]) & valid[..., None]
    s = jnp.where(vis[:, :, None], s, masklib.NEG_INF)
    p = jax.nn.softmax(s.reshape(b, hkv, n_rep, -1), axis=-1).reshape(s.shape)
    o_s = jnp.einsum("bhgjk,bhjkd->bhgd", p, v_sel_blocks)

    # --- linear branch: totals minus selected complete blocks ---
    qfeat = phi(q[:, :, 0]).reshape(b, hkv, n_rep, dh)
    kf_sel = phi(k_sel_blocks)
    ls = jnp.einsum("bhgd,bhjkd->bhgjk", qfeat, kf_sel)
    ls = ls * sel_complete[:, :, None, :, None].astype(jnp.float32)
    sub_num = jnp.einsum("bhgjk,bhjkd->bhgd", ls, v_sel_blocks)
    sub_den = ls.sum(axis=(-1, -2))
    den_tot = jnp.einsum("bhgd,bhd->bhg", qfeat, cache["z_tot"])
    num = jnp.einsum("bhgd,bhde->bhge", qfeat, cache["h_tot"]) - sub_num
    den = (den_tot - sub_den)
    den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)[..., None]
    o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    # --- combine ---
    a = jax.nn.sigmoid(sla2_p["alpha_logit"].astype(jnp.float32))
    if a.shape[0] == 1 and h > 1:
        a = jnp.broadcast_to(a, (h, a.shape[1]))
    a_last = a[:, -1].reshape(1, hkv, n_rep, 1)
    a_eff = jnp.where(den > 0, a_last, 1.0)
    o = a_eff * o_s + (1.0 - a_eff) * o_l
    return o.reshape(b, h, dh)[:, :, None, :]


# ---------------------------------------------------------------------------
# Multi-token verify window + linear-branch drafting (speculative decoding)
# ---------------------------------------------------------------------------
#
# Self-speculative decoding reuses SLA2's own decomposition: the linear
# branch (phi(k)·v running totals) drafts W-1 tokens without touching the
# page pool, then ONE windowed verify pass runs the full sparse+linear
# attention over all W rows at once.  The verify pass writes the window's
# K/V into pages but commits NO block state — pooled router keys and the
# linear totals are committed separately (``commit_paged_window``) once the
# host has decided the accepted prefix, so a rejected suffix rolls back by
# simply never being committed.  See docs/speculative.md.

def window_span(window: int, block_k: int) -> int:
    """Most logical blocks a ``window``-token run starting at any offset
    can touch (bounds the static span loops in verify/commit)."""
    return (window + block_k - 2) // block_k + 1


def decode_window_paged(params: dict, cfg: AttentionConfig, x_w: jax.Array,
                        cache: dict, *, page_table, lengths, active,
                        window_len):
    """Verify pass of speculative decoding: W query rows per slot, one call.

    x_w: (B, W, d_model) window embeddings — row 0 is the last accepted
    token, rows 1.. the draft tokens; lengths: (B,) tokens already cached
    (row w lands at position lengths + w); active: (B,) bool;
    window_len: (B,) int32 valid rows per slot — rows >= window_len write
    to the trash page and produce garbage outputs the engine ignores.

    Writes the whole window's K/V into the slot's pages but commits NO
    SLA2 block state (pooled keys / linear totals): those follow host-side
    acceptance via ``commit_paged_window``.  Rejected rows' K/V bytes sit
    beyond the committed length — invisible to every masked read and
    overwritten by the next window.  Returns (y (B, W, d_model), cache)."""
    b, wdw, _ = x_w.shape
    h, hkv, dh, bk = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.block_k)
    n_rep = h // hkv
    max_p = page_table.shape[1]
    tok_pos = lengths[:, None] + jnp.arange(wdw)        # (B, W)
    q, k_new, v_new = _project_qkv(params, cfg, x_w, tok_pos)
    q = q.transpose(0, 2, 1, 3)                         # (B, H, W, Dh)

    valid_w = (jnp.arange(wdw)[None, :] < window_len[:, None]) \
        & active[:, None]
    logical = jnp.minimum(tok_pos // bk, max_p - 1)
    phys_w = jnp.where(valid_w,
                       jnp.take_along_axis(page_table, logical, 1), 0)
    rows = tok_pos % bk
    cache = dict(cache)
    cache, _, _ = _store_kv_rows(cache, cfg, phys_w, rows, k_new, v_new)
    t_new = tok_pos + 1                                 # (B, W)

    if cfg.mechanism == "sla2":
        o = _sla2_decode_window(params, cfg, q, cache, page_table, t_new,
                                lengths)
        o = o.astype(x_w.dtype).reshape(b, wdw, h * dh)
    elif use_fused(cfg, "verify"):
        # fused dense verify: the dense decode grid at W query rows — the
        # per-row position mask is the causal intra-window mask, giving
        # non-SLA2 stacks a multi-token verify window with no gather
        o = fused_entry_fn("dense_decode_verify", cfg)(
            q.reshape(b, hkv, n_rep, wdw, dh).transpose(0, 1, 3, 2, 4),
            cache["k_pages"], cache["v_pages"], page_table, t_new,
            block_k=bk, window=cfg.sliding_window,
            prefix_len=cfg.prefix_len, quant_bits=cfg.decode_quant_bits,
            kv_quant=cfg.kv_quant, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"))
        o = o.transpose(0, 2, 1, 3, 4).astype(x_w.dtype) \
            .reshape(b, wdw, h * dh)
    else:
        # jnp gather reference (parity oracle for the dense verify kernel)
        k_all = _repeat_kv(_kv_gather_pages(cache, "k_pages", page_table),
                           n_rep)
        v_all = _repeat_kv(_kv_gather_pages(cache, "v_pages", page_table),
                           n_rep)
        s = jnp.einsum("bhwd,bhmd->bhwm", q.astype(jnp.float32),
                       k_all.astype(jnp.float32)) / jnp.sqrt(dh)
        pos_k = jnp.arange(k_all.shape[2])
        vis = pos_k[None, None, :] < t_new[:, :, None]  # (B, W, S)
        if cfg.sliding_window is not None:
            sw = pos_k[None, None, :] >= (t_new[:, :, None]
                                          - cfg.sliding_window)
            if cfg.prefix_len:
                sw = sw | (pos_k[None, None, :] < cfg.prefix_len)
            vis = vis & sw
        s = jnp.where(vis[:, None], s, masklib.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhwm,bhmd->bhwd", p, v_all.astype(jnp.float32))
        o = o.astype(x_w.dtype).transpose(0, 2, 1, 3).reshape(b, wdw,
                                                              h * dh)
    return o @ params["wo"], cache


def _sla2_decode_window(params: dict, cfg: AttentionConfig, q, cache,
                        page_table, t_new, lengths):
    """Per-row SLA2 routing + sparse/linear attention over a W-token
    window with all block state TRANSIENT (nothing committed to cache):

      * pooled router keys for the blocks the window touches are computed
        per row from page content masked to the row's length — the value
        sequential decode would have had in ``pooled_pages`` at that step;
      * each row's linear totals are the cache totals plus the (h, z) of
        span blocks that complete EARLIER in the window, so the complement
        trick subtracts routed complete blocks exactly as at decode;
      * the position mask ``pos < t_new[row]`` doubles as the causal
        intra-window mask (later window tokens sit at higher positions).

    q: (B, H, W, Dh); t_new: (B, W).  Returns (B, W, Hkv, n_rep, Dh) f32."""
    sla2_p = params["sla2"]
    b, h, wdw, dh = q.shape
    hkv = cfg.num_kv_heads
    n_rep = h // hkv
    bk = cfg.block_k
    t_n = page_table.shape[1]
    n_span = window_span(wdw, bk)

    # --- transient stats for the blocks the window can touch ---
    blk0 = lengths // bk
    span_ids_raw = blk0[:, None] + jnp.arange(n_span)[None, :]  # (B, S)
    genuine = span_ids_raw < t_n
    span_ids = jnp.minimum(span_ids_raw, t_n - 1)
    span_phys = jnp.take_along_axis(page_table, span_ids, 1)    # (B, S)
    kblk = _kv_read(cache, "k_pages", span_phys)        # (B,S,Hkv,bk,Dh)
    vblk = _kv_read(cache, "v_pages", span_phys)
    pos_blk = span_ids[:, :, None] * bk + jnp.arange(bk)        # (B,S,bk)
    msk = (pos_blk[:, None] < t_new[:, :, None, None]) \
        .astype(jnp.float32)                                    # (B,W,S,bk)
    pooled_ws = jnp.einsum("bwsk,bshkd->bwshd", msk, kblk) \
        / jnp.maximum(msk.sum(-1), 1.0)[..., None, None]
    # (h, z) of each span block over its FULL page — only ever used gated
    # by per-row completeness, when all bk positions are visible/written
    kf_span = phi(kblk)
    h_span = jnp.einsum("bshkd,bshke->bshde", kf_span, vblk)
    z_span = kf_span.sum(-2)                                    # (B,S,Hkv,Dh)
    # span blocks complete at row w (span starts at lengths // bk, so none
    # of them can already be inside the cache totals)
    cmplt = (genuine[:, None]
             & ((span_ids[:, None] + 1) * bk <= t_new[:, :, None])) \
        .astype(jnp.float32)                                    # (B,W,S)
    h_eff = cache["h_tot"][:, None] \
        + jnp.einsum("bws,bshde->bwhde", cmplt, h_span)
    z_eff = cache["z_tot"][:, None] \
        + jnp.einsum("bws,bshd->bwhd", cmplt, z_span)

    # --- route per row: group-shared, transient pooled keys for the span --
    rp = sla2_p.get("router", {})
    qr = q.astype(jnp.float32)                                  # (B,H,W,Dh)
    pk = _kv_read(cache, "pooled_pages", page_table)
    pk = pk.transpose(0, 2, 1, 3)                               # (B,Hkv,T,Dh)
    pw = pooled_ws
    if rp:
        qr = qr @ rp["proj_q"].astype(jnp.float32)
        pk = pk @ rp["proj_k"].astype(jnp.float32)
        pw = pw @ rp["proj_k"].astype(jnp.float32)
    qr_g = qr.reshape(b, hkv, n_rep, wdw, dh).mean(axis=2)      # (B,Hkv,W,Dh)
    scores = jnp.einsum("bhwd,bhtd->bwht", qr_g, pk) / jnp.sqrt(dh)
    s_span = jnp.einsum("bhwd,bwshd->bwhs", qr_g, pw) / jnp.sqrt(dh)
    blk_ids = jnp.arange(t_n)
    # the cache pooled keys of span blocks are stale (only committed after
    # acceptance): overwrite their scores with the per-row transient ones
    for s_i in range(n_span):
        m = (blk_ids[None, None, None, :]
             == span_ids[:, s_i, None, None, None]) \
            & genuine[:, s_i, None, None, None]
        scores = jnp.where(m, s_span[:, :, :, s_i:s_i + 1], scores)
    cur_blk = (t_new - 1) // bk                                 # (B, W)
    allowed = blk_ids[None, None, None, :] <= cur_blk[:, :, None, None]
    scores = jnp.where(allowed, scores, masklib.NEG_INF)
    scores = jnp.where(blk_ids[None, None, None, :]
                       == cur_blk[:, :, None, None], jnp.inf, scores)
    k_sel = max(1, round(cfg.k_frac * t_n))
    top_vals, idx = jax.lax.top_k(scores, k_sel)                # (B,W,Hkv,K)
    valid = top_vals > masklib.NEG_INF * 0.5
    pt = jnp.broadcast_to(page_table[:, None, None, :], (b, wdw, hkv, t_n))
    phys_sel = jnp.where(valid, jnp.take_along_axis(pt, idx, axis=3), 0)
    completed = (t_new % bk) == 0
    complete_bound = cur_blk + jnp.where(completed, 1, 0)
    sel_complete = valid & (idx < complete_bound[:, :, None, None])

    if use_fused(cfg, "verify"):
        # one Pallas pass over the routed pages for ALL window rows: the
        # decode grid extended from 1 to W query rows per (slot, kv head)
        logit = sla2_p["alpha_logit"][:, -1].astype(jnp.float32)
        if logit.shape[0] == 1 and h > 1:
            logit = jnp.broadcast_to(logit, (h,))
        alpha = jnp.broadcast_to(logit.reshape(1, hkv, n_rep),
                                 (b, hkv, n_rep))
        to_k = lambda x: x.transpose(0, 2, 1, 3).astype(jnp.int32)
        o = fused_entry_fn("sla2_decode_verify", cfg)(
            q.reshape(b, hkv, n_rep, wdw, dh).transpose(0, 1, 3, 2, 4),
            cache["k_pages"], cache["v_pages"],
            to_k(phys_sel), to_k(idx), to_k(valid.astype(jnp.int32)),
            to_k(sel_complete.astype(jnp.int32)), t_new,
            h_eff.transpose(0, 2, 1, 3, 4), z_eff.transpose(0, 2, 1, 3),
            alpha, block_k=bk, quant_bits=cfg.decode_quant_bits,
            kv_quant=cfg.kv_quant, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"))
        return o.transpose(0, 2, 1, 3, 4)       # (B, W, Hkv, n_rep, Dh)

    # --- jnp gather reference (parity oracle for the verify kernel) ---
    phys_f = phys_sel.reshape(b * wdw, hkv, k_sel)
    k_sel_blocks = _kv_gather_blocks(cache, "k_pages", phys_f) \
        .reshape(b, wdw, hkv, k_sel, bk, dh)
    v_sel_blocks = _kv_gather_blocks(cache, "v_pages", phys_f) \
        .reshape(b, wdw, hkv, k_sel, bk, dh)
    q_g = q.astype(jnp.float32).reshape(b, hkv, n_rep, wdw, dh) \
        .transpose(0, 3, 1, 2, 4)                               # (B,W,H,g,D)
    s = jnp.einsum("bwhgd,bwhjkd->bwhgjk", q_g, k_sel_blocks) / jnp.sqrt(dh)
    pos = idx[..., None] * bk + jnp.arange(bk)                  # (B,W,H,K,bk)
    vis = (pos < t_new[:, :, None, None, None]) & valid[..., None]
    s = jnp.where(vis[:, :, :, None], s, masklib.NEG_INF)
    p = jax.nn.softmax(s.reshape(b, wdw, hkv, n_rep, -1),
                       axis=-1).reshape(s.shape)
    o_s = jnp.einsum("bwhgjk,bwhjkd->bwhgd", p, v_sel_blocks)

    # --- linear branch: per-row effective totals minus selected blocks ---
    qfeat = phi(q).reshape(b, hkv, n_rep, wdw, dh).transpose(0, 3, 1, 2, 4)
    kf_sel = phi(k_sel_blocks)
    ls = jnp.einsum("bwhgd,bwhjkd->bwhgjk", qfeat, kf_sel)
    ls = ls * sel_complete[:, :, :, None, :, None].astype(jnp.float32)
    sub_num = jnp.einsum("bwhgjk,bwhjkd->bwhgd", ls, v_sel_blocks)
    sub_den = ls.sum(axis=(-1, -2))
    den_tot = jnp.einsum("bwhgd,bwhd->bwhg", qfeat, z_eff)
    num = jnp.einsum("bwhgd,bwhde->bwhge", qfeat, h_eff) - sub_num
    den = den_tot - sub_den
    den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)[..., None]
    o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    a = jax.nn.sigmoid(sla2_p["alpha_logit"].astype(jnp.float32))
    if a.shape[0] == 1 and h > 1:
        a = jnp.broadcast_to(a, (h, a.shape[1]))
    a_last = a[:, -1].reshape(1, 1, hkv, n_rep, 1)
    a_eff = jnp.where(den > 0, a_last, 1.0)
    return a_eff * o_s + (1.0 - a_eff) * o_l    # (B, W, Hkv, n_rep, Dh)


def commit_paged_window(cfg: AttentionConfig, cache: dict, *, page_table,
                        lengths, accepted, active, window: int) -> dict:
    """Commit the ACCEPTED prefix of a verify window into the SLA2 block
    state: rewrite the pooled router keys of every block the prefix
    touches (masked to the new committed length) and fold newly completed
    blocks into the per-slot linear totals.  K/V pages were already
    written by the verify pass; mechanisms without block state (dense
    attention) need no commit.

    lengths: (B,) committed tokens BEFORE the window; accepted: (B,) rows
    being committed (0 for slots that sat out the step); window: the
    static window size W, bounding the blocks touched."""
    if cfg.mechanism != "sla2":
        return cache
    bk = cfg.block_k
    t_n = page_table.shape[1]
    n_span = window_span(window, bk)
    new_len = lengths + accepted
    blk0 = lengths // bk
    span_ids_raw = blk0[:, None] + jnp.arange(n_span)[None, :]  # (B, S)
    genuine = span_ids_raw < t_n
    span_ids = jnp.minimum(span_ids_raw, t_n - 1)
    span_phys = jnp.take_along_axis(page_table, span_ids, 1)
    kblk = _kv_read(cache, "k_pages", span_phys)        # (B,S,Hkv,bk,Dh)
    vblk = _kv_read(cache, "v_pages", span_phys)
    pos_blk = span_ids[:, :, None] * bk + jnp.arange(bk)        # (B,S,bk)
    msk = (pos_blk < new_len[:, None, None]).astype(jnp.float32)
    live = genuine & active[:, None] & (accepted > 0)[:, None]
    has_tok = (msk.sum(-1) > 0) & live                          # (B,S)
    pooled = jnp.einsum("bsk,bshkd->bshd", msk, kblk) \
        / jnp.maximum(msk.sum(-1), 1.0)[..., None, None]
    upd_phys = jnp.where(has_tok, span_phys, 0)
    cache = dict(cache)
    cache = _store_pooled(cache, cfg, upd_phys, pooled, has_tok)
    # blocks that completed inside the accepted prefix join the totals
    newc = (live & ((span_ids + 1) * bk <= new_len[:, None])
            & ((span_ids + 1) * bk > lengths[:, None])).astype(jnp.float32)
    kf = phi(kblk)
    cache["h_tot"] = cache["h_tot"] \
        + jnp.einsum("bs,bshkd,bshke->bhde", newc, kf, vblk)
    cache["z_tot"] = cache["z_tot"] \
        + jnp.einsum("bs,bshkd->bhd", newc, kf)
    return cache


def linear_draft_state(cfg: AttentionConfig, cache: dict, *, page_table,
                       lengths, active) -> dict:
    """Speculative draft state for one attention layer: linear-branch
    running totals over EVERYTHING cached so far — the committed complete-
    block totals plus the current partial block's phi(k)·v mass read from
    its page.  Kept separate from the cache, so rejecting a draft rolls
    back by dropping the state.
    Returns {"h": (B, Hkv, Dh, Dh), "z": (B, Hkv, Dh)} f32."""
    if cfg.mechanism != "sla2":
        raise ValueError("linear drafting requires mechanism='sla2'")
    bk = cfg.block_k
    t_n = page_table.shape[1]
    blk0 = jnp.minimum(lengths // bk, t_n - 1)
    phys = jnp.where(active,
                     jnp.take_along_axis(page_table, blk0[:, None], 1)[:, 0],
                     0)
    kblk = _kv_read(cache, "k_pages", phys)             # (B, Hkv, bk, Dh)
    vblk = _kv_read(cache, "v_pages", phys)
    pos = blk0[:, None] * bk + jnp.arange(bk)           # (B, bk)
    w = ((pos < lengths[:, None]) & active[:, None]) \
        .astype(jnp.float32)[:, None, :, None]
    kf = phi(kblk) * w
    h = cache["h_tot"] + jnp.einsum("bhkd,bhke->bhde", kf, vblk * w)
    z = cache["z_tot"] + kf.sum(-2)
    return {"h": h, "z": z}


def linear_draft_attention(params: dict, cfg: AttentionConfig,
                           x_t: jax.Array, state: dict, *, positions,
                           active):
    """One draft-token decode through the LINEAR branch only — no page
    reads, no routing: O(d^2) per token against the running totals.  The
    new token's own phi(k)·v joins the state first, so the draft mimics
    attention over the full prefix including self (at real decode the
    sparse branch always covers the current block).
    x_t: (B, 1, d_model); positions: (B,).  Returns (y, new state)."""
    b = x_t.shape[0]
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n_rep = h // hkv
    q, k_new, v_new = _project_qkv(params, cfg, x_t, positions[:, None])
    kf = phi(k_new[:, 0])                               # (B, Hkv, Dh)
    v0 = v_new[:, 0].astype(jnp.float32)
    gate = active[:, None, None]
    state = {
        "h": state["h"] + jnp.where(
            gate[..., None], jnp.einsum("bhd,bhe->bhde", kf, v0), 0.0),
        "z": state["z"] + jnp.where(gate, kf, 0.0),
    }
    qfeat = phi(q[:, 0]).reshape(b, hkv, n_rep, dh)
    num = jnp.einsum("bhgd,bhde->bhge", qfeat, state["h"])
    den = jnp.einsum("bhgd,bhd->bhg", qfeat, state["z"])[..., None]
    o = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)
    o = o.reshape(b, 1, h * dh).astype(x_t.dtype)
    return o @ params["wo"], state


def _sla2_decode(params: dict, cfg: AttentionConfig, q, cache, t_new):
    """SLA2 decode: router over pooled block keys -> sparse flash over the
    K_sel selected blocks + linear state over the complement of complete
    blocks.  The current (possibly partial) block is always routed sparse."""
    sla2_p = params["sla2"]
    b, h, _, dh = q.shape
    hkv = cfg.num_kv_heads
    n_rep = h // hkv
    bk = cfg.block_k
    max_len = cache["k"].shape[2]
    t_n = max_len // bk

    # --- update block stats for the block containing the new token ---
    cur_blk = (t_new - 1) // bk                      # block being filled
    k_cache, v_cache = cache["k"], cache["v"]
    kblk = jax.lax.dynamic_slice(
        k_cache, (0, 0, cur_blk * bk, 0), (b, hkv, bk, dh)).astype(jnp.float32)
    vblk = jax.lax.dynamic_slice(
        v_cache, (0, 0, cur_blk * bk, 0), (b, hkv, bk, dh)).astype(jnp.float32)
    in_blk = (cur_blk * bk + jnp.arange(bk)) < t_new  # valid positions
    w = in_blk.astype(jnp.float32)[None, None, :, None]
    pooled_cur = (kblk * w).sum(axis=-2) / jnp.maximum(w.sum(axis=-2), 1.0)
    cache["pooled_k"] = jax.lax.dynamic_update_slice(
        cache["pooled_k"], pooled_cur[:, :, None].astype(
            cache["pooled_k"].dtype), (0, 0, cur_blk, 0))
    completed = (t_new % bk) == 0
    kf_cur = phi(kblk) * w
    h_cur = jnp.einsum("bhkd,bhke->bhde", kf_cur, vblk * w)
    z_cur = kf_cur.sum(axis=-2)
    cache["h_tot"] = cache["h_tot"] + jnp.where(completed, h_cur, 0.0)
    cache["z_tot"] = cache["z_tot"] + jnp.where(completed, z_cur, 0.0)

    # --- route: GROUP-SHARED routing (one block set per KV head) ---
    # Per-q-head routing would gather K/V repeated to every query head
    # (n_rep x the tiles, 100s of GiB at llama3 decode_32k); sharing the
    # selection across each GQA group keeps the gather at KV-head width.
    # Scores: mean over the group's query heads (DESIGN.md §2, causal/GQA
    # adaptation — the paper's DiT is MHA so this is new surface).
    rp = sla2_p.get("router", {})
    qr = q[:, :, 0].astype(jnp.float32)              # (B, H, Dh)
    pk = cache["pooled_k"].astype(jnp.float32)       # (B, Hkv, T_n, Dh)
    if rp:
        qr = qr @ rp["proj_q"].astype(jnp.float32)
        pk = pk @ rp["proj_k"].astype(jnp.float32)
    qr_g = qr.reshape(b, hkv, n_rep, dh).mean(axis=2)
    scores = jnp.einsum("bhd,bhtd->bht", qr_g, pk) / jnp.sqrt(dh)
    blk_ids = jnp.arange(t_n)
    allowed = blk_ids[None, None, :] <= cur_blk      # causal blocks
    scores = jnp.where(allowed, scores, masklib.NEG_INF)
    scores = jnp.where(blk_ids[None, None, :] == cur_blk, jnp.inf, scores)
    k_sel = max(1, round(cfg.k_frac * t_n))
    top_vals, idx = jax.lax.top_k(scores, k_sel)     # (B, Hkv, K_sel)
    valid = top_vals > masklib.NEG_INF * 0.5

    # --- sparse branch: gather selected blocks (KV-head width), flash ---
    gather = lambda blocks, ids: jnp.take_along_axis(
        blocks, ids[..., None, None], axis=2)
    k_sel_blocks = gather(k_cache.reshape(b, hkv, t_n, bk, dh),
                          idx).astype(jnp.float32)   # (B, Hkv, K_sel, bk, Dh)
    v_sel_blocks = gather(v_cache.reshape(b, hkv, t_n, bk, dh),
                          idx).astype(jnp.float32)
    q_g = q[:, :, 0].astype(jnp.float32).reshape(b, hkv, n_rep, dh)
    s = jnp.einsum("bhgd,bhjkd->bhgjk", q_g, k_sel_blocks) / jnp.sqrt(dh)
    pos = idx[..., None] * bk + jnp.arange(bk)[None, None, None, :]
    vis = (pos < t_new) & valid[..., None]           # (B, Hkv, K_sel, bk)
    s = jnp.where(vis[:, :, None], s, masklib.NEG_INF)
    p = jax.nn.softmax(s.reshape(b, hkv, n_rep, -1), axis=-1).reshape(s.shape)
    o_s = jnp.einsum("bhgjk,bhjkd->bhgd", p, v_sel_blocks)

    # --- linear branch: totals minus selected complete blocks ---
    # phi(q).h_j is contracted directly over the gathered tiles:
    #   phi(q) . h_j = sum_k (phi(q).phi(k_jk)) v_jk
    # so no (K_sel, Dh, Dh) per-block states are ever formed.
    complete_bound = cur_blk + jnp.where(completed, 1, 0)
    sel_complete = (valid & (idx < complete_bound))  # (B, Hkv, K_sel)
    qfeat = phi(q[:, :, 0]).reshape(b, hkv, n_rep, dh)
    kf_sel = phi(k_sel_blocks)                       # (B, Hkv, K_sel, bk, Dh)
    ls = jnp.einsum("bhgd,bhjkd->bhgjk", qfeat, kf_sel)
    ls = ls * sel_complete[:, :, None, :, None].astype(jnp.float32)
    sub_num = jnp.einsum("bhgjk,bhjkd->bhgd", ls, v_sel_blocks)
    sub_den = ls.sum(axis=(-1, -2))                  # (B, Hkv, n_rep)
    den_tot = jnp.einsum("bhgd,bhd->bhg", qfeat, cache["z_tot"])
    num = jnp.einsum("bhgd,bhde->bhge", qfeat, cache["h_tot"]) - sub_num
    # relative empty-complement threshold (cancellation residuals are not 0)
    den = (den_tot - sub_den)
    den = jnp.where(den > 1e-4 * den_tot + 1e-12, den, 0.0)[..., None]
    o_l = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)

    # --- combine ---
    a = jax.nn.sigmoid(sla2_p["alpha_logit"].astype(jnp.float32))
    if a.shape[0] == 1 and h > 1:
        a = jnp.broadcast_to(a, (h, a.shape[1]))
    a_last = a[:, -1].reshape(1, hkv, n_rep, 1)      # decode uses last alpha
    a_eff = jnp.where(den > 0, a_last, 1.0)
    o = a_eff * o_s + (1.0 - a_eff) * o_l            # (B, Hkv, n_rep, Dh)
    return o.reshape(b, h, dh)[:, :, None, :]        # (B, H, 1, Dh)
