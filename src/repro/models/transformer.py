"""The decoder-only transformer substrate for every assigned LM architecture.

One ``ModelConfig`` describes an architecture; layers are laid out as

    [first_kinds...unrolled]  +  scan over n_groups x layer_kinds

so heterogeneous stacks (llama4's alternating dense/MoE, deepseek's leading
dense-FFN layer, xlstm's 7:1 mLSTM:sLSTM pattern) scan over a homogeneous
*group* while keeping the HLO compact (one group body regardless of depth).

Layer kinds:
    dense     pre-norm attention + gated MLP
    moe       pre-norm attention + MoE FFN (EP-sharded)
    mla_dense DeepSeek MLA attention + gated MLP
    mla_moe   DeepSeek MLA attention + MoE FFN
    hybrid    Hymba parallel attention+Mamba mixer + MLP
    mlstm     xLSTM matrix-memory block (no FFN)
    slstm     xLSTM scalar-memory block (no FFN)

Params are plain nested dicts (stacked on a leading group axis inside
"groups"); sharding is assigned by key-path in distributed/sharding.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import hybrid as HY
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.core import maps


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One decoder-only architecture: layer layout, attention mechanism and
    masking, SLA2 knobs, paged-serving switches, and training/system
    fields.  See the module docstring for the layer-kind vocabulary."""
    name: str = "model"
    family: str = "dense"           # dense|moe|ssm|hybrid|vlm|audio|dit
    n_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 512
    vocab_size: int = 1024
    # layer layout
    layer_kinds: tuple = ("dense",)
    first_kinds: tuple = ()
    # attention
    mechanism: str = "sla2"         # full | sla2 | sla | sparse_only
    causal: bool = True
    sliding_window: Optional[int] = None
    qk_norm: bool = False
    prefix_len: int = 0             # prefix-LM tokens (VLM image prefix)
    rope_theta: float = 10000.0
    use_rope: bool = True
    mlp_activation: str = "silu"
    mlp_gated: bool = True
    tie_embeddings: bool = True
    embed_scale: bool = False       # gemma: embeddings * sqrt(d_model)
    # SLA2
    block_q: int = 128
    block_k: int = 64
    k_frac: float = 0.05
    quant_bits: str = "int8"
    sla2_impl: str = "gather"
    q_chunk: int = 16
    fuse_branches: bool = False
    # paged serving: 'fused' Pallas page-table kernels vs 'gather' jnp
    # reference (parity oracle); 'auto' = fused on compiled backends,
    # gather on CPU.  decode_quant_bits enables the QAT tile path inside
    # the fused decode kernel ('none' | 'int8' | 'fp8')
    paged_impl: str = "auto"
    decode_quant_bits: str = "none"
    # page-pool STORAGE dtype ('none' | 'int8' | 'fp8'): low-bit K/V pages
    # with per-row f32 scales, dequantized in registers by the fused
    # kernels / gather oracle — see models/attention.AttentionConfig
    kv_quant: str = "none"
    # sharded serving: a jax.sharding.Mesh routes the fused paged entries
    # through shard_map (distributed/shard_paged); the engine sets this
    # via its model override when EngineConfig.mesh is given
    mesh: Optional[Any] = None
    # sub-configs
    moe: Optional[MOE.MoEConfig] = None
    mla: Optional[MLA.MLAConfig] = None
    ssm: Optional[SSM.SSMConfig] = None
    # training / system
    remat: str = "full"             # full | none
    dtype: str = "bfloat16"
    max_target_len: int = 8192      # sizes the alpha table at init
    loss_chunk: int = 1024          # CE computed per sequence chunk
    z_loss: float = 1e-4
    ep_axis: Optional[str] = None   # mesh axis for MoE expert parallelism
    sp_axis: Optional[str] = None   # mesh axis for sequence sharding hints

    # ------------------------------------------------------------------
    @property
    def param_dtype(self):
        """The parameter dtype as a jnp dtype object."""
        return jnp.dtype(self.dtype)

    @property
    def n_groups(self) -> int:
        """Number of scanned layer groups (body layers / group size)."""
        body = self.n_layers - len(self.first_kinds)
        assert body % len(self.layer_kinds) == 0, \
            f"{body} layers not divisible by group {self.layer_kinds}"
        return body // len(self.layer_kinds)

    def attention_config(self) -> A.AttentionConfig:
        """The per-layer attention view of this model config."""
        return A.AttentionConfig(
            d_model=self.d_model, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            mechanism=self.mechanism, causal=self.causal,
            prefix_len=self.prefix_len, sliding_window=self.sliding_window,
            qk_norm=self.qk_norm, rope_theta=self.rope_theta,
            use_rope=self.use_rope, block_q=self.block_q,
            block_k=self.block_k, k_frac=self.k_frac,
            quant_bits=self.quant_bits, sla2_impl=self.sla2_impl,
            n_q_blocks=max(1, self.max_target_len // self.block_q),
            paged_impl=self.paged_impl,
            decode_quant_bits=self.decode_quant_bits,
            kv_quant=self.kv_quant, mesh=self.mesh)

    def sla2_config(self):
        """The core SLA2 config view, with the model-level chunking and
        branch-fusion knobs applied."""
        cfg = self.attention_config().sla2_config()
        return dataclasses.replace(cfg, q_chunk=self.q_chunk,
                                   fuse_branches=self.fuse_branches)


# ===========================================================================
# init
# ===========================================================================

def _init_layer(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 4)
    d, dt = cfg.d_model, cfg.param_dtype
    p: dict[str, Any] = {"ln1": L.init_rmsnorm(d, dt)}
    if kind in ("dense", "moe"):
        p["attn"] = A.init_attention(ks[0], cfg.attention_config(), dt)
    elif kind in ("mla_dense", "mla_moe"):
        p["mla"] = MLA.init_mla(
            ks[0], d, cfg.num_heads, cfg.mla, mechanism=cfg.mechanism,
            sla2_cfg=cfg.sla2_config(),
            n_q_blocks=max(1, cfg.max_target_len // cfg.block_q), dtype=dt)
    elif kind == "hybrid":
        p["mixer"] = HY.init_hybrid(ks[0], cfg.attention_config(), cfg.ssm, dt)
    elif kind == "mlstm":
        p["core"] = SSM.init_mlstm(ks[0], d, cfg.ssm, dt)
        return p
    elif kind == "slstm":
        p["core"] = SSM.init_slstm(ks[0], d, cfg.ssm, dt)
        return p
    else:
        raise ValueError(kind)
    p["ln2"] = L.init_rmsnorm(d, dt)
    if kind.endswith("moe"):
        p["moe"] = MOE.init_moe(ks[1], d, cfg.moe, dt)
    else:
        p["mlp"] = L.init_mlp(ks[1], d, cfg.d_ff, gated=cfg.mlp_gated,
                              dtype=dt)
    return p


def _init_group(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, len(cfg.layer_kinds))
    return {f"l{i}": _init_layer(ks[i], cfg, kind)
            for i, kind in enumerate(cfg.layer_kinds)}


def init_model(key, cfg: ModelConfig) -> dict:
    """Initialise the full parameter pytree: embeddings, prefix layers,
    the stacked scan groups, and the final norm / untied head."""
    k_e, k_f, k_g, k_h = jax.random.split(key, 4)
    dt = cfg.param_dtype
    params: dict[str, Any] = {
        "embed": {"table": L.truncated_normal(
            k_e, (cfg.vocab_size, cfg.d_model), dt, 1.0)},
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.truncated_normal(
            k_h, (cfg.d_model, cfg.vocab_size), dt, cfg.d_model ** -0.5)
    if cfg.first_kinds:
        fks = jax.random.split(k_f, len(cfg.first_kinds))
        params["prefix_layers"] = [
            _init_layer(fks[i], cfg, kind)
            for i, kind in enumerate(cfg.first_kinds)]
    gks = jax.random.split(k_g, cfg.n_groups)
    params["groups"] = jax.vmap(
        functools.partial(_init_group, cfg=cfg))(gks)
    return params


# ===========================================================================
# forward
# ===========================================================================

def _layer_forward(lp: dict, cfg: ModelConfig, kind: str, x, positions):
    """One block. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(lp["ln1"], x)
    if kind in ("dense", "moe"):
        x = x + A.attention_forward(lp["attn"], cfg.attention_config(), h,
                                    positions)
    elif kind in ("mla_dense", "mla_moe"):
        x = x + MLA.mla_forward(
            lp["mla"], h, positions, mcfg=cfg.mla, num_heads=cfg.num_heads,
            mechanism=cfg.mechanism, sla2_cfg=cfg.sla2_config())
    elif kind == "hybrid":
        x = x + HY.hybrid_forward(lp["mixer"], cfg.attention_config(),
                                  cfg.ssm, h, positions)
    elif kind == "mlstm":
        y, _ = SSM.mlstm_forward(lp["core"], h, cfg.ssm)
        return x + y, aux
    elif kind == "slstm":
        y, _ = SSM.slstm_forward(lp["core"], h, cfg.ssm)
        return x + y, aux
    h2 = L.rmsnorm(lp["ln2"], x)
    if kind.endswith("moe"):
        y, aux = MOE.moe_ffn(lp["moe"], h2, cfg.moe, ep_axis=cfg.ep_axis)
        x = x + y
    else:
        x = x + L.mlp(lp["mlp"], h2, activation=cfg.mlp_activation)
    return x, aux


def _group_forward(gp: dict, cfg: ModelConfig, x, positions):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.layer_kinds):
        x, a = _layer_forward(gp[f"l{i}"], cfg, kind, x, positions)
        aux = aux + a
    return x, aux


def _sp_constraint(cfg: ModelConfig, x):
    """Sequence-parallel residual-stream hint between blocks."""
    if cfg.sp_axis is None:
        return x
    spec = jax.sharding.PartitionSpec(None, cfg.sp_axis, None)
    return jax.lax.with_sharding_constraint(x, spec)


def forward(params: dict, cfg: ModelConfig, tokens=None, *,
            inputs_embeds=None, positions=None):
    """Full-sequence forward. Returns (hidden (B,N,d) pre-unembed, aux)."""
    if inputs_embeds is None:
        x = L.embed(params["embed"], tokens).astype(cfg.param_dtype)
    else:
        x = inputs_embeds.astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    b, n, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    aux = jnp.zeros((), jnp.float32)

    for i, kind in enumerate(cfg.first_kinds):
        x, a = _layer_forward(params["prefix_layers"][i], cfg, kind, x,
                              positions)
        aux = aux + a

    def body(carry, gp):
        x, aux = carry
        x = _sp_constraint(cfg, x)
        x, a = _group_forward(gp, cfg, x, positions)
        return (x, aux + a), None

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    (x, aux), _ = maps.scan(body, (x, aux), params["groups"])
    x = L.rmsnorm(params["final_norm"], x)
    return x, aux


def logits_from_hidden(params: dict, cfg: ModelConfig, hidden):
    """Unembed hidden states to vocab logits (tied or untied head)."""
    with jax.named_scope("lm.head"):
        if cfg.tie_embeddings:
            return L.unembed(params["embed"], hidden)
        return (hidden.astype(jnp.float32)
                @ params["lm_head"].astype(jnp.float32))


# ===========================================================================
# loss
# ===========================================================================

def lm_loss(params: dict, cfg: ModelConfig, batch: dict):
    """Next-token CE. batch: tokens (B, N) int32, labels (B, N) int32 with
    -1 = ignore. Returns (loss, metrics)."""
    hidden, aux = forward(params, cfg, batch["tokens"],
                          inputs_embeds=batch.get("inputs_embeds"))
    labels = batch["labels"]
    b, n, d = hidden.shape
    c = min(cfg.loss_chunk, n)
    pad = (-n) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (n + pad) // c
    hs = hidden.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, nc, c).transpose(1, 0, 2)

    def chunk_loss(args):
        h, lab = args
        lg = logits_from_hidden(params, cfg, h)             # (B, c, V) fp32
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(
            lg, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        valid = (lab >= 0).astype(jnp.float32)
        ce = (lse - tgt) * valid
        zl = cfg.z_loss * (lse ** 2) * valid
        return ((ce + zl).sum(), valid.sum())

    f = jax.checkpoint(chunk_loss) if cfg.remat == "full" else chunk_loss
    sums, counts = maps.chunk_map(f, (hs, ls))
    n_valid = jnp.maximum(counts.sum(), 1.0)
    loss = sums.sum() / n_valid + aux
    return loss, {"ce": sums.sum() / n_valid, "aux": aux,
                  "tokens": n_valid}


# ===========================================================================
# caches / prefill / decode
# ===========================================================================

def _init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    if kind in ("dense", "moe"):
        return {"attn": A.init_cache(cfg.attention_config(), batch, max_len,
                                     dtype)}
    if kind in ("mla_dense", "mla_moe"):
        return {"mla": MLA.init_mla_cache(cfg.mla, cfg.num_heads, batch,
                                          max_len, cfg.block_k, dtype)}
    if kind == "hybrid":
        return {"mixer": HY.init_hybrid_cache(cfg.attention_config(),
                                              cfg.ssm, batch, max_len, dtype)}
    if kind == "mlstm":
        return {"core": SSM.mlstm_init_state(cfg.ssm, batch)}
    if kind == "slstm":
        return {"core": SSM.slstm_init_state(cfg.ssm, batch)}
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> dict:
    """Static (non-paged) decode caches for every layer, mirroring the
    param layout (prefix layers unrolled, groups stacked for scan)."""
    caches: dict[str, Any] = {}
    if cfg.first_kinds:
        caches["prefix_layers"] = [
            _init_layer_cache(cfg, kind, batch, max_len, dtype)
            for kind in cfg.first_kinds]
    one = {f"l{i}": _init_layer_cache(cfg, kind, batch, max_len, dtype)
           for i, kind in enumerate(cfg.layer_kinds)}
    caches["groups"] = jax.tree.map(
        lambda a: jnp.tile(a[None], (cfg.n_groups,) + (1,) * a.ndim), one)
    return caches


def _layer_prefill(lp, cfg: ModelConfig, kind, x, lc, positions):
    h = L.rmsnorm(lp["ln1"], x)
    if kind in ("dense", "moe"):
        y, c = A.prefill_cache(lp["attn"], cfg.attention_config(), h,
                               lc["attn"])
        x = x + y
        lc = {"attn": c}
    elif kind in ("mla_dense", "mla_moe"):
        y, c = MLA.mla_prefill(lp["mla"], h, positions, lc["mla"],
                               mcfg=cfg.mla, num_heads=cfg.num_heads,
                               mechanism=cfg.mechanism,
                               sla2_cfg=cfg.sla2_config())
        x = x + y
        lc = {"mla": c}
    elif kind == "hybrid":
        y, c = HY.hybrid_prefill(lp["mixer"], cfg.attention_config(),
                                 cfg.ssm, h, lc["mixer"], positions)
        x = x + y
        lc = {"mixer": c}
    elif kind == "mlstm":
        y, st = SSM.mlstm_forward(lp["core"], h, cfg.ssm)
        return x + y, {"core": st}
    elif kind == "slstm":
        y, st = SSM.slstm_forward(lp["core"], h, cfg.ssm)
        return x + y, {"core": st}
    h2 = L.rmsnorm(lp["ln2"], x)
    if kind.endswith("moe"):
        y, _ = MOE.moe_layer(lp["moe"], h2, cfg.moe)
        x = x + y
    else:
        x = x + L.mlp(lp["mlp"], h2, activation=cfg.mlp_activation)
    return x, lc


def _layer_decode(lp, cfg: ModelConfig, kind, x_t, lc):
    h = L.rmsnorm(lp["ln1"], x_t)
    if kind in ("dense", "moe"):
        y, c = A.decode_step(lp["attn"], cfg.attention_config(), h,
                             lc["attn"])
        x_t = x_t + y
        lc = {"attn": c}
    elif kind in ("mla_dense", "mla_moe"):
        y, c = MLA.mla_decode_step(lp["mla"], h, lc["mla"], mcfg=cfg.mla,
                                   num_heads=cfg.num_heads,
                                   k_frac=cfg.k_frac, block_k=cfg.block_k)
        x_t = x_t + y
        lc = {"mla": c}
    elif kind == "hybrid":
        y, c = HY.hybrid_decode_step(lp["mixer"], cfg.attention_config(),
                                     cfg.ssm, h, lc["mixer"])
        x_t = x_t + y
        lc = {"mixer": c}
    elif kind == "mlstm":
        y, st = SSM.mlstm_decode_step(lp["core"], h, cfg.ssm, lc["core"])
        return x_t + y, {"core": st}
    elif kind == "slstm":
        y, st = SSM.slstm_decode_step(lp["core"], h, cfg.ssm, lc["core"])
        return x_t + y, {"core": st}
    h2 = L.rmsnorm(lp["ln2"], x_t)
    if kind.endswith("moe"):
        y, _ = MOE.moe_layer(lp["moe"], h2, cfg.moe)
        x_t = x_t + y
    else:
        x_t = x_t + L.mlp(lp["mlp"], h2, activation=cfg.mlp_activation)
    return x_t, lc


# ---------------------------------------------------------------------------
# Paged caches / chunked prefill / per-slot decode (continuous batching)
# ---------------------------------------------------------------------------

# Every layer kind serves through the paged engine; what differs is the
# SHAPE of its per-layer cache, summarized by LAYER_CACHE_KINDS:
#   paged-kv      — block_k-token K/V pages (+ SLA2 pooled keys / totals)
#   paged-latent  — MLA's compressed-latent pages (no v_pages; values are
#                   the c_kv slice of the latent)
#   state         — recurrent mixers: a degenerate "pool" of one per-slot
#                   state checkpoint, no page keys at all
#   paged-kv + state — hybrid blocks compose both, as a nested dict
# tools/gen_path_matrix.py renders this table into docs/paths.md, so the
# documented layer_kind column cannot drift from the dispatch below.
PAGED_KINDS = ("dense", "moe", "mla_dense", "mla_moe", "hybrid",
               "mlstm", "slstm")
LAYER_CACHE_KINDS = {
    "dense": "paged-kv", "moe": "paged-kv",
    "mla_dense": "paged-latent", "mla_moe": "paged-latent",
    "hybrid": "paged-kv + state", "mlstm": "state", "slstm": "state",
}
# layer kind -> the single key its params/caches live under
KIND_CACHE_KEY = {"dense": "attn", "moe": "attn", "mla_dense": "mla",
                  "mla_moe": "mla", "hybrid": "mixer", "mlstm": "core",
                  "slstm": "core"}
# kinds whose cache carries per-slot state beyond K/V pages (the engine's
# prefix cache must snapshot/restore it on hits)
_STATE_KINDS = ("mla_dense", "mla_moe", "hybrid", "mlstm", "slstm")


def supports_paged(cfg: ModelConfig) -> bool:
    """Paged serving covers every layer kind: attention pages K/V, MLA
    pages the compressed latent, recurrent mixers checkpoint per-slot
    state, hybrids compose both."""
    return all(k in PAGED_KINDS
               for k in tuple(cfg.first_kinds) + tuple(cfg.layer_kinds))


def has_slot_state(cfg: ModelConfig) -> bool:
    """True when any layer keeps per-slot state the serving prefix cache
    must snapshot on insert and restore on hit — SLA2 linear totals
    (mechanism 'sla2', incl. MLA) or recurrent-mixer checkpoints."""
    kinds = tuple(cfg.first_kinds) + tuple(cfg.layer_kinds)
    return cfg.mechanism == "sla2" or any(k in _STATE_KINDS for k in kinds)


def _init_layer_paged(cfg: ModelConfig, kind: str, batch: int,
                      num_pages: int, window: int, dtype) -> dict:
    """One layer's paged cache, dispatched on the layer kind."""
    if kind in ("dense", "moe"):
        return {"attn": A.init_paged_cache(cfg.attention_config(),
                                           num_pages, batch, dtype)}
    if kind in ("mla_dense", "mla_moe"):
        return {"mla": MLA.init_mla_paged_cache(
            cfg.mla, num_pages, batch, cfg.block_k, kv_quant=cfg.kv_quant,
            dtype=dtype)}
    if kind == "hybrid":
        return {"mixer": HY.init_hybrid_paged_cache(
            cfg.attention_config(), cfg.ssm, num_pages, batch,
            window=window, dtype=dtype)}
    if kind in ("mlstm", "slstm"):
        return {"core": SSM.init_paged_state(kind, cfg.ssm, batch, window)}
    raise ValueError(kind)


def init_paged_caches(cfg: ModelConfig, batch: int, num_pages: int, *,
                      window: int = 1, dtype=jnp.bfloat16) -> dict:
    """Per-layer paged caches sharing one page table (kept by the engine);
    page size == cfg.block_k.  ``window`` sizes the recurrent mixers'
    speculative-verify state buffers (draft window W; 1 when the engine
    never verifies multi-token windows)."""
    if not supports_paged(cfg):
        raise ValueError(f"paged serving unsupported for {cfg.layer_kinds}")
    caches: dict[str, Any] = {}
    if cfg.first_kinds:
        caches["prefix_layers"] = [
            _init_layer_paged(cfg, kind, batch, num_pages, window, dtype)
            for kind in cfg.first_kinds]
    one = {f"l{i}": _init_layer_paged(cfg, kind, batch, num_pages, window,
                                      dtype)
           for i, kind in enumerate(cfg.layer_kinds)}
    caches["groups"] = jax.tree.map(
        lambda a: jnp.tile(a[None], (cfg.n_groups,) + (1,) * a.ndim), one)
    return caches


def _walk_layers(cfg: ModelConfig, caches: dict, fn) -> dict:
    """Apply ``fn(kind, layer_cache, lead)`` over every layer cache (prefix
    layers at lead=0, scanned groups at lead=1), preserving the layout."""
    out: dict[str, Any] = {}
    if cfg.first_kinds:
        out["prefix_layers"] = [
            fn(kind, lc, 0)
            for kind, lc in zip(cfg.first_kinds, caches["prefix_layers"])]
    out["groups"] = {
        f"l{i}": fn(kind, caches["groups"][f"l{i}"], 1)
        for i, kind in enumerate(cfg.layer_kinds)}
    return out


def swap_out_slot(cfg: ModelConfig, caches: dict, page_row, slot) -> dict:
    """Extract one slot's full paged state across every layer: its pages
    (K/V or latent) at ``page_row`` and its per-slot states (SLA2 linear
    totals / recurrent checkpoints) at ``slot``.  The result pytree is
    what the serving SwapPool keeps on the host."""
    def f(kind, lc, lead):
        key = KIND_CACHE_KEY[kind]
        if kind == "hybrid":
            return {key: {
                "attn": A.extract_paged_state(lc[key]["attn"], page_row,
                                              slot, lead),
                "ssm": A.extract_slot_state(lc[key]["ssm"], slot, lead)}}
        return {key: A.extract_paged_state(lc[key], page_row, slot, lead)}
    return _walk_layers(cfg, caches, f)


def swap_in_slot(cfg: ModelConfig, caches: dict, page_row, slot,
                 state: dict) -> dict:
    """Write a swapped-out slot state back into the pools at a fresh page
    row / slot id (the physical placement may differ from swap-out)."""
    def f(kind, pair, lead):
        lc, st = pair
        key = KIND_CACHE_KEY[kind]
        if key not in st:
            raise ValueError(
                f"swap state for layer kind {kind!r} must carry {key!r} "
                f"leaves, got {sorted(st)} — state extracted from a "
                "different layer kind?")
        if kind == "hybrid":
            return {key: {
                "attn": A.insert_paged_state(lc[key]["attn"], page_row,
                                             slot, st[key]["attn"], lead),
                "ssm": A.insert_slot_state(lc[key]["ssm"], slot,
                                           st[key]["ssm"], lead)}}
        return {key: A.insert_paged_state(lc[key], page_row, slot, st[key],
                                          lead)}
    new = dict(caches)
    paired = _walk_layers(cfg, _zip_layouts(cfg, caches, state), f)
    new.update(paired)
    return new


def _zip_layouts(cfg: ModelConfig, a: dict, b: dict) -> dict:
    """Pair two cache-layout pytrees layer-by-layer for _walk_layers."""
    out: dict[str, Any] = {}
    if cfg.first_kinds:
        out["prefix_layers"] = list(zip(a["prefix_layers"],
                                        b["prefix_layers"]))
    out["groups"] = {k: (a["groups"][k], b["groups"][k])
                     for k in a["groups"]}
    return out


def extract_linear_totals(cfg: ModelConfig, caches: dict, slot) -> dict:
    """Extract every layer's per-slot state for one slot — SLA2 linear
    totals (h_tot, z_tot) and/or recurrent-mixer checkpoints — the
    snapshot a prefix-cache trie node stores so a hit restores the slot
    without re-prefilling.  Layers without per-slot state contribute empty
    dicts (dense non-sla2 models)."""
    def f(kind, lc, lead):
        key = KIND_CACHE_KEY[kind]
        if kind == "hybrid":
            return {key: {
                "attn": A.extract_slot_state(lc[key]["attn"], slot, lead),
                "ssm": A.extract_slot_state(lc[key]["ssm"], slot, lead)}}
        return {key: A.extract_slot_state(lc[key], slot, lead)}
    return _walk_layers(cfg, caches, f)


def insert_linear_totals(cfg: ModelConfig, caches: dict, slot,
                         totals: dict) -> dict:
    """Write an ``extract_linear_totals`` snapshot back into every layer at
    ``slot`` — the O(1) restore a prefix-cache hit performs before chunked
    prefill resumes at the first uncached page."""
    def f(kind, pair, lead):
        lc, st = pair
        key = KIND_CACHE_KEY[kind]
        if key not in st:
            raise ValueError(
                f"slot totals for layer kind {kind!r} must carry {key!r} "
                f"leaves, got {sorted(st)} — snapshot taken from a "
                "different layer kind?")
        if kind == "hybrid":
            return {key: {
                "attn": A.insert_slot_state(lc[key]["attn"], slot,
                                            st[key]["attn"], lead),
                "ssm": A.insert_slot_state(lc[key]["ssm"], slot,
                                           st[key]["ssm"], lead)}}
        return {key: A.insert_slot_state(lc[key], slot, st[key], lead)}
    new = dict(caches)
    new.update(_walk_layers(cfg, _zip_layouts(cfg, caches, totals), f))
    return new


def copy_kv_page(cfg: ModelConfig, caches: dict, src, dst) -> dict:
    """Copy one physical page (K/V or latent + pooled router key) onto
    another across every layer — the serving engine's copy-on-write
    primitive for pages shared through the prefix cache.  State-only
    layer caches have no page keys and pass through unchanged."""
    def f(kind, lc, lead):
        key = KIND_CACHE_KEY[kind]
        if kind == "hybrid":
            return {key: {
                "attn": A.copy_paged_page(lc[key]["attn"], src, dst, lead),
                "ssm": lc[key]["ssm"]}}
        return {key: A.copy_paged_page(lc[key], src, dst, lead)}
    new = dict(caches)
    new.update(_walk_layers(cfg, caches, f))
    return new


# layer kinds whose paged mixers read and write a scanned group's stacked
# pool in place at the layer index (models/mla.py's pool accessors); the
# other kinds' mixers hand one layer's pool to Pallas kernels through
# BlockSpecs, so ``_mix_stacked`` slices it out for them
_IN_PLACE_KINDS = ("mla_dense", "mla_moe")


def _mix_stacked(mix_fn, kind, lp, h, cache, layer):
    """The mixer, under its ``lm.attn`` scope.  ``cache`` is a layer's own
    cache when ``layer`` is None, else a scanned group's stacked cache and
    ``layer`` the scan's index: an in-place kind indexes the stack itself;
    any other kind gets its layer's slice, and the slice it returns is
    written back into the stack, both outside the scope: they are the
    scan's data movement, not the mixer's."""
    if layer is not None and kind not in _IN_PLACE_KINDS:
        sub = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            cache)
        with jax.named_scope("lm.attn"):
            y, sub = mix_fn(kind, lp, h, sub, None)
        return y, jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, layer, 0),
            cache, sub)
    with jax.named_scope("lm.attn"):
        return mix_fn(kind, lp, h, cache, layer)


def _layer_paged(lp, cfg: ModelConfig, kind, x, lc, mix_fn, rows,
                 stack=None, layer=None):
    """Shared block body around a paged mixer call, dispatched on the layer
    kind; recurrent-core kinds (mlstm/slstm) have no ln2/FFN half.  On a
    serving mesh the residual stream stays whole on every device
    (distributed/shard_paged.replicate).  Returns (x, cache, counters):
    the expert layer's counters (``MOE.moe_layer``), None for the other
    kinds.  ``rows`` (B, S) bool marks the real tokens the experts route.
    For a layer of the scanned groups, ``layer`` is its index, ``lc`` the
    group's stacked cache (``_mix_stacked``) and ``stack`` the expert
    layer's whole-stack weights and index (``MOE.moe_layer``)."""
    from repro.distributed.shard_paged import replicate
    x = replicate(x, cfg.mesh)
    with jax.named_scope("lm.norm"):
        h = L.rmsnorm(lp["ln1"], x)
    key = KIND_CACHE_KEY[kind]
    y, c = _mix_stacked(mix_fn, kind, lp, h, lc[key], layer)
    x = replicate(x + y, cfg.mesh)
    if kind in ("mlstm", "slstm"):
        return x, {key: c}, None
    with jax.named_scope("lm.norm"):
        h2 = L.rmsnorm(lp["ln2"], x)
    counters = None
    with jax.named_scope("lm.mlp"):
        if kind.endswith("moe"):
            y2, counters = MOE.moe_layer(lp["moe"], h2, cfg.moe, rows,
                                         stack)
            x = x + y2
        else:
            x = x + L.mlp(lp["mlp"], h2, activation=cfg.mlp_activation)
    return x, {key: c}, counters


def _add_counters(total, counters):
    if counters is None:
        return total
    if total is None:
        return counters
    return jax.tree.map(jnp.add, total, counters)


def _paged_stack(params, cfg: ModelConfig, x, caches, mix_fn, rows=None):
    """Run the layer stack (prefix layers + scanned groups) with ``mix_fn``
    (kind, layer_params, h, sub_cache, layer) -> (y, sub_cache) as the
    mixer body, where ``layer`` is None for a layer's own cache and the
    scan's layer index for a group's stacked one (``_mix_stacked``);
    returns (final hidden, new caches, counters).  ``counters`` sums
    the expert layers' counters over the stack (None for a stack without
    expert layers, whose programs gain no outputs)."""
    caches = dict(caches)
    total = None
    if cfg.first_kinds:
        new_pref = []
        for i, kind in enumerate(cfg.first_kinds):
            x, lc, n = _layer_paged(params["prefix_layers"][i], cfg, kind,
                                    x, caches["prefix_layers"][i], mix_fn,
                                    rows)
            new_pref.append(lc)
            total = _add_counters(total, n)
        caches["prefix_layers"] = new_pref

    # the scan slices each group's parameters but for what an expert layer
    # reads out of the whole stack itself (MOE.split_stack)
    groups, whole = dict(params["groups"]), {}
    for i, kind in enumerate(cfg.layer_kinds):
        if kind.endswith("moe"):
            lp = groups[f"l{i}"]
            moe, whole[f"l{i}"] = MOE.split_stack(lp["moe"])
            groups[f"l{i}"] = {**lp, "moe": moe}

    def body(carry, pair):
        x, pool = carry
        gp, g = pair
        pool, group = dict(pool), None
        for i, kind in enumerate(cfg.layer_kinds):
            stack = (whole[f"l{i}"], g) if f"l{i}" in whole else None
            x, pool[f"l{i}"], n = _layer_paged(
                gp[f"l{i}"], cfg, kind, x, pool[f"l{i}"], mix_fn, rows,
                stack, layer=g)
            group = _add_counters(group, n)
        return (x, pool), group

    # the stacked pool rides the carry, so a step holds one pool (a
    # donated one: the engine's).  Each layer's mixer gets the whole stack
    # and the layer index: MLA layers read and write it in place, the
    # other kinds slice their layer out and write it back in
    # ``_mix_stacked``.  Ops of the scan outside the layer body's scopes
    # are the loop's own: slicing each layer's weights, and a non-MLA
    # layer's pool, out of the stack and writing that pool back
    with jax.named_scope("lm.layers"):
        (x, new_groups), per_group = maps.scan(
            body, (x, caches["groups"]),
            (groups, jnp.arange(cfg.n_groups)))
    caches["groups"] = new_groups
    if per_group is not None:
        total = _add_counters(total, jax.tree.map(
            lambda a: a.sum(0), per_group))
    with jax.named_scope("lm.norm"):
        return L.rmsnorm(params["final_norm"], x), caches, total


def _with_counters(logits, counters):
    """A paged program's first output: the logits, or for a stack with
    expert layers the logits with the counters beside them."""
    if counters is None:
        return logits
    return {"logits": logits, **counters}


def prefill_chunk(params: dict, cfg: ModelConfig, tokens, caches, *,
                  page_row, offset, chunk_len, slot):
    """Prefill one chunk of one slot's prompt (tokens (1, C), padded).
    Returns (logits (1, V) at the last valid token, caches); with expert
    layers the logits come as ``{"logits", <counters>}``
    (``_with_counters``)."""
    acfg = cfg.attention_config()
    x = L.embed(params["embed"], tokens).astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)

    def mix_fn(kind, lp, h, lc, layer):
        if kind in ("dense", "moe"):
            return A.chunk_prefill_paged(
                lp["attn"], acfg, h, lc, page_row=page_row, offset=offset,
                chunk_len=chunk_len, slot=slot)
        if kind in ("mla_dense", "mla_moe"):
            return MLA.mla_prefill_chunk_paged(
                lp["mla"], h, lc, mcfg=cfg.mla, num_heads=cfg.num_heads,
                block_k=cfg.block_k, kv_quant=cfg.kv_quant,
                page_row=page_row, offset=offset, chunk_len=chunk_len,
                slot=slot, layer=layer)
        if kind == "hybrid":
            return HY.hybrid_prefill_chunk_paged(
                lp["mixer"], acfg, cfg.ssm, h, lc, page_row=page_row,
                offset=offset, chunk_len=chunk_len, slot=slot)
        return SSM.ssm_prefill_paged(kind, lp["core"], cfg.ssm, h, lc,
                                     offset=offset, chunk_len=chunk_len,
                                     slot=slot)

    rows = (jnp.arange(tokens.shape[1]) < chunk_len)[None]
    x, caches, counters = _paged_stack(params, cfg, x, caches, mix_fn, rows)
    last = jax.lax.dynamic_slice(x, (0, chunk_len - 1, 0),
                                 (1, 1, x.shape[-1]))
    return _with_counters(logits_from_hidden(params, cfg, last)[:, 0],
                          counters), caches


def decode_paged(params: dict, cfg: ModelConfig, token_t, caches, *,
                 page_table, lengths, active):
    """One decode step for the whole slot batch with per-slot offsets.
    token_t: (B,) int32; lengths: (B,) tokens already cached per slot;
    active: (B,) bool.  Returns (logits (B, V), caches), the logits with
    the expert counters beside them as in ``prefill_chunk``."""
    acfg = cfg.attention_config()
    x = L.embed(params["embed"], token_t[:, None]).astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)

    def mix_fn(kind, lp, h, lc, layer):
        if kind in ("dense", "moe"):
            return A.decode_step_paged(lp["attn"], acfg, h, lc,
                                       page_table=page_table,
                                       lengths=lengths, active=active)
        if kind in ("mla_dense", "mla_moe"):
            return MLA.mla_decode_step_paged(
                lp["mla"], h, lc, mcfg=cfg.mla, num_heads=cfg.num_heads,
                k_frac=cfg.k_frac, block_k=cfg.block_k,
                kv_quant=cfg.kv_quant, page_table=page_table,
                lengths=lengths, active=active, layer=layer)
        if kind == "hybrid":
            return HY.hybrid_decode_step_paged(
                lp["mixer"], acfg, cfg.ssm, h, lc, page_table=page_table,
                lengths=lengths, active=active)
        return SSM.ssm_decode_paged(kind, lp["core"], cfg.ssm, h, lc,
                                    active=active)

    x, caches, counters = _paged_stack(params, cfg, x, caches, mix_fn,
                                       active[:, None])
    return _with_counters(logits_from_hidden(params, cfg, x)[:, 0],
                          counters), caches


def decode_verify(params: dict, cfg: ModelConfig, tokens_w, caches, *,
                  page_table, lengths, active, window_len):
    """Speculative verify: decode a W-token window for the whole slot batch
    in ONE pass.  tokens_w: (B, W) int32 — row 0 is the last accepted
    token, rows 1.. the draft; window_len: (B,) valid rows per slot.
    Returns (logits (B, W, V), caches), with counters as in
    ``prefill_chunk``.  K/V (or latent) pages are written
    for the whole window; block-state and recurrent-checkpoint commits are
    deferred to ``commit_window`` once host-side acceptance is decided."""
    acfg = cfg.attention_config()
    x = L.embed(params["embed"], tokens_w).astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)

    def mix_fn(kind, lp, h, lc, layer):
        if kind in ("dense", "moe"):
            return A.decode_window_paged(lp["attn"], acfg, h, lc,
                                         page_table=page_table,
                                         lengths=lengths, active=active,
                                         window_len=window_len)
        if kind in ("mla_dense", "mla_moe"):
            return MLA.mla_decode_window_paged(
                lp["mla"], h, lc, mcfg=cfg.mla, num_heads=cfg.num_heads,
                k_frac=cfg.k_frac, block_k=cfg.block_k,
                kv_quant=cfg.kv_quant, page_table=page_table,
                lengths=lengths, active=active, window_len=window_len,
                layer=layer)
        if kind == "hybrid":
            return HY.hybrid_decode_window_paged(
                lp["mixer"], acfg, cfg.ssm, h, lc, page_table=page_table,
                lengths=lengths, active=active, window_len=window_len)
        return SSM.ssm_decode_window_paged(kind, lp["core"], cfg.ssm, h,
                                           lc, active=active,
                                           window_len=window_len)

    rows = (jnp.arange(tokens_w.shape[1])[None] < window_len[:, None]) \
        & active[:, None]
    x, caches, counters = _paged_stack(params, cfg, x, caches, mix_fn, rows)
    return _with_counters(logits_from_hidden(params, cfg, x), counters), \
        caches


def commit_window(cfg: ModelConfig, caches, page_table, lengths, accepted,
                  active, window: int):
    """Commit the accepted prefix of a verify window into every layer's
    block state — SLA2 pooled router keys + linear totals for attention /
    MLA layers, accepted-state promotion for recurrent mixers.  ``window``
    is the static window size the verify ran with."""
    acfg = cfg.attention_config()

    def upd(kind, lc):
        key = KIND_CACHE_KEY[kind]
        if kind in ("dense", "moe"):
            return {key: A.commit_paged_window(
                acfg, lc[key], page_table=page_table, lengths=lengths,
                accepted=accepted, active=active, window=window)}
        if kind in ("mla_dense", "mla_moe"):
            return {key: MLA.mla_commit_window(
                lc[key], mcfg=cfg.mla, block_k=cfg.block_k,
                kv_quant=cfg.kv_quant, page_table=page_table,
                lengths=lengths, accepted=accepted, active=active,
                window=window)}
        if kind == "hybrid":
            return {key: HY.hybrid_commit_window(
                acfg, cfg.ssm, lc[key], page_table=page_table,
                lengths=lengths, accepted=accepted, active=active,
                window=window)}
        return {key: SSM.ssm_commit_window(
            kind, cfg.ssm, lc[key], accepted=accepted, active=active,
            window=window)}

    caches = dict(caches)
    if cfg.first_kinds:
        caches["prefix_layers"] = [
            upd(kind, lc) for kind, lc in zip(cfg.first_kinds,
                                              caches["prefix_layers"])]
    caches["groups"] = {
        f"l{i}": jax.vmap(functools.partial(upd, kind))(
            caches["groups"][f"l{i}"])
        for i, kind in enumerate(cfg.layer_kinds)}
    return caches


def draft_init(cfg: ModelConfig, caches, page_table, lengths, active):
    """Per-layer linear draft states (running phi(k)·v totals over the full
    cached prefix) for the speculative drafter — one {"h", "z"} pytree per
    attention layer, mirroring the cache layout.  Attention-only stacks
    (dense/moe kinds): the linear drafter has no analogue for MLA latents
    or recurrent checkpoints, so api.py only wires it up for those."""
    acfg = cfg.attention_config()

    def f(lc):
        return {"attn": A.linear_draft_state(
            acfg, lc["attn"], page_table=page_table, lengths=lengths,
            active=active)}

    st: dict[str, Any] = {}
    if cfg.first_kinds:
        st["prefix_layers"] = [f(lc) for lc in caches["prefix_layers"]]
    st["groups"] = {k: jax.vmap(f)(v) for k, v in caches["groups"].items()}
    return st


def draft_step(params: dict, cfg: ModelConfig, token_t, states, *,
               positions, active):
    """One linear-only draft decode step (no page reads — O(d^2)/token).
    token_t: (B,) int32; positions: (B,) the draft token's position.
    Returns (logits (B, V), states)."""
    acfg = cfg.attention_config()
    x = L.embed(params["embed"], token_t[:, None]).astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)

    def mix_fn(kind, lp, h, lc, layer):
        return A.linear_draft_attention(lp["attn"], acfg, h, lc,
                                        positions=positions, active=active)

    x, states, _ = _paged_stack(params, cfg, x, states, mix_fn,
                                active[:, None])
    return logits_from_hidden(params, cfg, x)[:, 0], states


def prefill(params: dict, cfg: ModelConfig, tokens, caches, *,
            inputs_embeds=None):
    """Run the prompt through the model, filling every cache.
    Returns (logits_last (B, V), caches)."""
    if inputs_embeds is None:
        x = L.embed(params["embed"], tokens).astype(cfg.param_dtype)
    else:
        x = inputs_embeds.astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    b, n, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    caches = dict(caches)

    if cfg.first_kinds:
        new_pref = []
        for i, kind in enumerate(cfg.first_kinds):
            x, lc = _layer_prefill(params["prefix_layers"][i], cfg, kind, x,
                                   caches["prefix_layers"][i], positions)
            new_pref.append(lc)
        caches["prefix_layers"] = new_pref

    def body(x, pair):
        gp, gc = pair
        new_gc = {}
        for i, kind in enumerate(cfg.layer_kinds):
            x, lc = _layer_prefill(gp[f"l{i}"], cfg, kind, x, gc[f"l{i}"],
                                   positions)
            new_gc[f"l{i}"] = lc
        return x, new_gc

    x, new_groups = maps.scan(body, x, (params["groups"],
                                        caches["groups"]))
    caches["groups"] = new_groups
    x = L.rmsnorm(params["final_norm"], x)
    logits = logits_from_hidden(params, cfg, x[:, -1:])[:, 0]
    return logits, caches


def decode_step(params: dict, cfg: ModelConfig, token_t, caches):
    """One decode step. token_t: (B,) int32. Returns (logits (B, V), caches)."""
    x = L.embed(params["embed"], token_t[:, None]).astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    caches = dict(caches)

    if cfg.first_kinds:
        new_pref = []
        for i, kind in enumerate(cfg.first_kinds):
            x, lc = _layer_decode(params["prefix_layers"][i], cfg, kind, x,
                                  caches["prefix_layers"][i])
            new_pref.append(lc)
        caches["prefix_layers"] = new_pref

    def body(x, pair):
        gp, gc = pair
        new_gc = {}
        for i, kind in enumerate(cfg.layer_kinds):
            x, lc = _layer_decode(gp[f"l{i}"], cfg, kind, x, gc[f"l{i}"])
            new_gc[f"l{i}"] = lc
        return x, new_gc

    x, new_groups = maps.scan(body, x, (params["groups"],
                                        caches["groups"]))
    caches["groups"] = new_groups
    x = L.rmsnorm(params["final_norm"], x)
    return logits_from_hidden(params, cfg, x)[:, 0], caches
