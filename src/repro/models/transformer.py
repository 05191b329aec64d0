"""The decoder-only transformer substrate for every assigned LM architecture.

One ``ModelConfig`` describes an architecture; layers are laid out as

    [first_kinds...unrolled]  +  scan over n_groups x layer_kinds

so heterogeneous stacks (llama4's alternating dense/MoE, deepseek's leading
dense-FFN layer, xlstm's 7:1 mLSTM:sLSTM pattern) scan over a homogeneous
*group* while keeping the HLO compact (one group body regardless of depth).

A layer kind names a pre-norm block: a token mixer (``Mixer``) and an FFN
(``FFN``, or none).  ``LAYER_KINDS`` is the one table of kinds: each entry
holds every operation the stack asks of its mixer and FFN, from init and
the training forward to the paged serving programs, so the code below
looks a kind up and never branches on its name.

Params are plain nested dicts (stacked on a leading group axis inside
"groups"); sharding is assigned by key-path in distributed/sharding.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

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
    fields.  ``LAYER_KINDS`` holds the layer-kind vocabulary."""
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
# layer kinds: one token mixer and one FFN (or none) each
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class Mixer:
    """Every operation the stack asks of one token mixer.  ``p`` is the
    mixer's own params (``lp[key]``), ``c`` its own cache; the paged ops
    take the program's keyword batch and ``layer``, the scan's layer index
    when ``c`` is a scanned group's stacked cache (``in_place`` mixers
    only; the others get their layer's slice and None)."""
    key: str                # the param / cache key its leaves live under
    cache_kind: str         # paged cache geometry (docs/paths.md)
    slot_state: bool        # per-slot state beyond K/V pages, which the
                            # prefix cache snapshots and restores
    init: Callable          # (rng, cfg) -> params
    forward: Callable       # (cfg, p, h, positions) -> y
    init_cache: Callable    # (cfg, batch, max_len, dtype) -> static cache
    prefill: Callable       # (cfg, p, h, c, positions) -> (y, c)
    decode: Callable        # (cfg, p, h, c) -> (y, c)
    init_paged: Callable    # (cfg, batch, num_pages, window, dtype) -> c
    prefill_chunk: Callable  # (cfg, p, h, c, layer, **batch) -> (y, c)
    decode_paged: Callable  # as prefill_chunk
    decode_verify: Callable  # as prefill_chunk
    commit_window: Callable  # (cfg, c, **batch) -> c
    # a composite cache's sub-caches, each "pages" (K/V pages with their
    # slot totals) or "slot" (slot state alone); None: the whole cache is
    # of the first sort
    parts: Optional[dict] = None
    in_place: bool = False  # reads and writes the stacked cache in place
    # the SLA2 linear drafter (speculative 'linear'), attention only
    draft_init: Optional[Callable] = None   # (cfg, c, **batch) -> state
    draft_step: Optional[Callable] = None   # as prefill_chunk


@dataclasses.dataclass(frozen=True)
class FFN:
    """The block's second half: training runs ``train``, serving
    ``serve``; ``split`` separates what a layer scan slices per layer
    from the weights the FFN reads out of the whole stack."""
    key: str
    init: Callable          # (rng, cfg) -> params
    train: Callable         # (cfg, p, h) -> (y, aux loss or None)
    serve: Callable         # (cfg, p, h, rows, stack) -> (y, counters|None)
    split: Optional[Callable] = None


class Layer(NamedTuple):
    """One layer kind: its token mixer and its FFN (None: no FFN half)."""
    mixer: Mixer
    ffn: Optional[FFN]


_ATTN = Mixer(
    key="attn", cache_kind="paged-kv", slot_state=False,
    init=lambda k, cfg: A.init_attention(k, cfg.attention_config(),
                                         cfg.param_dtype),
    forward=lambda cfg, p, h, pos: A.attention_forward(
        p, cfg.attention_config(), h, pos),
    init_cache=lambda cfg, b, n, dt: A.init_cache(cfg.attention_config(),
                                                  b, n, dt),
    prefill=lambda cfg, p, h, c, pos: A.prefill_cache(
        p, cfg.attention_config(), h, c),
    decode=lambda cfg, p, h, c: A.decode_step(p, cfg.attention_config(), h,
                                              c),
    init_paged=lambda cfg, b, n, w, dt: A.init_paged_cache(
        cfg.attention_config(), n, b, dt),
    prefill_chunk=lambda cfg, p, h, c, layer, **kw: A.chunk_prefill_paged(
        p, cfg.attention_config(), h, c, **kw),
    decode_paged=lambda cfg, p, h, c, layer, **kw: A.decode_step_paged(
        p, cfg.attention_config(), h, c, **kw),
    decode_verify=lambda cfg, p, h, c, layer, **kw: A.decode_window_paged(
        p, cfg.attention_config(), h, c, **kw),
    commit_window=lambda cfg, c, **kw: A.commit_paged_window(
        cfg.attention_config(), c, **kw),
    draft_init=lambda cfg, c, **kw: A.linear_draft_state(
        cfg.attention_config(), c, **kw),
    draft_step=lambda cfg, p, h, c, layer, **kw: A.linear_draft_attention(
        p, cfg.attention_config(), h, c, **kw))


def _mla_seq(cfg):
    """MLA's full-sequence knobs (training forward, static prefill)."""
    return dict(mcfg=cfg.mla, num_heads=cfg.num_heads,
                mechanism=cfg.mechanism, sla2_cfg=cfg.sla2_config())


def _mla_paged(cfg, **kw):
    """MLA's paged knobs; the decode ops add ``k_frac``."""
    return dict(mcfg=cfg.mla, num_heads=cfg.num_heads, block_k=cfg.block_k,
                kv_quant=cfg.kv_quant, **kw)


_MLA = Mixer(
    key="mla", cache_kind="paged-latent", slot_state=True, in_place=True,
    init=lambda k, cfg: MLA.init_mla(
        k, cfg.d_model, cfg.num_heads, cfg.mla, mechanism=cfg.mechanism,
        sla2_cfg=cfg.sla2_config(),
        n_q_blocks=max(1, cfg.max_target_len // cfg.block_q),
        dtype=cfg.param_dtype),
    forward=lambda cfg, p, h, pos: MLA.mla_forward(p, h, pos,
                                                   **_mla_seq(cfg)),
    init_cache=lambda cfg, b, n, dt: MLA.init_mla_cache(
        cfg.mla, cfg.num_heads, b, n, cfg.block_k, dt),
    prefill=lambda cfg, p, h, c, pos: MLA.mla_prefill(p, h, pos, c,
                                                      **_mla_seq(cfg)),
    decode=lambda cfg, p, h, c: MLA.mla_decode_step(
        p, h, c, mcfg=cfg.mla, num_heads=cfg.num_heads, k_frac=cfg.k_frac,
        block_k=cfg.block_k),
    init_paged=lambda cfg, b, n, w, dt: MLA.init_mla_paged_cache(
        cfg.mla, n, b, cfg.block_k, kv_quant=cfg.kv_quant, dtype=dt),
    prefill_chunk=lambda cfg, p, h, c, layer, **kw:
        MLA.mla_prefill_chunk_paged(p, h, c, layer=layer,
                                    **_mla_paged(cfg, **kw)),
    decode_paged=lambda cfg, p, h, c, layer, **kw: MLA.mla_decode_step_paged(
        p, h, c, k_frac=cfg.k_frac, layer=layer, **_mla_paged(cfg, **kw)),
    decode_verify=lambda cfg, p, h, c, layer, **kw:
        MLA.mla_decode_window_paged(p, h, c, k_frac=cfg.k_frac, layer=layer,
                                    **_mla_paged(cfg, **kw)),
    commit_window=lambda cfg, c, **kw: MLA.mla_commit_window(
        c, mcfg=cfg.mla, block_k=cfg.block_k, kv_quant=cfg.kv_quant, **kw))

_HYBRID = Mixer(
    key="mixer", cache_kind="paged-kv + state", slot_state=True,
    parts={"attn": "pages", "ssm": "slot"},
    init=lambda k, cfg: HY.init_hybrid(k, cfg.attention_config(), cfg.ssm,
                                       cfg.param_dtype),
    forward=lambda cfg, p, h, pos: HY.hybrid_forward(
        p, cfg.attention_config(), cfg.ssm, h, pos),
    init_cache=lambda cfg, b, n, dt: HY.init_hybrid_cache(
        cfg.attention_config(), cfg.ssm, b, n, dt),
    prefill=lambda cfg, p, h, c, pos: HY.hybrid_prefill(
        p, cfg.attention_config(), cfg.ssm, h, c, pos),
    decode=lambda cfg, p, h, c: HY.hybrid_decode_step(
        p, cfg.attention_config(), cfg.ssm, h, c),
    init_paged=lambda cfg, b, n, w, dt: HY.init_hybrid_paged_cache(
        cfg.attention_config(), cfg.ssm, n, b, window=w, dtype=dt),
    prefill_chunk=lambda cfg, p, h, c, layer, **kw:
        HY.hybrid_prefill_chunk_paged(p, cfg.attention_config(), cfg.ssm,
                                      h, c, **kw),
    decode_paged=lambda cfg, p, h, c, layer, **kw:
        HY.hybrid_decode_step_paged(p, cfg.attention_config(), cfg.ssm, h,
                                    c, **kw),
    decode_verify=lambda cfg, p, h, c, layer, **kw:
        HY.hybrid_decode_window_paged(p, cfg.attention_config(), cfg.ssm,
                                      h, c, **kw),
    commit_window=lambda cfg, c, **kw: HY.hybrid_commit_window(
        cfg.attention_config(), cfg.ssm, c, **kw))


def _core(name, init, forward, decode, init_state) -> Mixer:
    """An xLSTM block's recurrent core: no pages, one state checkpoint per
    slot (``SSM.init_paged_state``)."""
    return Mixer(
        key="core", cache_kind="state", slot_state=True,
        init=lambda k, cfg: init(k, cfg.d_model, cfg.ssm, cfg.param_dtype),
        forward=lambda cfg, p, h, pos: forward(p, h, cfg.ssm)[0],
        init_cache=lambda cfg, b, n, dt: init_state(cfg.ssm, b),
        prefill=lambda cfg, p, h, c, pos: forward(p, h, cfg.ssm),
        decode=lambda cfg, p, h, c: decode(p, h, cfg.ssm, c),
        init_paged=lambda cfg, b, n, w, dt: SSM.init_paged_state(
            name, cfg.ssm, b, w),
        prefill_chunk=lambda cfg, p, h, c, layer, *, page_row, **kw:
            SSM.ssm_prefill_paged(name, p, cfg.ssm, h, c, **kw),
        decode_paged=lambda cfg, p, h, c, layer, *, active, **_:
            SSM.ssm_decode_paged(name, p, cfg.ssm, h, c, active=active),
        decode_verify=lambda cfg, p, h, c, layer, *, active, window_len,
            **_: SSM.ssm_decode_window_paged(
                name, p, cfg.ssm, h, c, active=active, window_len=window_len),
        commit_window=lambda cfg, c, *, accepted, active, window, **_:
            SSM.ssm_commit_window(name, cfg.ssm, c, accepted=accepted,
                                  active=active, window=window))


_MLP = FFN(
    key="mlp",
    init=lambda k, cfg: L.init_mlp(k, cfg.d_model, cfg.d_ff,
                                   gated=cfg.mlp_gated,
                                   dtype=cfg.param_dtype),
    train=lambda cfg, p, h: (L.mlp(p, h, activation=cfg.mlp_activation),
                             None),
    serve=lambda cfg, p, h, rows=None, stack=None: (
        L.mlp(p, h, activation=cfg.mlp_activation), None))

# training's GShard layer over all experts; serving's dropless layer over
# the held experts, with its counters
_MOE = FFN(
    key="moe",
    init=lambda k, cfg: MOE.init_moe(k, cfg.d_model, cfg.moe,
                                     cfg.param_dtype),
    train=lambda cfg, p, h: MOE.moe_ffn(p, h, cfg.moe, ep_axis=cfg.ep_axis),
    serve=lambda cfg, p, h, rows=None, stack=None: MOE.moe_layer(
        p, h, cfg.moe, rows, stack),
    split=MOE.split_stack)

# Every layer kind serves through the paged engine; tools/gen_path_matrix.py
# renders this table into docs/paths.md.
LAYER_KINDS = {
    "dense": Layer(_ATTN, _MLP),           # attention + gated MLP
    "moe": Layer(_ATTN, _MOE),             # attention + expert FFN
    "mla_dense": Layer(_MLA, _MLP),        # DeepSeek MLA + gated MLP
    "mla_moe": Layer(_MLA, _MOE),          # DeepSeek MLA + expert FFN
    "hybrid": Layer(_HYBRID, _MLP),        # Hymba attention+Mamba + MLP
    "mlstm": Layer(_core("mlstm", SSM.init_mlstm, SSM.mlstm_forward,
                        SSM.mlstm_decode_step, SSM.mlstm_init_state), None),
    "slstm": Layer(_core("slstm", SSM.init_slstm, SSM.slstm_forward,
                        SSM.slstm_decode_step, SSM.slstm_init_state), None),
}


def _kinds(cfg: ModelConfig) -> tuple:
    return tuple(cfg.first_kinds) + tuple(cfg.layer_kinds)


def has_slot_state(cfg: ModelConfig) -> bool:
    """True when any layer keeps per-slot state the serving prefix cache
    must snapshot on insert and restore on hit — SLA2 linear totals
    (mechanism 'sla2', incl. MLA) or recurrent-mixer checkpoints."""
    return cfg.mechanism == "sla2" or any(
        LAYER_KINDS[k].mixer.slot_state for k in _kinds(cfg))


def has_linear_drafter(cfg: ModelConfig) -> bool:
    """True when the SLA2 linear drafter can serve every layer: mechanism
    'sla2' and only mixers with a draft path (attention)."""
    return cfg.mechanism == "sla2" and all(
        LAYER_KINDS[k].mixer.draft_step is not None for k in _kinds(cfg))


# ===========================================================================
# init
# ===========================================================================

def _init_layer(key, cfg: ModelConfig, kind: str) -> dict:
    mixer, ffn = LAYER_KINDS[kind]
    ks = jax.random.split(key, 4)
    d, dt = cfg.d_model, cfg.param_dtype
    p: dict[str, Any] = {"ln1": L.init_rmsnorm(d, dt),
                         mixer.key: mixer.init(ks[0], cfg)}
    if ffn is not None:
        p["ln2"] = L.init_rmsnorm(d, dt)
        p[ffn.key] = ffn.init(ks[1], cfg)
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

def _embed(params: dict, cfg: ModelConfig, tokens, inputs_embeds=None):
    if inputs_embeds is None:
        x = L.embed(params["embed"], tokens).astype(cfg.param_dtype)
    else:
        x = inputs_embeds.astype(cfg.param_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    return x


def _layer_forward(lp: dict, cfg: ModelConfig, kind: str, x, positions):
    """One block. Returns (x, aux_loss)."""
    mixer, ffn = LAYER_KINDS[kind]
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(lp["ln1"], x)
    x = x + mixer.forward(cfg, lp[mixer.key], h, positions)
    if ffn is None:
        return x, aux
    h2 = L.rmsnorm(lp["ln2"], x)
    y, a = ffn.train(cfg, lp[ffn.key], h2)
    return x + y, aux if a is None else a


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
    x = _embed(params, cfg, tokens, inputs_embeds)
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

def _layer_caches(cfg: ModelConfig, make) -> dict:
    """Every layer's cache ``make(mixer)``, laid out as the params are
    (prefix layers unrolled, groups stacked for scan)."""
    def one_layer(kind):
        mixer = LAYER_KINDS[kind].mixer
        return {mixer.key: make(mixer)}

    caches: dict[str, Any] = {}
    if cfg.first_kinds:
        caches["prefix_layers"] = [one_layer(k) for k in cfg.first_kinds]
    one = {f"l{i}": one_layer(kind)
           for i, kind in enumerate(cfg.layer_kinds)}
    caches["groups"] = jax.tree.map(
        lambda a: jnp.tile(a[None], (cfg.n_groups,) + (1,) * a.ndim), one)
    return caches


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> dict:
    """Static (non-paged) decode caches for every layer."""
    return _layer_caches(
        cfg, lambda m: m.init_cache(cfg, batch, max_len, dtype))


def _layer_static(lp, cfg: ModelConfig, kind, x, lc, op, *args):
    """One block over its static cache; ``op`` is the mixer's 'prefill'
    (``args``: positions) or 'decode'."""
    mixer, ffn = LAYER_KINDS[kind]
    h = L.rmsnorm(lp["ln1"], x)
    y, c = getattr(mixer, op)(cfg, lp[mixer.key], h, lc[mixer.key], *args)
    x = x + y
    if ffn is not None:
        h2 = L.rmsnorm(lp["ln2"], x)
        x = x + ffn.serve(cfg, lp[ffn.key], h2)[0]
    return x, {mixer.key: c}


def _static_stack(params, cfg: ModelConfig, x, caches, op, *args):
    """Run the layer stack over the static caches; returns (final-normed
    hidden, caches)."""
    caches = dict(caches)
    if cfg.first_kinds:
        new_pref = []
        for i, kind in enumerate(cfg.first_kinds):
            x, lc = _layer_static(params["prefix_layers"][i], cfg, kind, x,
                                  caches["prefix_layers"][i], op, *args)
            new_pref.append(lc)
        caches["prefix_layers"] = new_pref

    def body(x, pair):
        gp, gc = pair
        new_gc = {}
        for i, kind in enumerate(cfg.layer_kinds):
            x, new_gc[f"l{i}"] = _layer_static(gp[f"l{i}"], cfg, kind, x,
                                               gc[f"l{i}"], op, *args)
        return x, new_gc

    x, new_groups = maps.scan(body, x, (params["groups"],
                                        caches["groups"]))
    caches["groups"] = new_groups
    return L.rmsnorm(params["final_norm"], x), caches


def prefill(params: dict, cfg: ModelConfig, tokens, caches, *,
            inputs_embeds=None):
    """Run the prompt through the model, filling every cache.
    Returns (logits_last (B, V), caches)."""
    x = _embed(params, cfg, tokens, inputs_embeds)
    b, n, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    x, caches = _static_stack(params, cfg, x, caches, "prefill", positions)
    logits = logits_from_hidden(params, cfg, x[:, -1:])[:, 0]
    return logits, caches


def decode_step(params: dict, cfg: ModelConfig, token_t, caches):
    """One decode step. token_t: (B,) int32. Returns (logits (B, V), caches)."""
    x = _embed(params, cfg, token_t[:, None])
    x, caches = _static_stack(params, cfg, x, caches, "decode")
    return logits_from_hidden(params, cfg, x)[:, 0], caches


# ---------------------------------------------------------------------------
# Paged caches / chunked prefill / per-slot decode (continuous batching)
# ---------------------------------------------------------------------------

def init_paged_caches(cfg: ModelConfig, batch: int, num_pages: int, *,
                      window: int = 1, dtype=jnp.bfloat16) -> dict:
    """Per-layer paged caches sharing one page table (kept by the engine);
    page size == cfg.block_k.  ``window`` sizes the recurrent mixers'
    speculative-verify state buffers (draft window W; 1 when the engine
    never verifies multi-token windows)."""
    return _layer_caches(
        cfg, lambda m: m.init_paged(cfg, batch, num_pages, window, dtype))


def _walk_layers(cfg: ModelConfig, caches: dict, fn) -> dict:
    """Apply ``fn(mixer, layer_cache, lead)`` over every layer cache
    (prefix layers at lead=0, scanned groups at lead=1), preserving the
    layout."""
    out: dict[str, Any] = {}
    if cfg.first_kinds:
        out["prefix_layers"] = [
            fn(LAYER_KINDS[kind].mixer, lc, 0)
            for kind, lc in zip(cfg.first_kinds, caches["prefix_layers"])]
    out["groups"] = {
        f"l{i}": fn(LAYER_KINDS[kind].mixer, caches["groups"][f"l{i}"], 1)
        for i, kind in enumerate(cfg.layer_kinds)}
    return out


def _vmap_layers(cfg: ModelConfig, caches: dict, fn) -> dict:
    """``fn(mixer, layer_cache)`` on every layer, vmapped over the scanned
    groups' leading axis."""
    out = dict(caches)
    if cfg.first_kinds:
        out["prefix_layers"] = [
            fn(LAYER_KINDS[kind].mixer, lc)
            for kind, lc in zip(cfg.first_kinds, caches["prefix_layers"])]
    out["groups"] = {
        f"l{i}": jax.vmap(functools.partial(fn, LAYER_KINDS[kind].mixer))(
            caches["groups"][f"l{i}"])
        for i, kind in enumerate(cfg.layer_kinds)}
    return out


def _by_part(mixer: Mixer, pages, slot, *caches):
    """``pages`` on a cache that holds pages, ``slot`` on one that holds
    slot state alone, through a composite cache's parts."""
    if mixer.parts is None:
        return pages(*caches)
    return {k: (pages if part == "pages" else slot)(*(c[k] for c in caches))
            for k, part in mixer.parts.items()}


def _check_key(mixer: Mixer, state: dict, what: str):
    if mixer.key not in state:
        raise ValueError(
            f"{what} for a {mixer.key!r} layer must carry {mixer.key!r} "
            f"leaves, got {sorted(state)} — taken from a different layer "
            "kind?")


def swap_out_slot(cfg: ModelConfig, caches: dict, page_row, slot) -> dict:
    """Extract one slot's full paged state across every layer: its pages
    (K/V or latent) at ``page_row`` and its per-slot states (SLA2 linear
    totals / recurrent checkpoints) at ``slot``.  The result pytree is
    what the serving SwapPool keeps on the host."""
    def f(m, lc, lead):
        return {m.key: _by_part(
            m, lambda c: A.extract_paged_state(c, page_row, slot, lead),
            lambda c: A.extract_slot_state(c, slot, lead), lc[m.key])}
    return _walk_layers(cfg, caches, f)


def swap_in_slot(cfg: ModelConfig, caches: dict, page_row, slot,
                 state: dict) -> dict:
    """Write a swapped-out slot state back into the pools at a fresh page
    row / slot id (the physical placement may differ from swap-out)."""
    def f(m, pair, lead):
        lc, st = pair
        _check_key(m, st, "swap state")
        return {m.key: _by_part(
            m, lambda c, s: A.insert_paged_state(c, page_row, slot, s, lead),
            lambda c, s: A.insert_slot_state(c, slot, s, lead),
            lc[m.key], st[m.key])}
    new = dict(caches)
    new.update(_walk_layers(cfg, _zip_layouts(cfg, caches, state), f))
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
    def f(m, lc, lead):
        def one(c):
            return A.extract_slot_state(c, slot, lead)
        return {m.key: _by_part(m, one, one, lc[m.key])}
    return _walk_layers(cfg, caches, f)


def insert_linear_totals(cfg: ModelConfig, caches: dict, slot,
                         totals: dict) -> dict:
    """Write an ``extract_linear_totals`` snapshot back into every layer at
    ``slot`` — the O(1) restore a prefix-cache hit performs before chunked
    prefill resumes at the first uncached page."""
    def f(m, pair, lead):
        lc, st = pair
        _check_key(m, st, "slot totals")

        def one(c, s):
            return A.insert_slot_state(c, slot, s, lead)
        return {m.key: _by_part(m, one, one, lc[m.key], st[m.key])}
    new = dict(caches)
    new.update(_walk_layers(cfg, _zip_layouts(cfg, caches, totals), f))
    return new


def copy_kv_page(cfg: ModelConfig, caches: dict, src, dst) -> dict:
    """Copy one physical page (K/V or latent + pooled router key) onto
    another across every layer — the serving engine's copy-on-write
    primitive for pages shared through the prefix cache.  State-only
    layer caches have no page keys and pass through unchanged."""
    def f(m, lc, lead):
        return {m.key: _by_part(
            m, lambda c: A.copy_paged_page(c, src, dst, lead),
            lambda c: c, lc[m.key])}
    new = dict(caches)
    new.update(_walk_layers(cfg, caches, f))
    return new


def _mix_stacked(cfg: ModelConfig, mixer: Mixer, op, p, h, cache, layer,
                 kw):
    """The mixer's paged ``op``, under its ``lm.attn`` scope.  ``cache`` is
    a layer's own cache when ``layer`` is None, else a scanned group's
    stacked cache and ``layer`` the scan's index: an in-place mixer
    indexes the stack itself; any other gets its layer's slice, and the
    slice it returns is written back into the stack, both outside the
    scope: they are the scan's data movement, not the mixer's."""
    fn = getattr(mixer, op)
    if layer is not None and not mixer.in_place:
        sub = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            cache)
        with jax.named_scope("lm.attn"):
            y, sub = fn(cfg, p, h, sub, None, **kw)
        return y, jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, layer, 0),
            cache, sub)
    with jax.named_scope("lm.attn"):
        return fn(cfg, p, h, cache, layer, **kw)


def _layer_paged(lp, cfg: ModelConfig, kind, x, lc, rows, op, kw,
                 stack=None, layer=None):
    """One block around its mixer's paged ``op``; a kind without an FFN
    has no ln2 half.  On a serving mesh the residual stream stays whole on
    every device (distributed/shard_paged.replicate).  Returns (x, cache,
    counters): the FFN's counters (``MOE.moe_layer``), or None.  ``rows``
    (B, S) bool marks the real tokens the experts route.  For a layer of
    the scanned groups, ``layer`` is its index, ``lc`` the group's stacked
    cache (``_mix_stacked``) and ``stack`` the FFN's whole-stack weights
    and index (``MOE.moe_layer``)."""
    from repro.distributed.shard_paged import replicate
    mixer, ffn = LAYER_KINDS[kind]
    x = replicate(x, cfg.mesh)
    with jax.named_scope("lm.norm"):
        h = L.rmsnorm(lp["ln1"], x)
    y, c = _mix_stacked(cfg, mixer, op, lp[mixer.key], h, lc[mixer.key],
                        layer, kw)
    x = replicate(x + y, cfg.mesh)
    if ffn is None:
        return x, {mixer.key: c}, None
    with jax.named_scope("lm.norm"):
        h2 = L.rmsnorm(lp["ln2"], x)
    with jax.named_scope("lm.mlp"):
        y2, counters = ffn.serve(cfg, lp[ffn.key], h2, rows, stack)
        x = x + y2
    return x, {mixer.key: c}, counters


def _add_counters(total, counters):
    if counters is None:
        return total
    if total is None:
        return counters
    return jax.tree.map(jnp.add, total, counters)


def _paged_stack(params, cfg: ModelConfig, x, caches, rows, op, **kw):
    """Run the layer stack (prefix layers + scanned groups), each mixer
    through its paged ``op`` (a ``Mixer`` field) with the program's
    keyword batch ``kw``; returns (final hidden, new caches, counters).
    ``counters`` sums the FFN counters over the stack (None for a stack
    without expert layers, whose programs gain no outputs)."""
    caches = dict(caches)
    total = None
    if cfg.first_kinds:
        new_pref = []
        for i, kind in enumerate(cfg.first_kinds):
            x, lc, n = _layer_paged(params["prefix_layers"][i], cfg, kind,
                                    x, caches["prefix_layers"][i], rows, op,
                                    kw)
            new_pref.append(lc)
            total = _add_counters(total, n)
        caches["prefix_layers"] = new_pref

    # the scan slices each group's parameters but for what an FFN reads
    # out of the whole stack itself (MOE.split_stack)
    groups, whole = dict(params["groups"]), {}
    for i, kind in enumerate(cfg.layer_kinds):
        ffn = LAYER_KINDS[kind].ffn
        if ffn is not None and ffn.split is not None:
            lp = groups[f"l{i}"]
            sliced, whole[f"l{i}"] = ffn.split(lp[ffn.key])
            groups[f"l{i}"] = {**lp, ffn.key: sliced}

    def body(carry, pair):
        x, pool = carry
        gp, g = pair
        pool, group = dict(pool), None
        for i, kind in enumerate(cfg.layer_kinds):
            stack = (whole[f"l{i}"], g) if f"l{i}" in whole else None
            x, pool[f"l{i}"], n = _layer_paged(
                gp[f"l{i}"], cfg, kind, x, pool[f"l{i}"], rows, op, kw,
                stack, layer=g)
            group = _add_counters(group, n)
        return (x, pool), group

    # the stacked pool rides the carry, so a step holds one pool (a
    # donated one: the engine's).  Each layer's mixer gets the whole stack
    # and the layer index: in-place (MLA) mixers read and write it there,
    # the others slice their layer out and write it back in
    # ``_mix_stacked``.  Ops of the scan outside the layer body's scopes
    # are the loop's own: slicing each layer's weights, and a sliced
    # mixer's pool, out of the stack and writing that pool back
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
    x = _embed(params, cfg, tokens)
    rows = (jnp.arange(tokens.shape[1]) < chunk_len)[None]
    x, caches, counters = _paged_stack(
        params, cfg, x, caches, rows, "prefill_chunk", page_row=page_row,
        offset=offset, chunk_len=chunk_len, slot=slot)
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
    x = _embed(params, cfg, token_t[:, None])
    x, caches, counters = _paged_stack(
        params, cfg, x, caches, active[:, None], "decode_paged",
        page_table=page_table, lengths=lengths, active=active)
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
    x = _embed(params, cfg, tokens_w)
    rows = (jnp.arange(tokens_w.shape[1])[None] < window_len[:, None]) \
        & active[:, None]
    x, caches, counters = _paged_stack(
        params, cfg, x, caches, rows, "decode_verify", page_table=page_table,
        lengths=lengths, active=active, window_len=window_len)
    return _with_counters(logits_from_hidden(params, cfg, x), counters), \
        caches


def commit_window(cfg: ModelConfig, caches, page_table, lengths, accepted,
                  active, window: int):
    """Commit the accepted prefix of a verify window into every layer's
    block state — SLA2 pooled router keys + linear totals for attention /
    MLA layers, accepted-state promotion for recurrent mixers.  ``window``
    is the static window size the verify ran with."""
    def upd(m, lc):
        return {m.key: m.commit_window(
            cfg, lc[m.key], page_table=page_table, lengths=lengths,
            accepted=accepted, active=active, window=window)}
    return _vmap_layers(cfg, caches, upd)


def draft_init(cfg: ModelConfig, caches, page_table, lengths, active):
    """Per-layer linear draft states (running phi(k)·v totals over the full
    cached prefix) for the speculative drafter, mirroring the cache
    layout.  Only for stacks ``has_linear_drafter`` admits: the linear
    drafter has no analogue for MLA latents or recurrent checkpoints."""
    def f(m, lc):
        return {m.key: m.draft_init(cfg, lc[m.key], page_table=page_table,
                                    lengths=lengths, active=active)}
    return _vmap_layers(cfg, caches, f)


def draft_step(params: dict, cfg: ModelConfig, token_t, states, *,
               positions, active):
    """One linear-only draft decode step (no page reads — O(d^2)/token).
    token_t: (B,) int32; positions: (B,) the draft token's position.
    Returns (logits (B, V), states)."""
    x = _embed(params, cfg, token_t[:, None])
    x, states, _ = _paged_stack(params, cfg, x, states, active[:, None],
                                "draft_step", positions=positions,
                                active=active)
    return logits_from_hidden(params, cfg, x)[:, 0], states
