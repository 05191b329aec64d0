"""Wan2.1-style video Diffusion Transformer — the paper's actual target.

Block = adaLN-zero(self-attn) + cross-attn(text) + adaLN-zero(MLP), scanned
over layers.  Self-attention is **bidirectional SLA2** (causal=False), which
is exactly the setting of the paper: video-latent tokens at 480P/720P give
N ≈ 32k sequence length, P decomposes into a 97%-sparse part plus a low-rank
part, and SLA2 routes between the block-sparse flash branch and the linear
branch.

The VAE/patchifier frontend is a stub: ``input_specs`` provides pre-
patchified latent tokens (B, N, c_latent); a linear patch embed maps them to
d_model.  Text conditioning is a stubbed (B, n_text, d_model) embedding
consumed by dense cross-attention (n_text = 77 is tiny).

Training objective: rectified-flow matching.
    x_t = (1 - t) x0 + t eps ,  target v = eps - x0 ,  L = ||v_hat - v||^2

Serving (serve/diffusion.DiffusionEngine) denoises many requests in one
batched dispatch per engine step.  Two per-request constants are invariant
across a request's denoise trajectory and are precomputed once at admission
instead of inside every step:

  * ``precompute_text_kv``  — the cross-attention K/V projections of the
    text embedding, one (K, V) pair per layer (the text never changes);
  * ``precompute_step_mods`` — the adaLN-zero modulation table for the
    request's whole timestep schedule, per layer plus the final-layer pair
    (t_emb -> 6 modulation vectors is a pure function of the scalar t).

``dit_forward`` / ``denoise_step`` accept both via keyword (``text_kv``,
``mods``); the default ``None`` recomputes in-step, which is what
``flow_matching_loss`` (training: fresh t every batch) keeps using.
Self-attention mechanisms are dispatched through ``MECHANISM_ATTENTION``
(the table tools/gen_path_matrix.py renders into docs/paths.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import maps
from repro.core import sla2 as sla2lib
from repro.core.router import RouterConfig
from repro.core.sla2 import SLA2Config
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    """Wan2.1-style video DiT geometry + SLA2 routing/impl knobs.

    ``mechanism`` picks the self-attention math (see MECHANISM_ATTENTION);
    ``sla2_impl`` picks the SLA2 implementation ('kernel' = the Pallas
    block-sparse flash forward, 'gather' = the jnp parity oracle, 'ref' =
    the O(N^2) reference)."""
    name: str = "wan_dit"
    n_layers: int = 30
    d_model: int = 1536
    num_heads: int = 12
    head_dim: int = 128
    d_ff: int = 8960
    c_latent: int = 16
    n_text: int = 77
    mechanism: str = "sla2"         # sla2 | sla | sparse_only | full
    block_q: int = 128
    block_k: int = 64
    k_frac: float = 0.05
    quant_bits: str = "int8"
    sla2_impl: str = "gather"
    q_chunk: int = 16
    fuse_branches: bool = False
    t_emb_dim: int = 256
    remat: str = "full"
    dtype: str = "bfloat16"
    max_target_len: int = 32768

    @property
    def param_dtype(self):
        """Parameter/activation dtype as a jnp dtype."""
        return jnp.dtype(self.dtype)

    def router_config(self) -> RouterConfig:
        """Router geometry — bidirectional (causal=False): video tokens."""
        return RouterConfig(block_q=self.block_q, block_k=self.block_k,
                            k_frac=self.k_frac, causal=False)

    def sla2_config(self) -> SLA2Config:
        """SLA2Config carrying this model's routing + impl + QAT choices."""
        return SLA2Config(router=self.router_config(),
                          quant_bits=self.quant_bits, impl=self.sla2_impl,
                          q_chunk=self.q_chunk,
                          fuse_branches=self.fuse_branches)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: DiTConfig) -> dict:
    ks = jax.random.split(key, 10)
    d, h, dh, dt = cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.param_dtype
    std = d ** -0.5
    p = {
        "ln1": L.init_layernorm(d, dt),
        "wq": L.truncated_normal(ks[0], (d, h * dh), dt, std),
        "wk": L.truncated_normal(ks[1], (d, h * dh), dt, std),
        "wv": L.truncated_normal(ks[2], (d, h * dh), dt, std),
        "wo": L.truncated_normal(ks[3], (h * dh, d), dt, (h * dh) ** -0.5),
        "ln_x": L.init_layernorm(d, dt),
        "xq": L.truncated_normal(ks[4], (d, h * dh), dt, std),
        "xk": L.truncated_normal(ks[5], (d, h * dh), dt, std),
        "xv": L.truncated_normal(ks[6], (d, h * dh), dt, std),
        "xo": L.truncated_normal(ks[7], (h * dh, d), dt, (h * dh) ** -0.5),
        "ln2": L.init_layernorm(d, dt),
        "mlp": L.init_mlp(ks[8], d, cfg.d_ff, gated=False, dtype=dt),
        # adaLN-zero: 6 modulation vectors from t-emb; zero-init projection
        "ada": {"w": jnp.zeros((cfg.t_emb_dim, 6 * d), dt),
                "b": jnp.zeros((6 * d,), dt)},
    }
    if cfg.mechanism == "sla2":
        p["sla2"] = sla2lib.init_sla2_params(
            ks[9], head_dim=dh, num_heads=h,
            n_q_blocks=max(1, cfg.max_target_len // cfg.block_q),
            cfg=cfg.sla2_config(), dtype=dt)
    elif cfg.mechanism == "sla":
        from repro.core import sla as slalib
        p["sla"] = slalib.init_sla_params(ks[9], head_dim=dh, dtype=dt)
    return p


def init_dit(key, cfg: DiTConfig) -> dict:
    """Init the full DiT parameter pytree; blocks are vmapped so every
    per-block tensor carries a leading (n_layers,) axis for maps.scan."""
    ks = jax.random.split(key, 6)
    d, dt = cfg.d_model, cfg.param_dtype
    blocks = jax.vmap(functools.partial(_init_block, cfg=cfg))(
        jax.random.split(ks[0], cfg.n_layers))
    return {
        "patch_in": {
            "w": L.truncated_normal(ks[1], (cfg.c_latent, d), dt,
                                    cfg.c_latent ** -0.5),
            "b": jnp.zeros((d,), dt)},
        "t_mlp": {
            "w1": L.truncated_normal(ks[2], (cfg.t_emb_dim, cfg.t_emb_dim),
                                     dt, cfg.t_emb_dim ** -0.5),
            "w2": L.truncated_normal(ks[3], (cfg.t_emb_dim, cfg.t_emb_dim),
                                     dt, cfg.t_emb_dim ** -0.5)},
        "blocks": blocks,
        "final_ln": L.init_layernorm(d, dt),
        "final_ada": {"w": jnp.zeros((cfg.t_emb_dim, 2 * d), dt),
                      "b": jnp.zeros((2 * d,), dt)},
        "patch_out": {
            "w": jnp.zeros((d, cfg.c_latent), dt),
            "b": jnp.zeros((cfg.c_latent,), dt)},
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def timestep_embedding(t: jax.Array, dim: int) -> jax.Array:
    """Sinusoidal embedding of t in [0, 1]. t: (B,) -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = t[:, None].astype(jnp.float32) * freqs[None] * 1000.0
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _attn_sla2(bp: dict, cfg: DiTConfig, q, k, v) -> jax.Array:
    """SLA2: routed block-sparse flash branch + linear complement,
    re-routed from this step's Q/K (cfg.sla2_impl picks kernel/gather/ref)."""
    return sla2lib.sla2_attention(bp["sla2"], q, k, v, cfg.sla2_config())


def _attn_sla(bp: dict, cfg: DiTConfig, q, k, v) -> jax.Array:
    """SLA ablation: fixed (non-learnable) routing, no alpha combine."""
    from repro.core import sla as slalib
    scfg = slalib.SLAConfig(router=dataclasses.replace(
        cfg.router_config(), learnable=False))
    return slalib.sla_attention(bp["sla"], q, k, v, scfg)


def _attn_sparse_only(bp: dict, cfg: DiTConfig, q, k, v) -> jax.Array:
    """VSA/VMoBA-style ablation: sparse branch only, no linear complement."""
    from repro.core import sla as slalib
    scfg = slalib.SLAConfig(router=dataclasses.replace(
        cfg.router_config(), learnable=False),
        quant_bits=cfg.quant_bits)
    return slalib.sparse_only_attention(q, k, v, scfg)


def _attn_full(bp: dict, cfg: DiTConfig, q, k, v) -> jax.Array:
    """Dense bidirectional softmax attention (the O(N^2) baseline)."""
    d = q.shape[-1]
    s = jnp.einsum("bhnd,bhmd->bhnm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(d)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhnm,bhmd->bhnd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# mechanism -> self-attention math over (B, H, N, Dh) q/k/v.  This is the
# table DiffusionEngine's `mechanism` knob selects from and the one
# tools/gen_path_matrix.py renders into docs/paths.md — extend it here and
# the generated matrix (and the serving ablation surface) follows.
MECHANISM_ATTENTION = {
    "sla2": _attn_sla2,
    "sla": _attn_sla,
    "sparse_only": _attn_sparse_only,
    "full": _attn_full,
}


def _self_attention(bp: dict, cfg: DiTConfig, x: jax.Array) -> jax.Array:
    b, n, _ = x.shape
    h, dh = cfg.num_heads, cfg.head_dim
    with jax.named_scope("dit.qkv"):
        q = (x @ bp["wq"]).reshape(b, n, h, dh).transpose(0, 2, 1, 3)
        k = (x @ bp["wk"]).reshape(b, n, h, dh).transpose(0, 2, 1, 3)
        v = (x @ bp["wv"]).reshape(b, n, h, dh).transpose(0, 2, 1, 3)
    o = MECHANISM_ATTENTION[cfg.mechanism](bp, cfg, q, k, v)
    with jax.named_scope("dit.out_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
        return o @ bp["wo"]


def _cross_attention(bp: dict, cfg: DiTConfig, x: jax.Array,
                     text: Optional[jax.Array],
                     kv: Optional[tuple] = None) -> jax.Array:
    """Dense cross-attention to the text embedding.  ``kv`` is an optional
    precomputed (k, v) pair, each (B, H, n_text, Dh) — the serving path
    projects the (constant) text once per request instead of per step; the
    training path passes ``text`` and projects in place."""
    b, n, _ = x.shape
    h, dh = cfg.num_heads, cfg.head_dim
    q = (x @ bp["xq"]).reshape(b, n, h, dh).transpose(0, 2, 1, 3)
    if kv is None:
        m = text.shape[1]
        k = (text @ bp["xk"]).reshape(b, m, h, dh).transpose(0, 2, 1, 3)
        v = (text @ bp["xv"]).reshape(b, m, h, dh).transpose(0, 2, 1, 3)
    else:
        k, v = kv
    s = jnp.einsum("bhnd,bhmd->bhnm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(dh)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhnm,bhmd->bhnd", p, v.astype(jnp.float32))
    o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, n, h * dh)
    return o @ bp["xo"]


# ---------------------------------------------------------------------------
# per-request constants (serving): text K/V + timestep modulation tables
# ---------------------------------------------------------------------------

def precompute_text_kv(params: dict, cfg: DiTConfig, text: jax.Array):
    """Project the text embedding through every layer's cross-attention
    K/V weights once.  text: (B, n_text, d_model) -> (k, v), each
    (n_layers, B, H, n_text, Dh).  Layer l's slice is bit-identical to what
    ``_cross_attention`` computes in-step (same per-row matmul), so cached
    and uncached denoise agree exactly."""
    h, dh = cfg.num_heads, cfg.head_dim
    text = text.astype(cfg.param_dtype)
    b, m, _ = text.shape

    def proj(w):  # (L, d, h*dh) stacked block weights
        y = jnp.einsum("bmd,lde->lbme", text, w)
        return y.reshape(-1, b, m, h, dh).transpose(0, 1, 3, 2, 4)

    blocks = params["blocks"]
    return proj(blocks["xk"]), proj(blocks["xv"])


def precompute_step_mods(params: dict, cfg: DiTConfig, t: jax.Array):
    """adaLN-zero modulation tables for a whole timestep schedule.

    t: (S,) timesteps -> {"blocks": (n_layers, S, 6*d_model),
    "final": (S, 2*d_model)}, float32.  One row per scheduled step; the
    engine gathers each request's current row instead of re-running the
    t-embedding MLP + per-layer ada projections every denoise step."""
    t_emb = timestep_embedding(t, cfg.t_emb_dim)
    t_emb = jax.nn.silu(t_emb @ params["t_mlp"]["w1"].astype(jnp.float32))
    t_emb = t_emb @ params["t_mlp"]["w2"].astype(jnp.float32)
    ada = params["blocks"]["ada"]
    blocks = (jnp.einsum("se,led->lsd", t_emb,
                         ada["w"].astype(jnp.float32))
              + ada["b"].astype(jnp.float32)[:, None, :])
    final = (t_emb @ params["final_ada"]["w"].astype(jnp.float32)
             + params["final_ada"]["b"].astype(jnp.float32))
    return {"blocks": blocks, "final": final}


def _block_forward(bp: dict, cfg: DiTConfig, x, text, t_emb,
                   kv: Optional[tuple] = None,
                   mod: Optional[jax.Array] = None):
    if mod is None:
        mod = (t_emb @ bp["ada"]["w"].astype(jnp.float32)
               + bp["ada"]["b"].astype(jnp.float32))
    with jax.named_scope("dit.modulate"):
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod.astype(x.dtype), 6,
                                               axis=-1)
        h = _modulate(L.layernorm(bp["ln1"], x), sh1, sc1)
    y = _self_attention(bp, cfg, h)
    with jax.named_scope("dit.modulate"):
        x = x + g1[:, None, :] * y
    with jax.named_scope("dit.cross_attn"):
        x = x + _cross_attention(bp, cfg, L.layernorm(bp["ln_x"], x), text,
                                 kv)
    with jax.named_scope("dit.modulate"):
        h2 = _modulate(L.layernorm(bp["ln2"], x), sh2, sc2)
    with jax.named_scope("dit.mlp"):
        y = L.mlp(bp["mlp"], h2, activation="gelu")
    with jax.named_scope("dit.modulate"):
        return x + g2[:, None, :] * y


def dit_forward(params: dict, cfg: DiTConfig, latents: jax.Array,
                text: Optional[jax.Array], t: Optional[jax.Array],
                *, text_kv: Optional[tuple] = None,
                mods: Optional[dict] = None) -> jax.Array:
    """latents: (B, N, c_latent); text: (B, n_text, d_model); t: (B,).
    Returns the predicted velocity field (B, N, c_latent).

    Serving passes the per-request constants instead of recomputing them
    per step: ``text_kv`` from ``precompute_text_kv`` and ``mods`` as
    {"blocks": (n_layers, B, 6*d_model), "final": (B, 2*d_model)} — this
    step's rows gathered from the ``precompute_step_mods`` tables.  With
    both set, ``text`` and ``t`` may be None."""
    x = (latents.astype(cfg.param_dtype) @ params["patch_in"]["w"]
         + params["patch_in"]["b"])
    if mods is None:
        t_emb = timestep_embedding(t, cfg.t_emb_dim)
        t_emb = jax.nn.silu(t_emb
                            @ params["t_mlp"]["w1"].astype(jnp.float32))
        t_emb = t_emb @ params["t_mlp"]["w2"].astype(jnp.float32)
    else:
        t_emb = None
    if text is not None:
        text = text.astype(cfg.param_dtype)

    if text_kv is None and mods is None:
        def body(x, bp):
            return _block_forward(bp, cfg, x, text, t_emb), None
        xs = params["blocks"]
    else:
        def body(x, scanned):
            bp, kv, mod = scanned
            return _block_forward(bp, cfg, x, text, t_emb,
                                  kv=kv, mod=mod), None
        xs = (params["blocks"], text_kv,
              mods["blocks"] if mods is not None else None)

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    x, _ = maps.scan(body, x, xs)

    if mods is None:
        mod = (t_emb @ params["final_ada"]["w"].astype(jnp.float32)
               + params["final_ada"]["b"].astype(jnp.float32))
    else:
        mod = mods["final"]
    with jax.named_scope("dit.modulate"):
        sh, sc = jnp.split(mod.astype(x.dtype), 2, axis=-1)
        x = _modulate(L.layernorm(params["final_ln"], x), sh, sc)
    return (x @ params["patch_out"]["w"] + params["patch_out"]["b"]) \
        .astype(jnp.float32)


def flow_matching_loss(params: dict, cfg: DiTConfig, batch: dict):
    """batch: latents x0 (B,N,c), text (B,n_text,d), noise eps (B,N,c),
    time t (B,) in (0,1)."""
    x0 = batch["latents"].astype(jnp.float32)
    eps = batch["noise"].astype(jnp.float32)
    t = batch["time"].astype(jnp.float32)
    x_t = (1.0 - t[:, None, None]) * x0 + t[:, None, None] * eps
    v_target = eps - x0
    v_hat = dit_forward(params, cfg, x_t, batch["text"], t)
    loss = jnp.mean((v_hat - v_target) ** 2)
    return loss, {"mse": loss}


def denoise_step(params: dict, cfg: DiTConfig, x_t, text, t, dt,
                 *, text_kv: Optional[tuple] = None,
                 mods: Optional[dict] = None):
    """One Euler step of the rectified-flow ODE (serving/e2e latency).
    ``text_kv`` / ``mods`` forward the per-request cached constants to
    ``dit_forward`` (see there); dt: (B,) per-request step size."""
    v = dit_forward(params, cfg, x_t, text, t, text_kv=text_kv, mods=mods)
    return x_t - dt[:, None, None] * v
