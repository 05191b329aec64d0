"""SLA2 — the paper's contribution as a composable JAX module.

    O = alpha (.) O_s + (1 - alpha) (.) O_l          (Eq. 13)
    O_s = softmax(Q K^T / sqrt(d) (.) M) V
    O_l = norm(phi(Q) phi(K)^T (.) (1 - M)) V
    M   = R(Q, K)                                    (Eq. 14/16)

``alpha`` is a learnable per-(head, query-block) ratio in (0, 1), stored as a
logit and squashed with a sigmoid.  The router R is in router.py; the
SoftTop-k relaxation used during stage-1 training is in soft_topk.py; QAT
fake-quant of the sparse branch is in quant.py.

Two interchangeable implementations:
  * impl='ref'    — pure-jnp O(N^2) oracle (tests, small models, soft mode)
  * impl='kernel' — Pallas block-sparse kernels (TPU target; interpret=True
                    on CPU), hard mask only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import attention as attn
from repro.core import router as routerlib
from repro.core.router import RouterConfig


@dataclasses.dataclass(frozen=True)
class SLA2Config:
    router: RouterConfig = RouterConfig()
    quant_bits: str = "int8"        # 'none' | 'int8' | 'fp8'  (QAT, fwd only)
    alpha_granularity: str = "per_block"  # 'per_block' | 'per_head' | 'scalar'
    alpha_init: float = 0.9         # initial sparse-branch weight
    impl: str = "ref"               # 'ref' | 'gather' | 'kernel'
    q_chunk: int = 32               # gather mode: query blocks per map step
    fuse_branches: bool = False     # gather mode: single-pass both branches

    @property
    def block_q(self) -> int:
        return self.router.block_q

    @property
    def block_k(self) -> int:
        return self.router.block_k


def init_sla2_params(key: jax.Array, *, head_dim: int, num_heads: int,
                     n_q_blocks: int, cfg: SLA2Config,
                     dtype=jnp.float32) -> dict:
    k_r, _ = jax.random.split(key)
    logit = jnp.log(cfg.alpha_init / (1.0 - cfg.alpha_init))
    if cfg.alpha_granularity == "per_block":
        alpha = jnp.full((num_heads, n_q_blocks), logit, dtype)
    elif cfg.alpha_granularity == "per_head":
        alpha = jnp.full((num_heads, 1), logit, dtype)
    elif cfg.alpha_granularity == "scalar":
        alpha = jnp.full((1, 1), logit, dtype)
    else:
        raise ValueError(cfg.alpha_granularity)
    return {
        "router": routerlib.init_router_params(k_r, head_dim, cfg.router, dtype),
        "alpha_logit": alpha,
    }


def alpha_for_blocks(params: dict, t_m: int, num_heads: int) -> jax.Array:
    """alpha as (H, T_m) in (0, 1), broadcasting the stored granularity and
    tolerating shape mismatch (e.g. decode uses the last block's alpha)."""
    logit = params["alpha_logit"]
    a = jax.nn.sigmoid(logit.astype(jnp.float32))
    if a.shape[0] == 1 and num_heads > 1:
        a = jnp.broadcast_to(a, (num_heads, a.shape[1]))
    if a.shape[1] == 1:
        a = jnp.broadcast_to(a, (num_heads, t_m))
    elif a.shape[1] < t_m:  # longer sequence than init: repeat last block
        pad = jnp.broadcast_to(a[:, -1:], (a.shape[0], t_m - a.shape[1]))
        a = jnp.concatenate([a, pad], axis=1)
    elif a.shape[1] > t_m:
        a = a[:, :t_m]
    return a  # (H, T_m)


def _expand_alpha(a_blocks: jax.Array, block_q: int, n: int) -> jax.Array:
    """(H, T_m) -> (H, N, 1) token-level alpha."""
    a = jnp.repeat(a_blocks, block_q, axis=-1)[..., :n]
    return a[..., None]


def sla2_attention(params: dict, q: jax.Array, k: jax.Array, v: jax.Array,
                   cfg: SLA2Config, *, soft: bool = False,
                   mask_override: Optional[jax.Array] = None,
                   return_aux: bool = False):
    """Apply SLA2 attention.

    q, k, v : (B, H, N, D) (GQA callers repeat K/V heads before this point;
              the router then shares routing across the repeated group).
    soft    : stage-1 training mode (SoftTop-k mask, differentiable routing).
    mask_override : use a precomputed block mask (ablations / tests).

    Returns O (B, H, N, D) and optionally aux dict with the block mask and
    achieved sparsity.
    """
    b, h, n, d = q.shape
    rcfg = cfg.router
    if mask_override is not None:
        mask_c = mask_override
    else:
        with jax.named_scope("sla2.router"):
            mask_c = routerlib.route(params.get("router", {}), q, k, rcfg,
                                     soft=soft)

    if cfg.impl == "kernel" and not soft:
        from repro.kernels import ops as kops  # lazy: keeps core import-light
        o, aux = kops.sla2_block_sparse(
            params, q, k, v, cfg, mask_c=mask_c)
    elif cfg.impl == "gather" and not soft:
        from repro.core import block_sparse
        flat = lambda x: x.reshape(b * h, *x.shape[2:])
        qf, kf, vf = flat(q), flat(k), flat(v)
        with jax.named_scope("sla2.router"):
            idx, valid = routerlib.route_indices(
                params.get("router", {}), qf, kf, rcfg)
        t_m = n // rcfg.block_q
        a = _expand_alpha(alpha_for_blocks(params, t_m, h), rcfg.block_q, n)
        a_tok = jnp.broadcast_to(a[None], (b, h, n, 1)).reshape(b * h, n, 1)
        o = block_sparse.sla2_gather(
            a_tok, qf, kf, vf, idx, valid, block_q=rcfg.block_q,
            block_k=rcfg.block_k, causal=rcfg.causal,
            quant_bits=cfg.quant_bits, prefix_len=rcfg.prefix_len,
            q_chunk=cfg.q_chunk, fuse_branches=cfg.fuse_branches)
        o = o.reshape(b, h, n, vf.shape[-1])
        aux = {"idx": idx, "valid": valid}
    else:
        with jax.named_scope("sla2.sparse"):
            o_s = attn.sparse_attention(
                q, k, v, mask_c, block_q=rcfg.block_q, block_k=rcfg.block_k,
                causal=rcfg.causal, soft=soft, quant_bits=cfg.quant_bits,
                prefix_len=rcfg.prefix_len)
        with jax.named_scope("sla2.linear"):
            o_l = attn.linear_attention(
                q, k, v, mask_c, block_q=rcfg.block_q, block_k=rcfg.block_k,
                causal=rcfg.causal, soft=soft, prefix_len=rcfg.prefix_len)
        with jax.named_scope("sla2.combine"):
            t_m = n // rcfg.block_q
            a = _expand_alpha(alpha_for_blocks(params, t_m, h),
                              rcfg.block_q, n)
            # where the routed complement is empty the row is fully sparse:
            # the decomposition P = P1 + P2 degenerates to P = P1, so alpha
            # must be 1 regardless of its learned value (matches the kernel
            # path).
            comp = 1.0 - mask_c.astype(jnp.float32)
            if rcfg.causal:
                i_arr = jnp.arange(t_m)
                n_full = (i_arr * rcfg.block_q + 1) // rcfg.block_k
                if rcfg.prefix_len:
                    n_full = jnp.maximum(n_full,
                                         rcfg.prefix_len // rcfg.block_k)
                fully = (jnp.arange(mask_c.shape[-1])[None, :]
                         < n_full[:, None])
                comp = comp * fully.astype(comp.dtype)
            nonempty = comp.sum(-1) > 1e-6               # (B, H, T_m)
            nonempty = jnp.repeat(nonempty, rcfg.block_q,
                                  axis=-1)[..., None]
            a = jnp.where(nonempty, a, 1.0)
            o = (a * o_s.astype(jnp.float32)
                 + (1.0 - a) * o_l.astype(jnp.float32)).astype(q.dtype)
        aux = {}
    if return_aux:
        from repro.core import masks as masklib
        allowed, _ = routerlib._allowed_and_forced(
            mask_c.shape[-2], mask_c.shape[-1], rcfg)
        aux = dict(aux)
        aux["mask_c"] = mask_c
        aux["sparsity"] = masklib.mask_sparsity(
            (mask_c > 0.5).astype(jnp.float32), allowed)
        return o, aux
    return o


def sla2_mse_loss(params: dict, q, k, v, cfg: SLA2Config, *,
                  soft: bool = True, causal: bool | None = None) -> jax.Array:
    """Stage-1 objective (Alg. 1 line 3):
    L = MSE(FullAttn(Q,K,V), SLA2(Q,K,V, k%, R, alpha))."""
    causal = cfg.router.causal if causal is None else causal
    target = attn.full_attention(q, k, v, causal=causal,
                                 prefix_len=cfg.router.prefix_len)
    pred = sla2_attention(params, q, k, v, cfg, soft=soft)
    return jnp.mean((pred.astype(jnp.float32) - target.astype(jnp.float32)) ** 2)
