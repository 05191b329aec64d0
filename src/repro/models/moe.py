"""Mixture-of-Experts FFN: one device's share of a dropless expert layer,
and the GShard capacity dispatch that training shards over a mesh.

Routing (both paths): a softmax router over all ``num_experts`` experts
of the layer and greedy top-k; the k gates are the router's probabilities
as they are, as DeepSeek-V2 publishes (``norm_topk_prob: false``,
``routed_scaling_factor: 1``), or renormalised to sum to 1 where the
config sets ``norm_topk_prob`` (Llama 4's top-1 gate is then 1).
DeepSeek-style *shared experts* run densely beside the routed ones.

``moe_layer`` (serving) holds experts ``[expert_offset, expert_offset +
held_experts)`` of the layer, as one device of an expert-parallel
deployment does.  It routes every row over all experts, sorts the (row,
expert) pairs that land on held experts by expert, runs them through the
grouped gated FFN (``kernels/moe_experts``) and combines them by gate.  No
capacity binds, so no pair is dropped, and a row's output does not depend
on which other rows the call holds.  What the experts held elsewhere add
is not computed here: on one device the layer runs without its exchange.

``moe_ffn`` (training) keeps the GShard pattern for a layer that holds
all its experts: pairs are scattered into a per-expert buffer of capacity
``C = round(T*k/E * capacity_factor)`` (pairs past it are dropped), whose
sharding over ``ep_axis`` makes XLA emit the all-to-all dispatch/combine
pair across the mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import moe_experts as K
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int            # router outputs: the experts of the layer
    top_k: int
    d_ff_expert: int            # per-expert hidden dim
    num_shared: int = 0         # DeepSeek shared experts (always-on)
    held_experts: int = 0       # experts this device holds (0: all)
    expert_offset: int = 0      # the first held expert
    norm_topk_prob: bool = False    # renormalise the k gates to sum to 1
    capacity_factor: float = 1.25   # training's GShard dispatch only
    aux_loss_weight: float = 0.01
    router_dtype: str = "float32"

    @property
    def held(self) -> int:
        """Number of experts whose weights this device holds."""
        return self.held_experts or self.num_experts


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    e, ff = cfg.held, cfg.d_ff_expert
    std = d_model ** -0.5
    p = {
        "router": L.truncated_normal(ks[0], (d_model, cfg.num_experts),
                                     jnp.float32, std),
        # held experts only; fused gate+up: (E, d, 2*ff); down: (E, ff, d)
        "w_in": L.truncated_normal(ks[1], (e, d_model, 2 * ff), dtype, std),
        "w_out": L.truncated_normal(ks[2], (e, ff, d_model), dtype, ff ** -0.5),
    }
    if cfg.num_shared:
        p["shared"] = L.init_mlp(
            jax.random.fold_in(key, 7), d_model, cfg.num_shared * ff,
            gated=True, dtype=dtype)
    return p


def route(params: dict, xt: jax.Array, cfg: MoEConfig):
    """Softmax over all experts, greedy top-k.  xt (T, d) -> probs (T, E),
    gates (T, k) f32, expert ids (T, k)."""
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, ids


def split_stack(params: dict):
    """Split a stack of expert layers' parameters (leading axis: the
    layer) into what a layer scan slices per layer and what the expert
    kernel reads out of the whole stack itself (the held experts'
    weights; slicing those per layer would copy them).  Returns (sliced,
    whole); ``moe_layer`` takes ``whole`` with the layer's index."""
    whole = {w: params[w] for w in ("w_in", "w_out")}
    return {k: v for k, v in params.items() if k not in whole}, whole


def moe_layer(params: dict, x: jax.Array, cfg: MoEConfig, rows=None,
              stack=None):
    """This device's share of the dropless expert layer, plus the shared
    experts.  x (B, S, d); ``rows`` (B, S) bool marks the rows to route
    (padding and idle slots are not).  ``stack`` is ``(whole, layer)``
    for a layer of a scanned stack: ``split_stack``'s whole-stack expert
    weights and this layer's index, which the kernel reads its weights
    out of; without it ``params`` holds the layer's own.  Returns (y (B,
    S, d), counters): ``moe_held_rows``, the (row, expert) pairs the held
    experts computed, and ``moe_active_experts``, the held experts with at
    least one."""
    b, s, d = x.shape
    t, k, held = b * s, cfg.top_k, cfg.held
    xt = x.reshape(t, d)
    with jax.named_scope("moe.router"):
        _, gates, ids = route(params, xt, cfg)
        local = ids - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        if rows is not None:
            mine = mine & rows.reshape(t, 1)
        # pairs sorted by held expert; pairs no held expert takes go last
        key = jnp.where(mine, local, held).reshape(t * k)
        order = jnp.argsort(key, stable=True)
        sizes = jax.nn.one_hot(key, held + 1, dtype=jnp.int32).sum(0)[:held]
        x_sorted = xt[order // k]
    with jax.named_scope("moe.experts"):
        weights, layer = stack if stack is not None else (params, None)
        experts = (weights["w_in"], weights["w_out"], sizes, layer)
        if jax.default_backend() == "cpu":
            out = K.moe_experts_reference(x_sorted, *experts)
        else:
            out = K.moe_experts(x_sorted, *experts,
                                tm=K.tile_rows(t * k, cfg.num_experts))
        pairs = out[jnp.argsort(order)].reshape(t, k, d).astype(jnp.float32)
        w = jnp.where(mine, gates, 0.0)
        y = (pairs * w[..., None]).sum(1).astype(x.dtype).reshape(b, s, d)
    if cfg.num_shared:
        with jax.named_scope("moe.shared"):
            y = y + L.mlp(params["shared"], x)
    counters = {"moe_held_rows": sizes.sum(),
                "moe_active_experts": (sizes > 0).sum().astype(jnp.int32)}
    return y, counters


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig,
            ep_axis: Optional[str] = None):
    """Training's GShard layer over all experts.  x: (B, S, d) -> (y: (B,
    S, d), aux_loss: scalar).

    ``ep_axis`` is the mesh axis name experts are sharded over; the dispatch
    buffer gets an explicit sharding constraint on it so GSPMD materialises
    the all-to-all at the dispatch/combine boundary.
    """
    if cfg.held != cfg.num_experts:
        raise ValueError("the GShard layer holds every expert; "
                         f"held_experts={cfg.held_experts} of "
                         f"{cfg.num_experts}")
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k
    xt = x.reshape(t, d)
    probs, gate_vals, expert_ids = route(params, xt, cfg)       # (T, k)

    # ---- load-balance aux loss (Switch): E * sum_e f_e * p_e ----
    one_hot_top1 = jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32)
    f = one_hot_top1.mean(0)
    p_mean = probs.mean(0)
    aux = cfg.aux_loss_weight * e * jnp.sum(f * p_mean)

    # ---- capacity + rank-within-expert ----
    cap = int(max(1, round(t * k / e * cfg.capacity_factor)))
    # flatten (token, slot) pairs; earlier slots (higher gate) win capacity
    flat_ids = expert_ids.reshape(t * k)                        # (T*k,)
    oh = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)           # (T*k, E)
    ranks = jnp.cumsum(oh, axis=0) - oh                         # exclusive
    rank_in_e = jnp.take_along_axis(
        ranks, flat_ids[:, None], axis=1)[:, 0]                 # (T*k,)
    keep = rank_in_e < cap
    slot = flat_ids * cap + jnp.minimum(rank_in_e, cap - 1)     # (T*k,)

    # ---- dispatch: scatter tokens into (E*C, d) ----
    xk = jnp.repeat(xt, k, axis=0)                              # (T*k, d)
    buf = jnp.zeros((e * cap, d), xt.dtype)
    buf = buf.at[jnp.where(keep, slot, e * cap - 1)].add(
        jnp.where(keep[:, None], xk, 0.0),
        mode="drop", indices_are_sorted=False)
    buf = buf.reshape(e, cap, d)
    if ep_axis is not None:
        buf = jax.lax.with_sharding_constraint(
            buf, jax.sharding.PartitionSpec(ep_axis, None, None))

    # ---- expert compute: grouped gated MLP ----
    hin = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])       # (E, C, 2ff)
    gate_h, up_h = jnp.split(hin, 2, axis=-1)
    act = jax.nn.silu(gate_h.astype(jnp.float32)).astype(up_h.dtype) * up_h
    out = jnp.einsum("ecf,efd->ecd", act, params["w_out"])      # (E, C, d)
    if ep_axis is not None:
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.PartitionSpec(ep_axis, None, None))

    # ---- combine: gather each (token, slot) result, weight by gate ----
    out_flat = out.reshape(e * cap, d)
    ys = jnp.take(out_flat, slot, axis=0)                       # (T*k, d)
    w = (gate_vals.reshape(t * k) * keep.astype(jnp.float32))
    y = (ys.astype(jnp.float32) * w[:, None]).reshape(t, k, d).sum(axis=1)
    y = y.astype(x.dtype).reshape(b, s, d)

    if cfg.num_shared:
        y = y + L.mlp(params["shared"], x)
    return y, aux
