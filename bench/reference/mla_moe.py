"""Plain float32 reference of the paged SLA2 MLA + expert model
(DeepSeek-V2 layout: RMSNorm; multi-head latent attention with a shared
latent [c_kv; k_rope]; the first ``first_k_dense_replace`` layers with a
gated MLP, the rest with a softmax top-k expert layer plus shared
experts; untied head), written from the model's equations.  It imports
nothing of the program: it reads the benchmark's weights by their names
in the parameter tree.

It returns the logits the served tokens were chosen from, over one
sequence (prompt + served tokens but the last).  Positions of the prompt
attend exactly, with each head's keys and values decompressed from the
latent (K_h = [c_kv W_uk^h; k_rope], V_h = c_kv W_uv^h, scale
1/sqrt(qk_nope + qk_rope)): the engine's chunked prefill is exact.  Each
later position is one decode step, which attends by SLA2 in latent space
as the program routes it.  With q~_h = [q_nope_h W_uk^h^T; q_rope_h]
scaled by sqrt(d_lat / d_qk), so that q~ . k / sqrt(d_lat) is the exact
score, over the latents k = [c_kv; k_rope] (d_lat wide) with values c_kv:

    router   per query head, q~ proj_q against each visible latent
             block's mean (the current, partial block: the mean of its
             tokens so far) through proj_k; the current block is always
             kept, then the top round(k_frac * max_len / block_k) blocks
    sparse   softmax over the kept blocks' visible tokens
    linear   softmax-feature attention (features over d_lat) over the
             complete blocks not kept
    out      alpha * sparse + (1 - alpha) * linear (alpha of the last
             query block; alpha = 1 when no complete block is left over),
             then V decompressed per head: o_h W_uv^h

The expert layer is the chip's share, as in the program: a softmax over
all ``router_num_experts`` experts, greedy top-k, gates as they are (no
renormalisation), and only the ``n_routed_experts`` experts held here
(from ``expert_offset``) add their part; the shared experts run on every
token.

``precision='fp8'`` is the control, one precision below the bfloat16 the
model is served in: where the program holds a bf16 value (weights, the
residual stream, projections, latents, attention outputs, FFN hidden
states) the control holds it in scaled float8 e4m3 (per row of
activations, per column of weights).  The router runs in f32 in both, as
in the program.  ``precision='bf16'`` rounds the same values to bfloat16
instead: the reference in the program's own precision, a witness of how
far bf16 alone moves the served tokens from the float32 reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import lm
from bench.reference.lm import F32, NEG, _bucket, _rms, _rope


def _act(x, precision):
    """An activation as the program holds it (``lm._act``); rounded to
    bf16 in the witness."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return lm._act(x, precision)


def _mm(x, w, precision, act=True):
    """``lm._mm``; in the witness the matmul's input is rounded to bf16
    (the weights are bf16 already) and accumulates in f32."""
    if precision != "bf16":
        return lm._mm(x, w, precision, act)
    y = _act(x, precision) @ w.astype(F32)
    return _act(y, precision) if act else y


def _head(x, norm, head, P, n_rows, eps, precision):
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.dynamic_slice_in_dim(x, P - 1, n_rows)
        return _mm(_rms(norm, rows, eps), head, precision, act=False)


def _causal(q, k, v, chunk=256):
    """Exact causal softmax attention for every position.  q, k (L, H,
    dk); v (L, H, dv)."""
    L, H, dk = q.shape

    def one(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk)
        s = jnp.einsum("chd,mhd->hcm", qc, k) / math.sqrt(dk)
        rows = i * chunk + jnp.arange(chunk)
        vis = jnp.arange(L)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(vis[None], s, NEG), -1)
        return jnp.einsum("hcm,mhd->chd", p, v)

    return jax.lax.map(one, jnp.arange(L // chunk)).reshape(L, H, -1)


def _decode(q, k, sla, P, n_pos, *, r, bk, k_sel, chunk=64):
    """Latent SLA2 decode attention for positions P .. P + n_pos - 1.
    q (L, H, D) scaled latent queries; k (L, D) latents, values k[:, :r].
    Returns (n_pos, H, r)."""
    L, H, D = q.shape
    nb = L // bk
    kb = k.reshape(nb, bk, D)
    kbar = kb.mean(1)                                         # (nb, D)
    csum = jnp.concatenate([jnp.zeros((1, D), F32), jnp.cumsum(k, 0)])
    fk = jax.nn.softmax(kb, -1)
    hpre = jnp.concatenate([
        jnp.zeros((1, D, r), F32),
        jnp.cumsum(jnp.einsum("jkd,jkr->jdr", fk, kb[..., :r]), 0)])
    zpre = jnp.concatenate([jnp.zeros((1, D), F32),
                            jnp.cumsum(fk.sum(1), 0)])
    pq = sla["router"]["proj_q"].astype(F32)
    pk = sla["router"]["proj_k"].astype(F32)
    alpha = jax.nn.sigmoid(sla["alpha_logit"][:, -1].astype(F32))  # (H,)

    def one(i):
        t = jnp.minimum(P + i * chunk + jnp.arange(chunk), L - 1)  # (c,)
        cur = t // bk
        part = (csum[t + 1] - csum[cur * bk]) / (t - cur * bk + 1)[:, None]
        j = jnp.arange(nb)
        pooled = jnp.where((j[None, :] == cur[:, None])[..., None],
                           part[:, None], kbar[None])             # (c, nb, D)
        qt = q[t]                                                 # (c, H, D)
        sc = jnp.einsum("chd,cjd->chj", qt @ pq, pooled @ pk) \
            / math.sqrt(D)
        sc = jnp.where(j[None, None] <= cur[:, None, None], sc, NEG)
        sc = jnp.where(j[None, None] == cur[:, None, None], jnp.inf, sc)
        top, idx = jax.lax.top_k(sc, k_sel)                       # (c, H, s)
        valid = top > NEG / 2
        ks = kb[idx]                                       # (c, H, s, bk, D)
        tok = idx[..., None] * bk + jnp.arange(bk)
        vis = valid[..., None] & (tok <= t[:, None, None, None])
        s = jnp.einsum("chd,chskd->chsk", qt, ks) / math.sqrt(D)
        s = jnp.where(vis, s, NEG)
        p = jax.nn.softmax(s.reshape(*s.shape[:2], -1), -1).reshape(s.shape)
        o_s = jnp.einsum("chsk,chskr->chr", p, ks[..., :r])
        # linear branch: complete blocks minus the kept complete ones
        n_full = (t + 1) // bk
        kept = valid & (idx < n_full[:, None, None])
        fq = jax.nn.softmax(qt, -1)
        ls = jnp.einsum("chd,chskd->chsk", fq, jax.nn.softmax(ks, -1))
        ls = ls * kept[..., None]
        num = jnp.einsum("chd,cdr->chr", fq, hpre[n_full]) \
            - jnp.einsum("chsk,chskr->chr", ls, ks[..., :r])
        den = jnp.einsum("chd,cd->ch", fq, zpre[n_full]) - ls.sum((-2, -1))
        left = (n_full[:, None] - kept.sum(-1)) > 0               # (c, H)
        o_l = jnp.where(left[..., None],
                        num / jnp.maximum(den, 1e-30)[..., None], 0.0)
        a = jnp.where(left, alpha[None], 1.0)[..., None]
        return a * o_s + (1.0 - a) * o_l

    return jax.lax.map(one, jnp.arange(n_pos // chunk)).reshape(n_pos, H, r)


def _mlp(m, y, precision):
    h = _act(jax.nn.silu(_mm(y, m["w_gate"], precision))
             * _mm(y, m["w_up"], precision), precision)
    return _mm(h, m["w_down"], precision)


def _experts(m, y, cfg, precision):
    """The held experts' part of the expert layer, plus the shared ones."""
    probs = jax.nn.softmax(_act(y, precision) @ m["router"].astype(F32), -1)
    gates, ids = jax.lax.top_k(probs, cfg["top_k"])
    ff = m["w_out"].shape[1]
    out = _mlp(m["shared"], y, precision)
    for e in range(m["w_out"].shape[0]):
        g = jnp.where(ids == cfg["offset"] + e, gates, 0.0).sum(-1)
        w_in = m["w_in"][e]
        h = _act(jax.nn.silu(_mm(y, w_in[:, :ff], precision))
                 * _mm(y, w_in[:, ff:], precision), precision)
        out = out + g[:, None] * _mm(h, m["w_out"][e], precision)
    return out


def _attention(at, y, P, cfg, n_pos, precision):
    L = y.shape[0]
    H, dn, dr = cfg["heads"], cfg["nope"], cfg["rope"]
    r, dv = cfg["rank"], cfg["v_dim"]
    pos = jnp.arange(L)
    q = _mm(y, at["w_q"], precision).reshape(L, H, dn + dr)
    q_nope = q[..., :dn]
    q_rope = _rope(q[..., dn:], pos, cfg["theta"])
    ckv = _mm(y, at["w_dkv"], precision)
    c = _act(_rms(at["kv_norm"], ckv[:, :r], cfg["eps"]), precision)
    k_rope = _rope(ckv[:, None, r:], pos, cfg["theta"])[:, 0]  # (L, dr)
    w_uk = at["w_uk"].astype(F32).reshape(r, H, dn)
    w_uv = at["w_uv"].astype(F32).reshape(r, H, dv)
    # prefill: each head's keys and values decompressed from the latent
    k = jnp.concatenate([jnp.einsum("lr,rhd->lhd", c, w_uk),
                         jnp.broadcast_to(k_rope[:, None], (L, H, dr))], -1)
    v = jnp.einsum("lr,rhd->lhd", c, w_uv)
    o = _causal(jnp.concatenate([q_nope, q_rope], -1), _act(k, precision),
                _act(v, precision))
    # decode: latent SLA2, then V decompressed per head
    q_abs = _act(jnp.einsum("lhd,rhd->lhr", q_nope, w_uk), precision)
    d_lat = r + dr
    ql = jnp.concatenate([q_abs, q_rope], -1) * math.sqrt(d_lat / (dn + dr))
    lat = jnp.concatenate([c, k_rope], -1)
    od = _decode(ql, lat, at["sla2"], P, n_pos, r=r, bk=cfg["block_k"],
                 k_sel=cfg["k_sel"])
    od = jnp.einsum("nhr,rhd->nhd", od, w_uv)
    o = _act(jax.lax.dynamic_update_slice(o, od, (P, 0, 0)), precision)
    return _mm(o.reshape(L, H * dv), at["w_o"], precision)


@functools.partial(jax.jit, static_argnames=("cfg_key", "n_pos", "precision"))
def _dense_layer(x, lw, P, cfg_key, n_pos, precision):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        x = _act(x + _attention(lw["mla"], _rms(lw["ln1"], x, cfg["eps"]),
                                P, cfg, n_pos, precision), precision)
        y = _rms(lw["ln2"], x, cfg["eps"])
        return _act(x + _mlp(lw["mlp"], y, precision), precision)


@functools.partial(jax.jit, static_argnames=("cfg_key", "n_pos", "precision"))
def _expert_layer(x, groups, i, P, cfg_key, n_pos, precision):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a[i], groups["l0"])
        x = _act(x + _attention(lw["mla"], _rms(lw["ln1"], x, cfg["eps"]),
                                P, cfg, n_pos, precision), precision)
        y = _rms(lw["ln2"], x, cfg["eps"])
        return _act(x + _experts(lw["moe"], y, cfg, precision), precision)


def logits(weights, cfg: dict, seq, n_prompt: int, max_len: int,
           precision: str = "fp32") -> np.ndarray:
    """(len(seq) - n_prompt + 1, vocab) logits at positions n_prompt - 1
    onwards: the distributions of the served tokens."""
    bk = cfg["sla2"]["block_k"]
    n_dec = len(seq) - n_prompt
    n_pos = _bucket(n_dec)
    L = -(-(n_prompt + n_pos + 1) // 1024) * 1024
    tokens = np.zeros((L,), np.int32)
    tokens[:len(seq)] = seq
    key = (("heads", cfg["num_attention_heads"]),
           ("nope", cfg["qk_nope_head_dim"]),
           ("rope", cfg["qk_rope_head_dim"]), ("rank", cfg["kv_lora_rank"]),
           ("v_dim", cfg["v_head_dim"]), ("eps", cfg["rms_norm_eps"]),
           ("theta", float(cfg["rope_theta"])), ("block_k", bk),
           ("k_sel", max(1, round(cfg["sla2"]["k_frac"] * (max_len // bk)))),
           ("top_k", cfg["num_experts_per_tok"]),
           ("offset", cfg["expert_offset"]))
    x = _act(jnp.asarray(weights["embed"]["table"])[jnp.asarray(tokens)]
             .astype(F32), precision)
    P = jnp.asarray(n_prompt, jnp.int32)
    for lw in weights.get("prefix_layers", []):
        x = _dense_layer(x, lw, P, key, n_pos, precision)
    n_groups = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    for i in range(n_groups):
        x = _expert_layer(x, weights["groups"], jnp.asarray(i, jnp.int32), P,
                          key, n_pos, precision)
    out = _head(x, weights["final_norm"], weights["lm_head"], P, n_pos + 1,
                cfg["rms_norm_eps"], precision)
    return np.asarray(out[:n_dec + 1])

