"""Plain float32 reference of the paged SLA2 language model (InternLM2
layout: RMSNorm, GQA attention with RoPE, SwiGLU MLP, untied head),
written from the model's equations.  It imports nothing of the program:
it reads the benchmark's weights by their names in the parameter tree.

It returns the logits the served tokens were chosen from, over one
sequence (prompt + served tokens but the last).  Positions of the prompt
attend exactly (causal softmax): the engine's chunked prefill is exact.
Each later position is one decode step, which attends by SLA2:

    router   per KV head, the mean over its query heads of q proj_q
             against each visible key block's mean key (the current,
             partial block: the mean of its tokens so far) through proj_k;
             the current block is always kept, then the top
             round(k_frac * max_len / block_k) blocks
    sparse   softmax over the kept blocks' visible tokens
    linear   softmax-feature attention over the complete blocks not kept
    out      alpha * sparse + (1 - alpha) * linear (alpha of the last
             query block; alpha = 1 when no complete block is left over)

``precision='fp8'`` is the control, one precision below the bfloat16 the
model is served in: where the program holds a bf16 value (weights, the
residual stream, q/k/v, attention outputs, the MLP hidden state, every
matmul result but the f32 logits) the control holds it in scaled float8
e4m3 (per row of activations, per column of weights).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0,
                    1e-12)
    # e4m3fn has no inf: a quotient rounded past 448 would convert to NaN
    q = jnp.clip(x / s, -448.0, 448.0)
    return q.astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, precision, act=True):
    w = w.astype(F32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return _act(x @ w, precision) if act else x @ w


def _act(x, precision):
    """An activation as the program holds it: bf16 there, so fp8 in the
    control; float32 in the reference."""
    return _fp8(x, -1) if precision == "fp8" else x


def _rms(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def _rope(x, pos, theta):
    """Rotate interleaved pairs (x[2i], x[2i+1]) by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def _dense(q, k, v, chunk=256):
    """Causal softmax attention for every position.  q (L, H, d);
    k, v (L, H, d) already repeated over the query groups."""
    L, H, d = q.shape

    def one(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk)
        s = jnp.einsum("chd,mhd->hcm", qc, k) / math.sqrt(d)
        vis = jnp.arange(L)[None, :] <= (i * chunk + jnp.arange(chunk))[:, None]
        p = jax.nn.softmax(jnp.where(vis[None], s, NEG), -1)
        return jnp.einsum("hcm,mhd->chd", p, v)

    return jax.lax.map(one, jnp.arange(L // chunk)).reshape(L, H, d)


def _decode(q, k, v, sla, P, n_pos, *, bk, k_sel, chunk=128):
    """SLA2 decode attention for positions P .. P + n_pos - 1.
    q (L, H, d); k, v (L, Hkv, d)."""
    L, H, d = q.shape
    hkv = k.shape[1]
    rep = H // hkv
    nb = L // bk
    kb = k.reshape(nb, bk, hkv, d).transpose(2, 0, 1, 3)      # (hkv, nb, bk, d)
    vb = v.reshape(nb, bk, hkv, d).transpose(2, 0, 1, 3)
    kbar = kb.mean(2)                                         # (hkv, nb, d)
    csum = jnp.concatenate([jnp.zeros((1, hkv, d), F32), jnp.cumsum(k, 0)])
    fk = jax.nn.softmax(kb, -1)
    h = jnp.einsum("gjkd,gjke->jgde", fk, vb)                 # (nb, hkv, d, d)
    hpre = jnp.concatenate([jnp.zeros((1,) + h.shape[1:], F32),
                            jnp.cumsum(h, 0)])
    zpre = jnp.concatenate([jnp.zeros((1, hkv, d), F32),
                            jnp.cumsum(fk.sum(2).transpose(1, 0, 2), 0)])
    pq = sla["router"]["proj_q"].astype(F32)
    pk = sla["router"]["proj_k"].astype(F32)
    alpha = jax.nn.sigmoid(sla["alpha_logit"][:, -1].astype(F32)).reshape(
        hkv, rep)
    g_ix = jnp.arange(hkv)[None, :, None]

    def one(i):
        t = jnp.minimum(P + i * chunk + jnp.arange(chunk), L - 1)  # (c,)
        cur = t // bk
        part = (csum[t + 1] - csum[cur * bk]) / (t - cur * bk + 1)[:, None,
                                                                    None]
        j = jnp.arange(nb)
        pooled = jnp.where((j[None, :] == cur[:, None])[..., None, None],
                           part[:, None], kbar.transpose(1, 0, 2)[None])
        qt = q[t].reshape(-1, hkv, rep, d)
        qr = (qt @ pq).mean(2)                                   # (c, hkv, d)
        sc = jnp.einsum("cgd,cjgd->cgj", qr, pooled @ pk) / math.sqrt(d)
        sc = jnp.where(j[None, None] <= cur[:, None, None], sc, NEG)
        sc = jnp.where(j[None, None] == cur[:, None, None], jnp.inf, sc)
        top, idx = jax.lax.top_k(sc, k_sel)                      # (c, hkv, s)
        valid = top > NEG / 2
        ks, vs = kb[g_ix, idx], vb[g_ix, idx]                    # (c,g,s,bk,d)
        tok = idx[..., None] * bk + jnp.arange(bk)
        vis = valid[..., None] & (tok <= t[:, None, None, None])
        s = jnp.einsum("cgrd,cgskd->cgrsk", qt, ks) / math.sqrt(d)
        s = jnp.where(vis[:, :, None], s, NEG)
        p = jax.nn.softmax(s.reshape(*s.shape[:3], -1), -1).reshape(s.shape)
        o_s = jnp.einsum("cgrsk,cgskd->cgrd", p, vs)
        # linear branch: complete blocks minus the kept complete ones
        n_full = (t + 1) // bk
        kept = valid & (idx < n_full[:, None, None])
        fq = jax.nn.softmax(qt, -1)
        ls = jnp.einsum("cgrd,cgskd->cgrsk", fq, jax.nn.softmax(ks, -1))
        ls = ls * kept[:, :, None, :, None]
        num = jnp.einsum("cgrd,cgde->cgre", fq, hpre[n_full]) \
            - jnp.einsum("cgrsk,cgskd->cgrd", ls, vs)
        den = jnp.einsum("cgrd,cgd->cgr", fq, zpre[n_full]) - ls.sum((-2, -1))
        left = (n_full[:, None] - kept.sum(-1)) > 0              # (c, hkv)
        o_l = jnp.where(left[..., None, None],
                        num / jnp.maximum(den, 1e-30)[..., None], 0.0)
        a = jnp.where(left[..., None], alpha[None], 1.0)[..., None]
        return (a * o_s + (1.0 - a) * o_l).reshape(-1, H, d)

    return jax.lax.map(one, jnp.arange(n_pos // chunk)).reshape(n_pos, H, d)


@functools.partial(jax.jit, static_argnames=("cfg_key", "n_pos", "precision"))
def _layer(x, groups, i, P, cfg_key, n_pos, precision):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: a[i], groups["l0"])
        at = lw["attn"]
        L = x.shape[0]
        H, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
        y = _rms(lw["ln1"], x, cfg["eps"])
        pos = jnp.arange(L)
        q = _rope(_mm(y, at["wq"], precision).reshape(L, H, d), pos,
                  cfg["theta"])
        k = _rope(_mm(y, at["wk"], precision).reshape(L, hkv, d), pos,
                  cfg["theta"])
        v = _mm(y, at["wv"], precision).reshape(L, hkv, d)
        o = _dense(q, jnp.repeat(k, H // hkv, 1), jnp.repeat(v, H // hkv, 1))
        od = _decode(q, k, v, at["sla2"], P, n_pos, bk=cfg["block_k"],
                     k_sel=cfg["k_sel"])
        o = _act(jax.lax.dynamic_update_slice(o, od, (P, 0, 0)), precision)
        x = _act(x + _mm(o.reshape(L, H * d), at["wo"], precision), precision)
        y = _rms(lw["ln2"], x, cfg["eps"])
        m = lw["mlp"]
        g = _mm(y, m["w_gate"], precision)
        h = _act(jax.nn.silu(g) * _mm(y, m["w_up"], precision), precision)
        return _act(x + _mm(h, m["w_down"], precision), precision)


@functools.partial(jax.jit, static_argnames=("n_rows", "eps", "precision"))
def _head(x, norm, head, P, n_rows, eps, precision):
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.dynamic_slice_in_dim(x, P - 1, n_rows)
        return _mm(_rms(norm, rows, eps), head, precision, act=False)


def _bucket(n: int) -> int:
    return max(128, 1 << (max(n, 1) - 1).bit_length())


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
           ("kv_heads", cfg["num_key_value_heads"]),
           ("head_dim", cfg["head_dim"]), ("eps", cfg["rms_norm_eps"]),
           ("theta", float(cfg["rope_theta"])), ("block_k", bk),
           ("k_sel", max(1, round(cfg["sla2"]["k_frac"] * (max_len // bk)))))
    x = _act(jnp.asarray(weights["embed"]["table"])[jnp.asarray(tokens)]
             .astype(F32), precision)
    P = jnp.asarray(n_prompt, jnp.int32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, weights["groups"], jnp.asarray(i, jnp.int32), P, key,
                   n_pos, precision)
    out = _head(x, weights["final_norm"], weights["lm_head"], P, n_pos + 1,
                cfg["rms_norm_eps"], precision)
    return np.asarray(out[:n_dec + 1])
