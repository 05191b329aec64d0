"""Plain float32 reference of the video DiT denoiser (Wan2.1 layout with
SLA2 self-attention), written from the model's equations.  It imports
nothing of the program: it reads the weights the benchmark made, by their
names in the parameter tree, and the sizes from the configuration file.

Per layer, with adaLN modulation (shift, scale, gate) x 2 from the
timestep:
    x += g1 * SLA2(LN(x) * (1 + c1) + s1)
    x += CrossAttn(LN(x), text)
    x += g2 * MLP_gelu(LN(x) * (1 + c2) + s2)

SLA2 (bidirectional), per head, query blocks of block_q and key blocks of
block_k tokens:
    router  mean-pooled Q, K blocks through proj_q / proj_k; each query
            block keeps its top round(k_frac * T_n) key blocks
    sparse  softmax over the kept blocks' keys, int8 QAT: per-tile Q and
            K (K centred over the sequence) and V codes, P codes at 1/127
    linear  softmax-feature attention over the other blocks:
            phi(Q) sum_j h_j / phi(Q) sum_j z_j,  h_j = phi(K_j)^T V_j
    out     alpha * sparse + (1 - alpha) * linear, alpha per (head, block)

``precision='fp8'`` is the control, one precision below the bfloat16 the
model is served in: where the program holds a bf16 value (weights, the
residual stream, q/k/v, attention outputs, the MLP hidden state, every
matmul result) the control holds it in scaled float8 e4m3 (per row of
activations, per column of weights).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0,
                    1e-12)
    # e4m3fn has no inf: a quotient rounded past 448 would convert to NaN
    q = jnp.clip(x / s, -448.0, 448.0)
    return q.astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, precision):
    w = w.astype(F32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return _act(x @ w, precision)


def _act(x, precision):
    """An activation as the program holds it: bf16 there, so fp8 in the
    control; float32 in the reference."""
    return _fp8(x, -1) if precision == "fp8" else x


def _ln(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _int8(x):
    """Symmetric int8 fake quantization, one scale per tile (last 2 axes)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True) / 127.0,
                    1e-8)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _sla2_head(q, k, v, alpha, pq, pk, *, bq, bk, k_frac):
    """One head: q, k, v (N, d) f32; alpha (T_m,) in (0, 1)."""
    n, d = q.shape
    tm, tn = n // bq, n // bk
    k_sel = max(1, round(k_frac * tn))
    qb = q.reshape(tm, bq, d).mean(1) @ pq
    kb = k.reshape(tn, bk, d).mean(1) @ pk
    _, sel = jax.lax.top_k(qb @ kb.T / math.sqrt(d), k_sel)   # (tm, k_sel)

    # sparse branch over the kept blocks, int8 QAT
    qt = _int8(q.reshape(tm, bq, d))
    kt = _int8((k - k.mean(0)).reshape(tn, bk, d))
    vt = _int8(v.reshape(tn, bk, d))
    s = jnp.einsum("ibd,ijkd->ibjk", qt, kt[sel]) / math.sqrt(d)
    p = jnp.exp(s - s.max(axis=(-2, -1), keepdims=True))
    l = p.sum(axis=(-2, -1))
    pc = jnp.round(p * 127.0) / 127.0
    o_s = jnp.einsum("ibjk,ijkd->ibd", pc, vt[sel]) / l[..., None]

    # linear branch over the other blocks
    fq = jax.nn.softmax(q, -1).reshape(tm, bq, d)
    fk = jax.nn.softmax(k, -1).reshape(tn, bk, d)
    h = jnp.einsum("jkd,jke->jde", fk, v.reshape(tn, bk, d))
    z = fk.sum(1)
    other = 1.0 - jax.nn.one_hot(sel, tn, dtype=F32).sum(1)   # (tm, tn)
    num = jnp.einsum("ibd,ide->ibe", fq, jnp.einsum("ij,jde->ide", other, h))
    den = jnp.einsum("ibd,id->ib", fq, other @ z)
    o_l = num / jnp.maximum(den, 1e-30)[..., None]
    a = jnp.where(den > 1e-12, alpha[:, None], 1.0)[..., None]
    return (a * o_s + (1.0 - a) * o_l).reshape(n, d)


def _layer(x, lw, text, mod, cfg, precision):
    """x (N, d) f32, text (M, d) f32, mod (6d,) f32."""
    n, d = x.shape
    h_n, dh = cfg["num_heads"], cfg["head_dim"]
    eps = cfg["eps"]
    s = cfg["sla2"]
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6)

    y = _ln(lw["ln1"], x, eps) * (1.0 + sc1) + sh1
    q, k, v = (_mm(y, lw[w], precision).reshape(n, h_n, dh).transpose(1, 0, 2)
               for w in ("wq", "wk", "wv"))
    alpha = jax.nn.sigmoid(lw["sla2"]["alpha_logit"].astype(F32))
    pq = lw["sla2"]["router"]["proj_q"].astype(F32)
    pk = lw["sla2"]["router"]["proj_k"].astype(F32)
    head = functools.partial(_sla2_head, pq=pq, pk=pk, bq=s["block_q"],
                             bk=s["block_k"], k_frac=s["k_frac"])
    o = jax.lax.map(lambda a: head(*a), (q, k, v, alpha[:, :n // s["block_q"]]))
    o = _act(o, precision)
    x = _act(x + g1 * _mm(o.transpose(1, 0, 2).reshape(n, h_n * dh), lw["wo"],
                     precision), precision)

    y = _ln(lw["ln_x"], x, eps)
    q = _mm(y, lw["xq"], precision).reshape(n, h_n, dh).transpose(1, 0, 2)
    m = text.shape[0]
    k = _mm(text, lw["xk"], precision).reshape(m, h_n, dh).transpose(1, 0, 2)
    v = _mm(text, lw["xv"], precision).reshape(m, h_n, dh).transpose(1, 0, 2)
    att = jax.nn.softmax(jnp.einsum("hnd,hmd->hnm", q, k) / math.sqrt(dh), -1)
    o = jnp.einsum("hnm,hmd->hnd", att, v).transpose(1, 0, 2).reshape(n, -1)
    x = _act(x + _mm(_act(o, precision), lw["xo"], precision), precision)

    y = _ln(lw["ln2"], x, eps) * (1.0 + sc2) + sh2
    mlp = lw["mlp"]
    y = _act(_gelu_tanh(_mm(y, mlp["w_up"], precision)), precision)
    return _act(x + g2 * _mm(y, mlp["w_down"], precision), precision)


def _t_embed(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t * freqs * 1000.0
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _velocity(w, x, text, t, cfg_key, precision):
    cfg = dict(cfg_key)
    cfg["sla2"] = dict(cfg["sla2"])
    with jax.default_matmul_precision("highest"):
        te = _t_embed(t, cfg["freq_dim"])
        te = jax.nn.silu(te @ w["t_mlp"]["w1"].astype(F32)) \
            @ w["t_mlp"]["w2"].astype(F32)
        h = _mm(x, w["patch_in"]["w"], precision) \
            + w["patch_in"]["b"].astype(F32)
        tx = text

        def body(h, lw):
            mod = te @ lw["ada"]["w"].astype(F32) + lw["ada"]["b"].astype(F32)
            return _layer(h, lw, tx, mod, cfg, precision), None

        h, _ = jax.lax.scan(body, h, w["blocks"])
        mod = te @ w["final_ada"]["w"].astype(F32) \
            + w["final_ada"]["b"].astype(F32)
        sh, sc = jnp.split(mod, 2)
        h = _ln(w["final_ln"], h, cfg["eps"]) * (1.0 + sc) + sh
        return _mm(h, w["patch_out"]["w"], precision) \
            + w["patch_out"]["b"].astype(F32)


def _key(cfg: dict):
    keys = ("num_heads", "head_dim", "eps", "freq_dim")
    return tuple((k, cfg[k]) for k in keys) + (
        ("sla2", tuple(sorted(cfg["sla2"].items()))),)


def denoise(weights, cfg: dict, latents, text, n_steps: int,
            precision: str = "fp32") -> np.ndarray:
    """Rectified-flow Euler sampling, t_i = 1 - i / n, x -= v(x, t_i) / n."""
    x = jnp.asarray(latents, F32)
    tx = jnp.asarray(text, F32)
    for i in range(n_steps):
        v = _velocity(weights, x, tx, jnp.asarray(1.0 - i / n_steps, F32),
                      _key(cfg), precision)
        x = x - v / n_steps
    return np.asarray(x, np.float64)
