"""Pallas kernel allclose sweeps vs kernels/ref.py oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import router as routerlib
from repro.core.router import RouterConfig
from repro.core.quant import smooth_k
from repro.kernels import ref as kref
from repro.kernels.sla2_fwd import sparse_flash_fwd
from repro.kernels.sla2_bwd import sparse_flash_bwd, sort_pairs


def make_qkv(bh, n, d, dtype=jnp.float32, scale=0.5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (bh, n, d), dtype) * scale for k in ks]


def route(q, k, bq, bk, k_frac, causal):
    rc = RouterConfig(block_q=bq, block_k=bk, k_frac=k_frac, causal=causal)
    return routerlib.route_indices({}, q, k, rc)


SHAPES = [
    # (bh, n, d, bq, bk, k_frac); the paper-tile 512-token shape is
    # interpret-mode-slow and runs in the slow tier
    (2, 256, 64, 32, 16, 0.3),
    (1, 256, 128, 64, 32, 0.2),
    (3, 128, 32, 16, 16, 0.5),
    pytest.param((1, 512, 64, 128, 64, 0.1), marks=pytest.mark.slow),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_fwd_matches_oracle(shape, causal):
    bh, n, d, bq, bk, kf = shape
    q, k, v = make_qkv(bh, n, d)
    idx, valid = route(q, k, bq, bk, kf, causal)
    o, lse = sparse_flash_fwd(q, k, v, idx, valid.astype(jnp.int32),
                              block_q=bq, block_k=bk, causal=causal)
    o_r, lse_r = kref.sparse_flash_ref(q, k, v, idx, valid,
                                       block_q=bq, block_k=bk, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
def test_fwd_dtypes(dtype, causal):
    bh, n, d, bq, bk, kf = 2, 256, 64, 32, 16, 0.25
    q, k, v = make_qkv(bh, n, d, dtype)
    idx, valid = route(q, k, bq, bk, kf, causal)
    o, lse = sparse_flash_fwd(q, k, v, idx, valid.astype(jnp.int32),
                              block_q=bq, block_k=bk, causal=causal)
    o_r, _ = kref.sparse_flash_ref(q, k, v, idx, valid,
                                   block_q=bq, block_k=bk, causal=causal)
    assert o.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("bits", ["int8", "fp8"])
def test_fwd_quantized_close_to_fp(bits):
    bh, n, d, bq, bk, kf = 2, 256, 64, 32, 16, 0.3
    q, k, v = make_qkv(bh, n, d)
    idx, valid = route(q, k, bq, bk, kf, True)
    ks = smooth_k(k)
    o_q, _ = sparse_flash_fwd(q, ks, v, idx, valid.astype(jnp.int32),
                              block_q=bq, block_k=bk, causal=True,
                              quant_bits=bits)
    o_fp, _ = kref.sparse_flash_ref(q, ks, v, idx, valid,
                                    block_q=bq, block_k=bk, causal=True)
    rel = float(jnp.linalg.norm(o_q - o_fp) / jnp.linalg.norm(o_fp))
    assert np.isfinite(np.asarray(o_q)).all()
    assert rel < (0.02 if bits == "int8" else 0.06), rel


def test_smoothing_improves_int8():
    """SageAttention claim: K-smoothing reduces INT8 attention error."""
    bh, n, d, bq, bk = 2, 256, 64, 32, 16
    q, k, v = make_qkv(bh, n, d)
    k = k + 3.0  # channel offset -> outliers for symmetric quantization
    idx, valid = route(q, k, bq, bk, 0.3, False)
    o_fp, _ = kref.sparse_flash_ref(q, k, v, idx, valid,
                                    block_q=bq, block_k=bk, causal=False)
    o_raw, _ = sparse_flash_fwd(q, k, v, idx, valid.astype(jnp.int32),
                                block_q=bq, block_k=bk, causal=False,
                                quant_bits="int8")
    o_sm, _ = sparse_flash_fwd(q, smooth_k(k), v, idx, valid.astype(jnp.int32),
                               block_q=bq, block_k=bk, causal=False,
                               quant_bits="int8")
    err_raw = float(jnp.linalg.norm(o_raw - o_fp))
    err_sm = float(jnp.linalg.norm(o_sm - o_fp))
    assert err_sm < err_raw


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_bwd_matches_manual_and_autodiff(shape, causal):
    bh, n, d, bq, bk, kf = shape
    q, k, v = make_qkv(bh, n, d)
    do = jax.random.normal(jax.random.PRNGKey(7), (bh, n, d), jnp.float32)
    idx, valid = route(q, k, bq, bk, kf, causal)
    o, lse = sparse_flash_fwd(q, k, v, idx, valid.astype(jnp.int32),
                              block_q=bq, block_k=bk, causal=causal)
    dq, dk, dv = sparse_flash_bwd(q, k, v, idx, valid.astype(jnp.int32),
                                  o, lse, do, block_q=bq, block_k=bk,
                                  causal=causal)
    dq_r, dk_r, dv_r = kref.manual_backward(
        q, k, v, idx, valid, o, lse, do, block_q=bq, block_k=bk, causal=causal)
    for a, b in [(dq, dq_r), (dk, dk_r), (dv, dv_r)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)

    def f(q_, k_, v_):
        o_, _ = kref.sparse_flash_ref(q_, k_, v_, idx, valid,
                                      block_q=bq, block_k=bk, causal=causal)
        return (o_ * do).sum()

    gq, gk, gv = jax.grad(f, (0, 1, 2))(q, k, v)
    for a, b in [(dq, gq), (dk, gk), (dv, gv)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_sort_pairs_monotonic_and_complete():
    bh, t_m, k_sel, t_n = 3, 8, 3, 16
    key = jax.random.PRNGKey(0)
    scores = jax.random.normal(key, (bh, t_m, t_n))
    _, idx = jax.lax.top_k(scores, k_sel)
    idx = jnp.sort(idx, axis=-1).astype(jnp.int32)
    valid = jnp.ones_like(idx)
    js, is_, vs = sort_pairs(idx, valid)
    js_np, is_np = np.asarray(js), np.asarray(is_)
    assert (np.diff(js_np, axis=-1) >= 0).all()  # monotonic writes
    for b in range(bh):
        got = set(zip(js_np[b].tolist(), is_np[b].tolist()))
        want = set()
        idx_np = np.asarray(idx)
        for i in range(t_m):
            for jj in range(k_sel):
                want.add((int(idx_np[b, i, jj]), i))
        assert got == want


def test_full_op_kernel_vs_ref_paths():
    from repro.core.sla2 import SLA2Config, init_sla2_params, sla2_attention
    B, H, N, D = 2, 2, 128, 64
    bq, bk = 32, 16
    q, k, v = [jax.random.normal(jax.random.PRNGKey(i), (B, H, N, D)) * 0.5
               for i in range(3)]
    for causal in (False, True):
        rc = RouterConfig(block_q=bq, block_k=bk, k_frac=0.3, causal=causal)
        cfg_r = SLA2Config(router=rc, quant_bits="none", impl="ref")
        cfg_k = SLA2Config(router=rc, quant_bits="none", impl="kernel")
        p = init_sla2_params(jax.random.PRNGKey(0), head_dim=D, num_heads=H,
                             n_q_blocks=N // bq, cfg=cfg_r)
        o_r = sla2_attention(p, q, k, v, cfg_r)
        o_k = sla2_attention(p, q, k, v, cfg_k)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gather_impl_matches_ref_and_kernel(causal):
    """The three execution paths (ref / gather / Pallas-interpret) agree
    exactly at fp32; the fused single-pass gather variant agrees with the
    two-pass gather."""
    from repro.core.sla2 import SLA2Config, init_sla2_params, sla2_attention
    B, H, N, D = 2, 2, 128, 64
    bq, bk = 32, 16
    q, k, v = [jax.random.normal(jax.random.PRNGKey(i), (B, H, N, D)) * 0.5
               for i in range(3)]
    rc = RouterConfig(block_q=bq, block_k=bk, k_frac=0.3, causal=causal)
    p = init_sla2_params(jax.random.PRNGKey(0), head_dim=D, num_heads=H,
                         n_q_blocks=N // bq,
                         cfg=SLA2Config(router=rc))
    outs = {}
    for impl in ("ref", "gather", "kernel"):
        cfg = SLA2Config(router=rc, quant_bits="none", impl=impl, q_chunk=3)
        outs[impl] = np.asarray(sla2_attention(p, q, k, v, cfg))
    np.testing.assert_allclose(outs["gather"], outs["ref"], atol=5e-5)
    np.testing.assert_allclose(outs["gather"], outs["kernel"], atol=5e-5)
    fused = sla2_attention(p, q, k, v, SLA2Config(
        router=rc, quant_bits="none", impl="gather", q_chunk=3,
        fuse_branches=True))
    np.testing.assert_allclose(np.asarray(fused), outs["gather"], atol=5e-5)


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_quant_paths_agree_within_qat_noise(quant):
    """All low-bit paths sit within quantization noise of fp32 truth and
    of each other (different accumulation orders)."""
    from repro.core.sla2 import SLA2Config, init_sla2_params, sla2_attention
    B, H, N, D = 2, 2, 256, 64
    rc = RouterConfig(block_q=32, block_k=16, k_frac=0.3, causal=False)
    q, k, v = [jax.random.normal(jax.random.PRNGKey(i), (B, H, N, D)) * 0.5
               for i in range(3)]
    p = init_sla2_params(jax.random.PRNGKey(0), head_dim=D, num_heads=H,
                         n_q_blocks=8, cfg=SLA2Config(router=rc))
    truth = sla2_attention(p, q, k, v, SLA2Config(
        router=rc, quant_bits="none", impl="gather"))
    tn = np.linalg.norm(np.asarray(truth))
    for impl in ("gather", "kernel"):
        o = sla2_attention(p, q, k, v, SLA2Config(
            router=rc, quant_bits=quant, impl=impl))
        rel = np.linalg.norm(np.asarray(o) - np.asarray(truth)) / tn
        assert rel < 0.05, (impl, quant, rel)


# ---------------------------------------------------------------------------
# paged serving kernels (sla2_decode_paged)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_rep", [1, 2])
def test_paged_flash_prefill_matches_dense(n_rep):
    """paged_flash_prefill reads K/V pages through the page table and must
    equal dense causal attention over the gathered logical view."""
    from repro.kernels.sla2_decode_paged import paged_flash_prefill

    hkv, dh, bk, max_p, c = 2, 32, 16, 6, 24
    h = hkv * n_rep
    num_pages = 10
    offset = 33                                  # chunk starts mid-page
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (h, c, dh)) * 0.5
    k_pages = jax.random.normal(ks[1], (num_pages, hkv, bk, dh)) * 0.5
    v_pages = jax.random.normal(ks[2], (num_pages, hkv, bk, dh)) * 0.5
    # logical blocks 0..3 cover positions [0, 64) > offset + c = 57
    page_row = jnp.array([7, 3, 9, 5, 0, 0], jnp.int32)

    o = paged_flash_prefill(q, k_pages, v_pages, page_row,
                            offset=jnp.asarray(offset, jnp.int32),
                            block_k=bk, n_rep=n_rep)

    # dense reference over the gathered logical view
    kv_h = jnp.repeat(jnp.arange(hkv), n_rep)    # q head -> kv head
    k_all = k_pages[page_row].transpose(1, 0, 2, 3).reshape(hkv, -1, dh)
    v_all = v_pages[page_row].transpose(1, 0, 2, 3).reshape(hkv, -1, dh)
    s = jnp.einsum("hcd,hmd->hcm", q, k_all[kv_h]) / jnp.sqrt(dh)
    rows = offset + jnp.arange(c)
    cols = jnp.arange(max_p * bk)
    s = jnp.where(rows[:, None] >= cols[None, :], s, -1e30)
    o_ref = jnp.einsum("hcm,hmd->hcd", jax.nn.softmax(s, axis=-1),
                       v_all[kv_h])
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_sla2_decode_fused_skips_invalid_pages():
    """Invalid routed entries (valid=0, phys=0 trash duplicates) contribute
    nothing: padding the routed set with invalid entries is a no-op."""
    from repro.kernels.sla2_decode_paged import sla2_decode_fused

    b, hkv, n_rep, dh, bk = 2, 2, 2, 16, 8
    num_pages = 6
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, hkv, n_rep, dh)) * 0.5
    k_pages = jax.random.normal(ks[1], (num_pages, hkv, bk, dh)) * 0.5
    v_pages = jax.random.normal(ks[2], (num_pages, hkv, bk, dh)) * 0.5
    h_tot = jnp.zeros((b, hkv, dh, dh))
    z_tot = jnp.zeros((b, hkv, dh))
    alpha = jnp.full((b, hkv, n_rep), 4.0)       # sigmoid ~ 1: sparse only
    t_new = jnp.array([17, 9], jnp.int32)

    def run(phys, jlog, valid):
        comp = jnp.zeros_like(valid)
        return np.asarray(sla2_decode_fused(
            q, k_pages, v_pages, phys, jlog, valid, comp, t_new,
            h_tot, z_tot, alpha, block_k=bk))

    phys = jnp.array([[[3, 1], [2, 4]], [[5, 1], [3, 2]]], jnp.int32)
    jlog = jnp.array([[[0, 2], [1, 2]], [[0, 1], [0, 1]]], jnp.int32)
    valid = jnp.ones((b, hkv, 2), jnp.int32)
    o = run(phys, jlog, valid)

    pad = lambda x, v: jnp.concatenate([x, jnp.full_like(x[..., :1], v)], -1)
    o_pad = run(pad(phys, 0), pad(jlog, 0), pad(valid, 0))
    np.testing.assert_allclose(o_pad, o, atol=2e-5)


# ---------------------------------------------------------------------------
# SMEM row groups: a scalar-prefetch table too large for one call splits
# the kernel's leading rows over several calls with identical results
# ---------------------------------------------------------------------------

def test_row_groups_fit_budget_and_cover_rows():
    from repro.kernels.ops import SMEM_PREFETCH_BYTES, row_groups
    for rows, words in [(24, 256 * 26), (48, 256 * 26), (64, 53), (7, 10**6)]:
        groups = row_groups(rows, words)
        assert [s for s, _ in groups] == list(range(0, rows, groups[0][1]))
        assert sum(n for _, n in groups) == rows
        size = groups[0][1]
        assert size == 1 or size * words * 4 <= SMEM_PREFETCH_BYTES
    assert row_groups(24, 256 * 26) == [(0, 12), (12, 12)]


def _one_row_per_call(monkeypatch, words_per_row):
    """Shrink the SMEM budget so every row group holds a single row."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "SMEM_PREFETCH_BYTES", 4 * words_per_row)


def _retraced(entry, **static):
    """``entry`` jitted afresh, so it traces under the patched budget."""
    return jax.jit(functools.partial(entry.__wrapped__, **static))


@pytest.mark.parametrize("causal", [False, True])
def test_sparse_flash_split_rows_match_one_call(monkeypatch, causal):
    bh, n, d, bq, bk, kf = 3, 128, 32, 32, 16, 0.3
    q, k, v = make_qkv(bh, n, d, seed=3)
    do = jax.random.normal(jax.random.PRNGKey(8), (bh, n, d), jnp.float32)
    idx, valid = route(q, k, bq, bk, kf, causal)
    valid = valid.astype(jnp.int32)
    kw = dict(block_q=bq, block_k=bk, causal=causal)
    whole = sparse_flash_fwd(q, k, v, idx, valid, **kw)
    grads = sparse_flash_bwd(q, k, v, idx, valid, *whole, do, **kw)
    _one_row_per_call(monkeypatch, idx.shape[1] * idx.shape[2])
    split = _retraced(sparse_flash_fwd, **kw)(q, k, v, idx, valid)
    split_grads = _retraced(sparse_flash_bwd, **kw)(q, k, v, idx, valid,
                                                    *split, do)
    for a, b in zip((*whole, *grads), (*split, *split_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Sparse forward: one grid step walks G routed key blocks, copied from HBM
# into double-buffered VMEM; G follows the shapes against a VMEM budget
# ---------------------------------------------------------------------------

def test_kv_tiles_per_step_fits_budget():
    from repro.kernels.ops import (MAX_KV_TILES_PER_STEP,
                                   VMEM_KV_BUFFER_BYTES, kv_tiles_per_step)
    # wan-dit-1.3b: 24 rows of 32,768 tokens, 128 x 64 blocks, d 128, bf16
    assert kv_tiles_per_step(round(0.05 * 32768 / 64), 64, 128,
                             jnp.bfloat16) == 26
    for k_sel, bk, d, dt in [(1, 16, 32, jnp.float32), (26, 64, 128,
                             jnp.float32), (102, 64, 128, jnp.bfloat16),
                             (128, 64, 128, jnp.int8), (512, 16, 128,
                             jnp.bfloat16), (410, 128, 256, jnp.float32),
                             (7, 4096, 512, jnp.float32)]:
        g = kv_tiles_per_step(k_sel, bk, d, dt)
        tile_bytes = 2 * 2 * bk * d * jnp.dtype(dt).itemsize
        cap = max(1, min(MAX_KV_TILES_PER_STEP,
                         VMEM_KV_BUFFER_BYTES // tile_bytes))
        assert 1 <= g <= min(k_sel, MAX_KV_TILES_PER_STEP)
        assert g == 1 or g * tile_bytes <= VMEM_KV_BUFFER_BYTES
        # as few chunks as the budget allows
        assert -(-k_sel // g) == -(-k_sel // cap)


def _kv_tiles(monkeypatch, g, block_k, d, dtype):
    """Shrink the VMEM budget to ``g`` double-buffered K/V tiles."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "VMEM_KV_BUFFER_BYTES",
                        g * 2 * 2 * block_k * d * jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("quant_bits", ["none", "int8"])
@pytest.mark.parametrize("causal", [False, True])
def test_sparse_flash_fwd_same_for_any_g(monkeypatch, g, quant_bits, causal):
    from repro.kernels.ops import kv_tiles_per_step
    bh, n, d, bq, bk, kf = 2, 256, 32, 32, 16, 0.3
    q, k, v = make_qkv(bh, n, d, seed=5)
    if quant_bits != "none":
        k = smooth_k(k)
    idx, valid = route(q, k, bq, bk, kf, causal)
    valid = valid.astype(jnp.int32)
    k_sel = idx.shape[-1]
    kw = dict(block_q=bq, block_k=bk, causal=causal, quant_bits=quant_bits)
    assert kv_tiles_per_step(k_sel, bk, d, q.dtype) == k_sel
    whole = sparse_flash_fwd(q, k, v, idx, valid, **kw)
    _kv_tiles(monkeypatch, g, bk, d, q.dtype)
    # G = 1 walks one block a step; G = 2 leaves the last of k_sel = 5
    # chunks with a surplus entry
    assert k_sel == 5 and kv_tiles_per_step(k_sel, bk, d, q.dtype) == g
    chunked = _retraced(sparse_flash_fwd, **kw)(q, k, v, idx, valid)
    for a, b in zip(whole, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("g", [None, 1])
@pytest.mark.parametrize("dma", ["eager", "on_wait"])
def test_sparse_flash_fwd_padding_starts_no_copy(monkeypatch, g, dma):
    """Padding entries point past the keys: the TPU interpreter raises on
    any copy they start, and reads NaN from a tile never copied in."""
    from jax.experimental.pallas import tpu as pltpu
    bh, n, d, bq, bk, kf = 2, 128, 32, 16, 16, 0.3
    q, k, v = make_qkv(bh, n, d, seed=6)
    idx, valid = route(q, k, bq, bk, kf, True)
    assert not bool(valid.all())
    poisoned = jnp.where(valid, idx, n // bk + 7)
    if g:
        _kv_tiles(monkeypatch, g, bk, d, q.dtype)
    kw = dict(block_q=bq, block_k=bk, causal=True,
              interpret=pltpu.InterpretParams(dma_execution_mode=dma))
    fwd = _retraced(sparse_flash_fwd, **kw)
    o, lse = fwd(q, k, v, poisoned, valid.astype(jnp.int32))
    o_r, lse_r = kref.sparse_flash_ref(q, k, v, idx, valid, block_q=bq,
                                       block_k=bk, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(Exception, match="ut-of-bounds"):
        jax.block_until_ready(fwd(q, k, v, poisoned,
                                  jnp.ones_like(valid, jnp.int32)))
