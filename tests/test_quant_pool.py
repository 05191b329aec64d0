"""Quantized page pool (EngineConfig.kv_quant / AttentionConfig.kv_quant).

The pool can store K/V pages (and SLA2 pooled router keys) in int8 or
fp8-e4m3 with one fp32 scale per (page, kv head, token row), written once
at page-write time.  Contracts locked here:

  * fused-vs-gather parity stays TIGHT on a quantized pool — kernel and
    jnp oracle share the exact dequant formula (``ops.dequant_rows``), so
    the quantization error cancels in the comparison;
  * quantized-vs-fp32 output error is bounded by the same noise budget as
    the existing QAT decode paths (rel < 0.05);
  * the dense decode/verify kernels' NEW QAT tile path (decode_quant_bits)
    perturbs outputs but stays inside the budget;
  * swap round-trips and prefix-cache hits are BIT-EXACT within the
    quantized representation (codes + scales travel together);
  * SwapPool accounts capacity in bytes (quantized pages pack denser) and
    the engine surfaces swap/pool telemetry in ``stats``;
  * teacher-forced NLL through the paged prefill path moves by < 0.05
    nats/token when the pool quantizes (perplexity smoke).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import attention as A
from repro.serve.scenario import make_paged_attention_state

LENGTHS = (37, 16, 70)
QUANTS = ("int8", "fp8")


def _decode(cfg, params, cache, pt, x_t, impl):
    c = dataclasses.replace(cfg, paged_impl=impl)
    lens = jnp.asarray(LENGTHS, jnp.int32)
    act = jnp.ones((len(LENGTHS),), bool)
    o, _ = A.decode_step_paged(params, c, x_t, dict(cache),
                               page_table=pt, lengths=lens, active=act)
    return np.asarray(o)


def _verify(cfg, params, cache, pt, impl):
    c = dataclasses.replace(cfg, paged_impl=impl)
    b, dm = len(LENGTHS), cfg.d_model
    x_w = jax.random.normal(jax.random.PRNGKey(9), (b, 4, dm)) * 0.3
    lens = jnp.asarray(LENGTHS, jnp.int32)
    act = jnp.ones((b,), bool)
    wl = jnp.asarray([4, 3, 4], jnp.int32)
    o, _ = A.decode_window_paged(params, c, x_w, dict(cache),
                                 page_table=pt, lengths=lens, active=act,
                                 window_len=wl)
    return np.asarray(o)


# ---------------------------------------------------------------------------
# row quantization primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_quant", QUANTS)
def test_quantize_rows_roundtrip(kv_quant):
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 64)) * 2.0
    codes, scale = ops.quantize_rows(x, kv_quant)
    assert codes.dtype == ops.kv_pool_dtype(kv_quant)
    assert scale.shape == x.shape[:-1] and scale.dtype == jnp.float32
    back = ops.dequant_rows(codes, scale)
    rel = np.max(np.abs(np.asarray(back - x))) / np.max(np.abs(np.asarray(x)))
    assert rel < (0.01 if kv_quant == "int8" else 0.07)
    # requantizing the dequantized values is a fixed point (bit-exact) —
    # the property swap/CoW round-trips rely on
    codes2, scale2 = ops.quantize_rows(back, kv_quant)
    assert np.array_equal(np.asarray(codes), np.asarray(codes2))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale2))


def test_kv_pool_dtype_rejects_unknown():
    with pytest.raises(ValueError):
        ops.kv_pool_dtype("none")
    with pytest.raises(ValueError):
        ops.quantize_rows(jnp.zeros((2, 4)), "int4")


# ---------------------------------------------------------------------------
# fused-vs-gather parity on quantized pools (decode / verify / prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mechanism", ["sla2", "full"])
@pytest.mark.parametrize("kv_quant", QUANTS)
def test_fused_matches_gather_on_quantized_pool(mechanism, kv_quant):
    cfg, params, cache, pt, x_t = make_paged_attention_state(
        mechanism=mechanism, kv_quant=kv_quant)
    o_f = _decode(cfg, params, cache, pt, x_t, "fused")
    o_g = _decode(cfg, params, cache, pt, x_t, "gather")
    np.testing.assert_allclose(o_f, o_g, atol=2e-5)
    w_f = _verify(cfg, params, cache, pt, "fused")
    w_g = _verify(cfg, params, cache, pt, "gather")
    np.testing.assert_allclose(w_f, w_g, atol=2e-5)


@pytest.mark.parametrize("mechanism", ["sla2", "full"])
@pytest.mark.parametrize("kv_quant", QUANTS)
def test_prefill_fused_matches_gather_on_quantized_pool(mechanism, kv_quant):
    """chunk_prefill_paged under the fused kernel (paged_flash_prefill with
    in-kernel dequant) writes the same pool AND emits the same chunk
    outputs as the gather oracle."""
    outs = {}
    for impl in ("fused", "gather"):
        cfg, params, cache, pt, _ = make_paged_attention_state(
            mechanism=mechanism, kv_quant=kv_quant)
        # re-prefill slot 0's prompt through the chosen impl, reusing the
        # already-populated pool pages (writes are idempotent)
        c = dataclasses.replace(cfg, paged_impl=impl)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model)) \
            * 0.3
        o, cache2 = A.chunk_prefill_paged(
            params, c, x, dict(cache), page_row=pt[0],
            offset=jnp.asarray(0, jnp.int32),
            chunk_len=jnp.asarray(32, jnp.int32),
            slot=jnp.asarray(0, jnp.int32))
        outs[impl] = (np.asarray(o),
                      np.asarray(cache2["k_pages"]),
                      np.asarray(cache2.get("k_scale", 0)))
    np.testing.assert_allclose(outs["fused"][0], outs["gather"][0],
                               atol=2e-5)
    # identical pool writes: codes and scales bit-equal across impls
    np.testing.assert_array_equal(outs["fused"][1], outs["gather"][1])
    np.testing.assert_array_equal(outs["fused"][2], outs["gather"][2])


# ---------------------------------------------------------------------------
# quantization noise bounds vs the fp32 pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mechanism", ["sla2", "full"])
@pytest.mark.parametrize("kv_quant", QUANTS)
def test_quantized_pool_noise_bounded(mechanism, kv_quant):
    cfg0, params, cache0, pt, x_t = make_paged_attention_state(
        mechanism=mechanism, kv_quant="none")
    cfg_q, _, cache_q, _, _ = make_paged_attention_state(
        mechanism=mechanism, kv_quant=kv_quant)
    base = _decode(cfg0, params, cache0, pt, x_t, "gather")
    quant = _decode(cfg_q, params, cache_q, pt, x_t, "gather")
    rel = np.max(np.abs(quant - base)) / (np.max(np.abs(base)) + 1e-9)
    assert 0.0 < rel < 0.05, rel


@pytest.mark.parametrize("quant_bits", QUANTS)
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_dense_decode_qat_tiles(quant_bits, kv_quant):
    """The dense fused decode/verify kernels now honour decode_quant_bits
    (previously fp32-only): low-bit MXU tiles perturb the output but stay
    inside the QAT noise budget, composing with pool quantization."""
    cfg, params, cache, pt, x_t = make_paged_attention_state(
        mechanism="full", kv_quant=kv_quant)
    cfg = dataclasses.replace(cfg, paged_impl="fused")
    base = _decode(cfg, params, cache, pt, x_t, "fused")
    c_q = dataclasses.replace(cfg, decode_quant_bits=quant_bits)
    out = _decode(c_q, params, cache, pt, x_t, "fused")
    rel = np.max(np.abs(out - base)) / (np.max(np.abs(base)) + 1e-9)
    assert 0.0 < rel < 0.06, rel
    basew = _verify(cfg, params, cache, pt, "fused")
    outw = _verify(c_q, params, cache, pt, "fused")
    relw = np.max(np.abs(outw - basew)) / (np.max(np.abs(basew)) + 1e-9)
    assert 0.0 < relw < 0.06, relw


# ---------------------------------------------------------------------------
# SwapPool byte accounting
# ---------------------------------------------------------------------------

def test_swap_pool_byte_accounting():
    from repro.serve.engine import SwapPool

    # unconfigured: legacy page semantics exactly
    pool = SwapPool(4)
    assert pool.capacity == 4 and pool.can_hold(4) and not pool.can_hold(5)
    pool.put(0, 3, {"x": np.zeros(3)})
    assert pool.used == 3 and pool.used_bytes == 3

    # configured: capacity = capacity_pages * REFERENCE page bytes; a
    # half-size (quantized) page packs twice as many pages into the budget
    pool = SwapPool(4)
    pool.configure_bytes(page_bytes=100, ref_page_bytes=200)
    assert pool.capacity_bytes == 800 and pool.capacity == 8
    assert pool.can_hold(8) and not pool.can_hold(9)
    pool.put(0, 5, "s")
    assert pool.used == 5 and pool.used_bytes == 500
    assert pool.can_hold(3) and not pool.can_hold(4)
    assert pool.pop(0) == "s" and pool.used_bytes == 0
    with pytest.raises(AssertionError):
        pool.put(1, 9, "too big")


def test_pool_page_bytes_walker():
    from repro.serve.engine import _pool_page_bytes

    caches = [{"attn": {
        "k_pages": np.zeros((2, 7, 2, 16, 32), np.int8),
        "v_pages": np.zeros((2, 7, 2, 16, 32), np.int8),
        "k_scale": np.zeros((2, 7, 2, 16), np.float32),
        "v_scale": np.zeros((2, 7, 2, 16), np.float32),
        "other": np.zeros((5,), np.float32),      # non-page leaf: ignored
    }}]
    actual = _pool_page_bytes(caches)
    # per page: 2 groups * (2*2*16*32 int8 codes + 2*16 f32 scales) * 2 kv
    assert actual == 2 * (2 * 2 * 16 * 32 * 1 + 2 * 2 * 16 * 4)
    ref = _pool_page_bytes(caches, reference=True)
    assert ref == 2 * (2 * 2 * 16 * 32 * 2)       # codes at 2B, no scales
    assert ref / actual > 1.7


# ---------------------------------------------------------------------------
# engine end-to-end: swap, prefix cache, telemetry, perplexity smoke
# ---------------------------------------------------------------------------

def _smoke_model():
    from repro.configs import get_smoke_config
    from repro.models.api import build_model

    cfg = get_smoke_config("qwen3_14b", n_layers=2, d_model=128, d_ff=256,
                           num_heads=4, num_kv_heads=2, head_dim=32,
                           vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _serve(model, params, reqs, **ecfg_kw):
    from repro.serve import EngineConfig, Request, ServeEngine

    eng = ServeEngine(model, EngineConfig(
        max_slots=3, max_len=128, prefill_chunk=32, paged_impl="gather",
        **ecfg_kw))
    eng.load(params)
    for i, (p, m) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=p.copy(), max_new_tokens=m))
    done = eng.run_to_completion()
    return {r.uid: list(r.output) for r in done}, eng


def test_quantized_swap_roundtrip_bit_exact():
    """Preempted slots swapped out and back in on an int8 pool produce
    token-identical outputs to recompute-from-prompt: codes + scales are
    mirrored to host and restored without requantization."""
    from repro.serve.scenario import overcommit_workload

    model, params = _smoke_model()
    work, num_pages = overcommit_workload(max_slots=3, page_size=16,
                                          n_requests=8, seed=2)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 512, n).astype(np.int32), m) for n, m in work]
    o_swap, eng = _serve(model, params, reqs, num_pages=num_pages,
                         kv_quant="int8")
    assert eng.stats["swap_outs"] > 0, "swap path not exercised"
    o_reco, eng2 = _serve(model, params, reqs, num_pages=num_pages,
                          kv_quant="int8", swap_pages=0)
    assert eng2.stats["recomputes"] > 0
    assert o_swap == o_reco


def test_engine_stats_telemetry():
    """stats carries the pool-pressure and swap telemetry: swap_bytes
    tracks SwapPool.used_bytes, pool_peak_pages the allocator high-water
    mark."""
    from repro.serve.scenario import overcommit_workload

    model, params = _smoke_model()
    work, num_pages = overcommit_workload(max_slots=3, page_size=16,
                                          n_requests=6, seed=3)
    rng = np.random.default_rng(6)
    reqs = [(rng.integers(1, 512, n).astype(np.int32), m) for n, m in work]
    _, eng = _serve(model, params, reqs, num_pages=num_pages)
    for key in ("swap_bytes", "min_available", "pool_peak_pages"):
        assert key in eng.stats
    assert eng.stats["pool_peak_pages"] == (
        eng.allocator.num_pages - 1 - eng.allocator.min_available)
    assert eng.stats["pool_peak_pages"] > 0
    assert eng.stats["swap_bytes"] == eng.swap.used_bytes
    # the swap budget reflects real byte sizes after load()
    assert eng.swap.page_bytes > 1
    # quantized pool: same page budget, bigger page capacity in swap
    _, eng_q = _serve(model, params, reqs, num_pages=num_pages,
                      kv_quant="int8")
    assert eng_q.swap.page_bytes < eng.swap.page_bytes
    assert eng_q.swap.capacity > eng.swap.capacity_pages


def test_prefix_cache_hits_identical_on_quantized_pool():
    """Prefix-cache hits (including the CoW duplicate-prompt path) on an
    int8 pool reproduce the cache-off outputs token-exactly: shared pages
    carry codes + scales, and CoW copies both."""
    model, params = _smoke_model()
    rng = np.random.default_rng(7)
    sysp = rng.integers(1, 512, 64).astype(np.int32)
    reqs = [(np.concatenate(
        [sysp, rng.integers(1, 512, 8).astype(np.int32)]), 8)
        for _ in range(5)]
    reqs.append((sysp.copy(), 8))          # exact duplicate: forces CoW
    o_on, eng = _serve(model, params, reqs, prefix_cache=True,
                       kv_quant="int8")
    o_off, _ = _serve(model, params, reqs, prefix_cache=False,
                      kv_quant="int8")
    assert eng.stats["prefix_hits"] > 0
    assert eng.stats["cow_copies"] > 0
    assert o_on == o_off


def test_perplexity_smoke_quantized_pool():
    """Teacher-forced NLL through the paged chunked-prefill path moves by
    < 0.05 nats/token between the fp32 and int8 pools (the QAT noise
    budget) — quantized serving does not change what the model believes."""
    model, params = _smoke_model()
    rng = np.random.default_rng(11)
    tokens = rng.integers(1, 512, 64).astype(np.int32)

    def nll(kvq):
        m = model.with_overrides(kv_quant=kvq) if kvq else model
        caches = m.init_paged_caches(1, 9)
        page_row = jnp.asarray(np.arange(1, 9, dtype=np.int32))
        batch = {"tokens": jnp.asarray(tokens[:32][None]),
                 "page_row": page_row,
                 "offset": jnp.asarray(0, jnp.int32),
                 "chunk_len": jnp.asarray(32, jnp.int32),
                 "slot": jnp.asarray(0, jnp.int32)}
        logits, caches = m.prefill_chunk(params, batch, caches)
        logps = []
        for pos in range(32, 56):
            lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
            logps.append(float(lp[tokens[pos]]))
            dbatch = {"token": jnp.asarray(tokens[pos:pos + 1]),
                      "page_table": page_row[None],
                      "lengths": jnp.asarray([pos], jnp.int32),
                      "active": jnp.ones((1,), bool)}
            logits, caches = m.decode_paged(params, dbatch, caches)
        return -np.mean(logps)

    base = nll(None)
    quant = nll("int8")
    assert np.isfinite(base) and np.isfinite(quant)
    assert abs(quant - base) < 0.05, (base, quant)
