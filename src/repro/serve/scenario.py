"""Small paged-attention state builders shared by the tests.

``make_paged_attention_state`` drives the REAL chunked-prefill path
(``models/attention.chunk_prefill_paged``) to populate a multi-slot page
pool with ragged per-slot lengths — the canonical fixture for fused-vs-
gather parity checks (tests/test_parity.py, tests/test_quant_pool.py).

``overcommit_workload`` builds the forced-preemption serving scenario for
the scheduler tests: a mixed-length (prompt, max_new) work list plus a
page-pool size deliberately below the workload's worst-case concurrent
page demand by an ``overcommit`` factor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A


def overcommit_workload(*, max_slots: int, page_size: int,
                        overcommit: float = 2.0, n_requests: int = 12,
                        seed: int = 0) -> tuple[list, int]:
    """A mixed-length work list whose concurrent worst-case page demand
    exceeds the returned pool size by ~``overcommit``x.

    Returns ``(work, num_pages)`` where ``work`` is a list of
    (prompt_len, max_new_tokens) pairs (feed to ``make_mixed_requests``)
    and ``num_pages`` sizes the engine pool (including the trash page) so
    that ``max_slots`` concurrent requests need ~overcommit x the usable
    pages — guaranteeing the scheduler preempts."""
    rng = np.random.default_rng(seed)
    work = []
    for _ in range(n_requests):
        # decode-heavy mix: sub-page prompts, 2-4 pages of decode — page
        # demand grows lazily during decode
        n_prompt = int(rng.integers(6, page_size))
        max_new = int(rng.integers(2, 5)) * page_size
        work.append((n_prompt, max_new))
    pages_per = [-(-(n + m) // page_size) for n, m in work]
    # worst concurrent demand: the max_slots hungriest requests at once
    demand = sum(sorted(pages_per, reverse=True)[:max_slots])
    usable = max(max(pages_per), int(round(demand / overcommit)))
    return work, usable + 1


def make_paged_attention_state(hkv: int = 2, lengths=(37, 16, 70), *,
                               num_heads: int = 4, d_model: int = 64,
                               head_dim: int = 16, max_p: int = 8,
                               seed: int = 0, mechanism: str = "sla2",
                               sliding_window=None, kv_quant: str = "none"):
    """Build (cfg, params, cache, page_table, x_t) for one attention
    layer (``mechanism`` sla2 by default; 'full' builds the dense paged
    baseline, optionally sliding-windowed): per-slot prompts of
    ``lengths`` tokens prefilled chunk by chunk into a shared pool (trash
    page 0, pages allocated densely per slot), plus a random decode-step
    input ``x_t`` of shape (B, 1, d_model).  ``kv_quant`` selects the
    pool storage dtype ('none' | 'int8' | 'fp8') — quantized pools carry
    per-row scale arrays and all reads dequantize."""
    cfg = A.AttentionConfig(
        d_model=d_model, num_heads=num_heads, num_kv_heads=hkv,
        head_dim=head_dim, mechanism=mechanism, block_q=32, block_k=16,
        k_frac=0.25, n_q_blocks=8, sliding_window=sliding_window,
        kv_quant=kv_quant)
    params = A.init_attention(jax.random.PRNGKey(seed), cfg)
    b = len(lengths)
    pt = np.zeros((b, max_p), np.int32)
    alloc = 1
    for s, n in enumerate(lengths):
        for lg in range(n // cfg.block_k + 1):
            pt[s, lg] = alloc
            alloc += 1
    cache = A.init_paged_cache(cfg, alloc + 2, b, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (b, 96, d_model)) * 0.3
    for s, n in enumerate(lengths):
        off = 0
        while off < n:
            c = min(32, n - off)
            xi = jnp.zeros((1, 32, d_model)).at[:, :c].set(
                x[s, off:off + c][None])
            _, cache = A.chunk_prefill_paged(
                params, cfg, xi, cache, page_row=jnp.asarray(pt[s]),
                offset=jnp.asarray(off, jnp.int32),
                chunk_len=jnp.asarray(c, jnp.int32),
                slot=jnp.asarray(s, jnp.int32))
            off += c
    x_t = jax.random.normal(jax.random.PRNGKey(seed + 2),
                            (b, 1, d_model)) * 0.3
    return cfg, params, cache, jnp.asarray(pt), x_t
