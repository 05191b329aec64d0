"""Copy-on-write prefix caching: the trie index, the refcounted allocator,
and the engine-level guarantee that a cache hit is INVISIBLE in the
outputs — token-for-token identical to a cold prefill (including the SLA2
linear-totals restore) across both paged attention paths and through
preemption of slots holding shared pages.  Also home to the engine-level
pool-invariant property test and the run_to_completion livelock guards."""
import numpy as np
import pytest

from repro.serve import (EngineConfig, PageAllocator, PrefixCache, Request,
                         ServeEngine, generate_sequential)

MAX_LEN = 192
MAX_NEW = 8


# ===========================================================================
# PageAllocator refcounts (incl. the double-free regression)
# ===========================================================================

def test_allocator_double_free_rejected():
    """Freeing an unreferenced page must raise: the old blind-append free
    list put the same physical page on the list twice and handed it to two
    slots (silent cross-slot KV corruption)."""
    a = PageAllocator(5)
    p = a.alloc()
    a.free([p])
    with pytest.raises(RuntimeError, match="double free"):
        a.free([p])
    # and a page can never appear on the free list twice
    assert sorted(a._free) == sorted(set(a._free))


def test_allocator_refcount_sharing():
    """free() is a decref: a shared page returns to the free list only
    when its LAST reference drops."""
    a = PageAllocator(5)
    p = a.alloc()
    a.incref(p)
    assert a.refcount(p) == 2
    a.free([p])
    assert a.refcount(p) == 1 and p not in a._free
    a.free([p])
    assert a.refcount(p) == 0 and p in a._free
    with pytest.raises(AssertionError):
        a.incref(p)                          # incref of a free page


# ===========================================================================
# submit() page-demand boundary (unclamped worst case)
# ===========================================================================

def test_submit_rejects_demand_beyond_pool(full_attn_smoke):
    """The reject gate must compare the request's TRUE page demand against
    the pool: with page_size=16 and 3 usable pages, 48 total tokens (3
    pages) are admissible and 64 (4 pages) are not — even though 64 tokens
    still fit max_len."""
    _, model, _ = full_attn_smoke

    def make(num_pages):
        return ServeEngine(model, EngineConfig(
            max_len=64, prefill_chunk=32, num_pages=num_pages))

    prompt = np.arange(1, 41, dtype=np.int32)          # 40 tokens
    make(4).submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    with pytest.raises(ValueError, match="pool"):      # 4 pages > 3 usable
        make(4).submit(Request(uid=1, prompt=prompt, max_new_tokens=24))
    # one more usable page and the same request is admissible
    make(5).submit(Request(uid=2, prompt=prompt, max_new_tokens=24))


# ===========================================================================
# run_to_completion progress guards
# ===========================================================================

def test_run_to_completion_raises_on_livelock(full_attn_smoke, make_prompts):
    """An engine that stops making progress with occupied slots must raise
    instead of silently returning partial results at max_steps."""
    cfg, model, params = full_attn_smoke
    p = make_prompts(cfg, [8], seed=9)[0]
    eng = ServeEngine(model, EngineConfig(max_len=64, prefill_chunk=32))
    eng.load(params)
    eng.submit(Request(uid=0, prompt=p, max_new_tokens=MAX_NEW))
    eng.step()                               # admit + prefill: slot occupied
    assert eng._slots
    # freeze the engine internals: every further step is a no-op
    eng._admit = lambda: None
    eng._prefill_step = lambda: None
    eng._decode_step = lambda: None
    with pytest.raises(RuntimeError, match="livelock"):
        eng.run_to_completion(max_steps=500, livelock_after=20)


def test_run_to_completion_raises_on_max_steps(full_attn_smoke,
                                               make_prompts):
    """max_steps running out with work still active is an error, not a
    quiet partial result."""
    cfg, model, params = full_attn_smoke
    p = make_prompts(cfg, [8], seed=9)[0]
    eng = ServeEngine(model, EngineConfig(max_len=64, prefill_chunk=32))
    eng.load(params)
    eng.submit(Request(uid=0, prompt=p, max_new_tokens=MAX_NEW))
    with pytest.raises(RuntimeError, match="max_steps"):
        eng.run_to_completion(max_steps=2)


# ===========================================================================
# PrefixCache trie unit tests (no model)
# ===========================================================================

def _toks(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 1000, n).astype(np.int32)


def test_trie_lookup_truncates_to_chunk_alignment():
    a = PageAllocator(20)
    pc = PrefixCache(page_size=4, pages_per_chunk=2, need_totals=False)
    toks = _toks(16)                         # 4 full pages, 2 chunks
    row = np.array([a.alloc() for _ in range(4)])
    created, node = pc.insert(toks, row, 4, {2: None, 4: None}, a)
    assert created == 4 and node.depth == 4 and pc.n_nodes == 4
    # a 3-page prefix walks 3 nodes but must truncate to the chunk boundary
    pages, nd = pc.lookup(toks[:12])
    assert len(pages) == 2 and nd.depth == 2
    assert pages == [int(row[0]), int(row[1])]
    # a diverging prompt shares only what actually matches
    other = toks.copy()
    other[5] += 1                            # breaks page 2 onward
    pages, nd = pc.lookup(other)
    assert pages == []                       # depth 1 is not chunk-aligned
    assert pc.lookup(_toks(3))[0] == []      # shorter than one page


def test_trie_need_totals_requires_snapshot():
    a = PageAllocator(20)
    pc = PrefixCache(page_size=4, pages_per_chunk=2, need_totals=True)
    toks = _toks(16, seed=1)
    row = np.array([a.alloc() for _ in range(4)])
    pc.insert(toks, row, 4, {2: "snap2"}, a)     # no snapshot at depth 4
    pages, nd = pc.lookup(toks)
    assert len(pages) == 2                       # falls back to depth 2
    assert pc.totals_at(nd, 2) == "snap2"


def test_trie_eviction_lru_and_pinning():
    a = PageAllocator(20)
    pc = PrefixCache(page_size=4, pages_per_chunk=1, need_totals=False)
    t1, t2 = _toks(8, seed=2), _toks(8, seed=3)
    r1 = np.array([a.alloc() for _ in range(2)])
    r2 = np.array([a.alloc() for _ in range(2)])
    pc.insert(t1, r1, 2, {}, a)
    pc.insert(t2, r2, 2, {}, a)
    pc.lookup(t1)                            # t1 is now the most recent
    avail0 = a.available
    assert pc.evict_one(a)                   # LRU leaf: t2's deep page
    assert pc.n_nodes == 3
    # the cache held the only reference (insert increfs on top of alloc's
    # 1), so eviction decrefs to 1 — nothing reaches the free list until
    # the owning slot also frees its reference
    assert a.available == avail0
    # a pinned node protects itself (and, leaf-only, its ancestors)
    _, nd = pc.lookup(t1)
    pc.pin(nd)
    assert pc.evict_one(a)                   # t2's remaining page
    assert not pc.evict_one(a)               # only the pinned path is left
    pc.unpin(nd)
    assert pc.evict_one(a) and pc.evict_one(a)
    assert pc.n_nodes == 0


def test_trie_evictable_pages_counts_sole_references():
    a = PageAllocator(20)
    pc = PrefixCache(page_size=4, pages_per_chunk=1, need_totals=False)
    toks = _toks(8, seed=4)
    row = np.array([a.alloc() for _ in range(2)])
    pc.insert(toks, row, 2, {}, a)           # refcount 2 on both pages
    assert pc.evictable_pages(a) == 0        # the "slot" still holds refs
    a.free(row)                              # slot finished: cache-only now
    assert pc.evictable_pages(a) == 2
    _, nd = pc.lookup(toks)
    pc.pin(nd)
    # the pinned leaf doesn't count — nor does its ancestor, which
    # leaf-only eviction cannot reach while the pin is held
    assert pc.evictable_pages(a) == 0
    pc.unpin(nd)
    assert pc.evictable_pages(a) == 2


# ===========================================================================
# Engine-level identity: a hit must be invisible in the outputs
# ===========================================================================

def _serve_sequential(model, params, prompts, *, max_new=MAX_NEW,
                      max_steps=4000, **ecfg_kw):
    """One engine, requests submitted and drained ONE AT A TIME — later
    prompts can hit the prefixes earlier ones left in the cache."""
    eng = ServeEngine(model, EngineConfig(max_len=MAX_LEN, prefill_chunk=32,
                                          **ecfg_kw))
    eng.load(params)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
        eng.run_to_completion(max_steps=max_steps)
    return {r.uid: r.output for r in eng.completed}, eng


def _shared_prefix_prompts(cfg, n_sys=96, suffixes=(13, 22, 7), seed=0):
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(1, cfg.vocab_size, n_sys).astype(np.int32)
    out = [np.concatenate(
        [sys_p, rng.integers(1, cfg.vocab_size, n).astype(np.int32)])
        for n in suffixes]
    return sys_p, out


@pytest.mark.parametrize("impl", ["gather", "fused"])
def test_hit_identical_to_cold_prefill_dense(full_attn_smoke, impl):
    """Dense stack, both paged paths: outputs with the prefix cache enabled
    must equal the cache-disabled engine AND the non-paged sequential
    oracle, while actually hitting the cache."""
    cfg, model, params = full_attn_smoke
    _, prompts = _shared_prefix_prompts(cfg)
    ref = [generate_sequential(model, params, p, max_new_tokens=MAX_NEW,
                               max_len=MAX_LEN) for p in prompts]
    off, _ = _serve_sequential(model, params, prompts, paged_impl=impl)
    on, eng = _serve_sequential(model, params, prompts, paged_impl=impl,
                                prefix_cache=True)
    assert eng.stats["prefix_hits"] >= 2     # prompts 2 and 3 hit prompt 1
    assert eng.stats["prefix_hit_tokens"] >= 2 * 96
    for i in range(len(prompts)):
        assert on[i] == ref[i] == off[i], f"request {i} diverged"


@pytest.mark.parametrize("impl", ["gather", "fused"])
def test_hit_identical_to_cold_prefill_sla2(qwen3_smoke, qwen3_params,
                                            impl):
    """SLA2 stack: a hit restores the linear totals (h_tot, z_tot) from the
    trie snapshot instead of re-prefilling — decode must still be
    token-identical to the cache-off engine on both paged paths."""
    cfg, model = qwen3_smoke
    _, prompts = _shared_prefix_prompts(cfg, seed=1)
    off, _ = _serve_sequential(model, qwen3_params, prompts, paged_impl=impl)
    on, eng = _serve_sequential(model, qwen3_params, prompts,
                                paged_impl=impl, prefix_cache=True)
    assert eng.stats["prefix_hits"] >= 2
    for i in range(len(prompts)):
        assert on[i] == off[i], f"request {i} diverged"


def test_sla2_totals_restored_bit_exact_after_hit(qwen3_smoke, qwen3_params):
    """Layer-level state parity: after serving a hit, the slot's linear
    totals must be BIT-identical to the same request served cold — the
    engine-output identity above could in principle hide tiny drift."""
    import jax
    import jax.numpy as jnp

    cfg, model = qwen3_smoke
    _, prompts = _shared_prefix_prompts(cfg, suffixes=(13, 22), seed=2)

    def totals_after(prefix_cache):
        eng = ServeEngine(model, EngineConfig(
            max_len=MAX_LEN, prefill_chunk=32, max_slots=1,
            prefix_cache=prefix_cache))
        eng.load(qwen3_params)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=1))
            eng.run_to_completion(max_steps=2000)
            if i == 0:
                continue
            # capture slot 0's per-layer totals right after request 1
            # finished (max_new=1: nothing decoded on top of the prefill)
            ext = jax.jit(model.extract_totals)
            return jax.device_get(ext(eng.caches,
                                      jnp.asarray(0, jnp.int32))), eng

    cold, _ = totals_after(False)
    warm, eng = totals_after(True)
    assert eng.stats["prefix_hits"] >= 1
    flat_c = jax.tree.leaves(cold)
    flat_w = jax.tree.leaves(warm)
    assert len(flat_c) == len(flat_w) > 0
    for c, w in zip(flat_c, flat_w):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(w))


def test_full_prompt_hit_triggers_copy_on_write(qwen3_smoke, qwen3_params):
    """An exact duplicate of a chunk-aligned cached prompt re-runs only its
    final chunk, whose pages are shared — the write guard must CoW them
    into private pages and still produce identical tokens."""
    cfg, model = qwen3_smoke
    rng = np.random.default_rng(3)
    p = rng.integers(1, cfg.vocab_size, 96).astype(np.int32)  # 3 chunks
    prompts = [p, p.copy()]
    off, _ = _serve_sequential(model, qwen3_params, prompts)
    on, eng = _serve_sequential(model, qwen3_params, prompts,
                                prefix_cache=True)
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["cow_copies"] == 2      # the final chunk's 2 pages
    assert on[0] == off[0] and on[1] == off[1]
    _check_pool_invariants(eng)


def test_preemption_of_shared_pages(qwen3_smoke, qwen3_params):
    """Slots holding shared pages get preempted under a tight pool: the
    shared prefix must survive on-device (pinned trie node, never swapped),
    resume must re-map it by incref, and every request must still decode
    token-identically to an undisturbed cache-off engine."""
    cfg, model = qwen3_smoke
    _, prompts = _shared_prefix_prompts(cfg, n_sys=64,
                                        suffixes=(9, 17, 26), seed=4)
    off, _ = _serve_sequential(model, qwen3_params, prompts, max_slots=1)
    # warm the cache, then serve the rest CONCURRENTLY under a pool that
    # cannot hold both remaining requests (4 cached + 2 + 3 private pages
    # > 7 usable) -> forced preemption of a slot holding shared pages
    eng = ServeEngine(model, EngineConfig(
        max_len=MAX_LEN, prefill_chunk=32, max_slots=3, num_pages=8,
        prefix_cache=True))
    eng.load(qwen3_params)
    eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=MAX_NEW))
    eng.run_to_completion(max_steps=4000)
    for i in (1, 2):
        eng.submit(Request(uid=i, prompt=prompts[i],
                           max_new_tokens=MAX_NEW))
    eng.run_to_completion(max_steps=4000)
    on = {r.uid: r.output for r in eng.completed}
    assert eng.stats["prefix_hits"] >= 1
    assert eng.stats["preemptions"] > 0, "pool was not tight enough"
    for i in range(len(prompts)):
        assert on[i] == off[i], f"request {i} diverged across preemption"
    _check_pool_invariants(eng)
    # all slots drained: only the cache's own references remain mapped
    cached = len(eng._pcache.page_refs())
    assert eng.allocator.available == eng.allocator.num_pages - 1 - cached


# ===========================================================================
# Pool-invariant property test (hypothesis)
# ===========================================================================

def _check_pool_invariants(eng):
    """The full refcount accounting, checked from outside the engine:
    every physical page's refcount equals its page-table occurrences plus
    its prefix-cache references; the free list holds exactly the pages at
    refcount zero; nothing leaks and nothing is double-mapped."""
    alloc = eng.allocator
    counts = np.zeros(alloc.num_pages, np.int64)
    vals, occ = np.unique(eng._page_table, return_counts=True)
    for p, c in zip(vals, occ):
        if p > 0:
            counts[p] = c
    if eng._pcache is not None:
        for p, c in eng._pcache.page_refs().items():
            counts[p] += c
    free = set(alloc._free)
    assert len(free) == len(alloc._free), "free list holds duplicates"
    for p in range(1, alloc.num_pages):
        assert alloc.refcount(p) == counts[p], f"page {p} refcount drift"
        assert (p in free) == (counts[p] == 0), f"page {p} free-list drift"
    assert alloc.available + int((counts[1:] > 0).sum()) \
        == alloc.num_pages - 1


try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:                          # optional test dependency
    given = None

if given is not None:
    @given(seed=st.integers(0, 2 ** 16),
           num_pages=st.sampled_from([10, 14]),
           swap=st.sampled_from([0, None]),
           spec=st.sampled_from(["off", "ngram"]),
           share=st.booleans())
    # every slot preempted, the head's pages pinned by a swapped request
    # queued behind it: admission deadlocked before the idle demotion
    @example(seed=12068, num_pages=10, swap=None, spec="off", share=True)
    @settings(max_examples=8, deadline=None)
    def test_pool_invariants_hold_after_every_step(qwen3_smoke,
                                                   qwen3_params, seed,
                                                   num_pages, swap, spec,
                                                   share):
        """Randomized preempt/swap/spec workloads with the prefix cache
        on: after EVERY engine step the pool must satisfy the refcount/
        free-list invariants (see _check_pool_invariants) — and the
        workload must drain."""
        cfg, model = qwen3_smoke
        rng = np.random.default_rng(seed)
        sys_p = rng.integers(1, cfg.vocab_size, 64).astype(np.int32)
        prompts = []
        for _ in range(4):
            tail = rng.integers(1, cfg.vocab_size,
                                int(rng.integers(4, 40))).astype(np.int32)
            prompts.append(np.concatenate([sys_p, tail]) if share else tail)
        eng = ServeEngine(model, EngineConfig(
            max_len=MAX_LEN, prefill_chunk=32, max_slots=3,
            num_pages=num_pages, swap_pages=swap, speculative=spec,
            prefix_cache=True))
        eng.load(qwen3_params)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
        for _ in range(4000):
            n = eng.step()
            _check_pool_invariants(eng)
            if n == 0 and not eng._queue:
                break
        else:
            raise AssertionError("randomized workload did not drain")
        assert len(eng.completed) == len(prompts)
