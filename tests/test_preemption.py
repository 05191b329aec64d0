"""Preemption-scheduler regression tests: with the page pool sized below
aggregate demand, the optimistic scheduler must preempt (swap-out to the
host pool, or recompute-from-prompt when swap is full) and still produce
outputs token-identical to undisturbed decode — across the dense, sla2,
fused and gather paged paths.  The serve harness lives in conftest
(``serve_mixed`` / ``make_prompts``, shared with tests/test_serving.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import (EngineConfig, Request, Scheduler, ServeEngine,
                         SwapPool, generate_sequential)

MAX_LEN = 192
MAX_NEW = 8


def test_forced_preemption_matches_sequential_decode(full_attn_smoke,
                                                     make_prompts,
                                                     serve_mixed):
    """Pool below aggregate demand + late joiner: slots get preempted
    (swapped) and resumed, outputs stay identical to plain unbatched
    prefill+decode; pool and swap space drain completely."""
    cfg, model, params = full_attn_smoke
    prompts = make_prompts(cfg, [20, 35, 28, 40], seed=0)
    ref = [generate_sequential(model, params, p, max_new_tokens=MAX_NEW,
                               max_len=MAX_LEN) for p in prompts]
    # 3 slots x up to 3 worst-case pages vs 7 usable pages -> must preempt
    out, eng = serve_mixed(model, params, prompts, late_idx=3, max_slots=3,
                           num_pages=8)
    assert eng.stats["preemptions"] > 0 and eng.stats["swap_outs"] > 0
    assert eng.stats["swap_ins"] == eng.stats["swap_outs"]
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"request {i} diverged after preemption"
    assert eng.allocator.available == eng.allocator.num_pages - 1
    assert eng.swap.used == 0 and eng.swap.n_swapped == 0


def test_recompute_fallback_when_swap_full(full_attn_smoke, make_prompts,
                                           serve_mixed):
    """swap_pages=0 disables the swap pool: preemption falls back to
    recompute-from-prompt (replay through chunked prefill + teacher-forced
    decode of the already-sampled tokens), still token-identical."""
    cfg, model, params = full_attn_smoke
    prompts = make_prompts(cfg, [20, 35, 28, 40], seed=1)
    ref = [generate_sequential(model, params, p, max_new_tokens=MAX_NEW,
                               max_len=MAX_LEN) for p in prompts]
    out, eng = serve_mixed(model, params, prompts, late_idx=3, max_slots=3,
                           num_pages=8, swap_pages=0)
    assert eng.stats["recomputes"] > 0 and eng.stats["swap_outs"] == 0
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"request {i} diverged after recompute"
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_sla2_swap_preserves_linear_totals(qwen3_smoke, qwen3_params,
                                           make_prompts, serve_mixed):
    """SLA2 decode depends on the per-slot linear totals (h_tot/z_tot) and
    per-page pooled router keys; a swap-out/swap-in cycle (possibly landing
    on a different slot and different physical pages) must restore them
    exactly — verified by token-identity against an undisturbed single-slot
    engine."""
    cfg, model = qwen3_smoke
    prompts = make_prompts(cfg, [20, 35, 28, 40], seed=2)
    eng = ServeEngine(model, EngineConfig(max_slots=1, max_len=MAX_LEN,
                                          prefill_chunk=32))
    eng.load(qwen3_params)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=MAX_NEW))
        eng.run_to_completion(max_steps=4000)
    ref = {r.uid: r.output for r in eng.completed}
    out, eng2 = serve_mixed(model, qwen3_params, prompts, late_idx=3,
                            max_slots=3, num_pages=8)
    assert eng2.stats["swap_outs"] > 0
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"request {i} diverged across swap"


def test_preempted_fused_and_gather_agree(qwen3_smoke, qwen3_params,
                                          make_prompts, serve_mixed):
    """Forced preemption must be path-invariant: the fused Pallas paged
    kernels and the jnp gather reference serve identical tokens through
    preempt/swap/resume cycles."""
    cfg, model = qwen3_smoke
    prompts = make_prompts(cfg, [20, 35, 28], seed=4)

    def serve(impl):
        out, eng = serve_mixed(model, qwen3_params, prompts, max_slots=3,
                               num_pages=7, paged_impl=impl)
        assert eng.stats["preemptions"] > 0
        return out

    fused, gather = serve("fused"), serve("gather")
    for i in range(len(prompts)):
        assert fused[i] == gather[i], f"request {i} diverged across impls"


def test_mid_chunk_self_preemption_resumes(full_attn_smoke, make_prompts,
                                           serve_mixed):
    """A slot that self-preempts MID-CHUNK (some of the chunk's pages
    already mapped) must be re-admittable once the pool frees: the
    admission gate takes max(saved pages, pages the resumed chunk
    reaches) — summing them would demand more pages than the pool holds
    and deadlock the request behind an always-failing FCFS head."""
    cfg, model, params = full_attn_smoke
    prompts = make_prompts(cfg, [8, 56], seed=5)
    ref = [generate_sequential(model, params, p, max_new_tokens=m,
                               max_len=64) for p, m in zip(prompts, (4, 8))]
    eng = ServeEngine(model, EngineConfig(
        max_slots=2, max_len=64, prefill_chunk=32, num_pages=5))
    eng.load(params)
    # request 1's worst case is exactly the whole pool (4 pages) and its
    # 32-token chunk spans 2 pages: it self-preempts mid-chunk
    eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=4))
    eng.submit(Request(uid=1, prompt=prompts[1], max_new_tokens=8))
    done = eng.run_to_completion(max_steps=500)
    out = {r.uid: r.output for r in done}
    assert sorted(out) == [0, 1], "mid-chunk preemption deadlocked"
    assert eng.stats["preemptions"] > 0
    assert out[0] == ref[0] and out[1] == ref[1]


def test_swap_state_roundtrip_bit_exact():
    """Layer-level: extracting a slot's pages + linear totals and inserting
    them into a fresh pool at different physical pages / a different slot
    row must reproduce the state bit for bit (the engine's swap path is a
    numpy round trip of exactly this state)."""
    from repro.models import attention as A
    from repro.serve.scenario import make_paged_attention_state

    cfg, params, cache, pt, x_t = make_paged_attention_state()
    src_slot, dst_slot = 2, 0
    max_p = pt.shape[1]
    src_row = np.asarray(pt)[src_slot]
    n_pages = int((src_row > 0).sum())
    state = jax.tree.map(np.asarray,
                         A.extract_paged_state(cache, jnp.asarray(src_row),
                                               src_slot))
    # different physical placement in a fresh (zeroed) pool
    dst_row = np.zeros((max_p,), np.int32)
    dst_row[:n_pages] = np.arange(1, n_pages + 1)
    fresh = A.init_paged_cache(cfg, int(cache["k_pages"].shape[0]),
                               int(cache["h_tot"].shape[0]),
                               dtype=jnp.float32)
    restored = A.insert_paged_state(fresh, jnp.asarray(dst_row), dst_slot,
                                    state)
    back = jax.tree.map(np.asarray,
                        A.extract_paged_state(restored,
                                              jnp.asarray(dst_row),
                                              dst_slot))
    for key in state:
        # compare only the real pages (padded row entries read the trash
        # page, whose content legitimately differs between pools)
        a, b = state[key], back[key]
        if key in ("k_pages", "v_pages", "pooled_pages"):
            a, b = a[:n_pages], b[:n_pages]
        assert np.array_equal(a, b), f"{key} not bit-exact after round trip"


def test_scheduler_priority_and_swap_accounting():
    """Host-side policy units: preempted requests resume in arrival order
    ahead of later arrivals; SwapPool accounts capacity in pages."""
    from repro.serve.engine import _ResumeState, _Slot

    sched = Scheduler()
    reqs = [Request(uid=i, prompt=np.ones(4, np.int32)) for i in range(4)]
    for r in reqs:
        sched.enqueue(r)
    assert [sched.pop_head().uid for _ in range(3)] == [0, 1, 2]
    # preempt uid=2 then uid=1 (preempt-last order): queue must come back
    # in arrival order, ahead of the never-admitted uid=3
    mk = lambda r: _ResumeState(mode="recompute",
                                slot=_Slot(req=r, tokens=r.prompt))
    sched.requeue(reqs[2], mk(reqs[2]))
    sched.requeue(reqs[1], mk(reqs[1]))
    assert [r.uid for r in sched.waiting] == [1, 2, 3]
    assert sched.victim({7: _Slot(req=reqs[1], tokens=reqs[1].prompt),
                         3: _Slot(req=reqs[2], tokens=reqs[2].prompt)}) == 3
    pool = SwapPool(4)
    assert pool.can_hold(4) and not pool.can_hold(5)
    pool.put(0, 3, {"x": np.zeros(3)})
    assert pool.used == 3 and not pool.can_hold(2)
    pool.pop(0)
    assert pool.used == 0 and pool.n_swapped == 0
    with pytest.raises(KeyError):
        pool.pop(0)


# engine settings, and the counter that shows the case's path ran
DONATED_CASES = {
    "swap": (dict(max_slots=3, num_pages=8), "swap_outs"),
    "recompute": (dict(max_slots=3, num_pages=8, swap_pages=0),
                  "recomputes"),
    "prefix_cache": (dict(max_slots=1, prefix_cache=True), "prefix_hits"),
    "speculative": (dict(max_slots=3, speculative="ngram", draft_len=3),
                    "spec_steps"),
}


@pytest.mark.parametrize("case", sorted(DONATED_CASES))
def test_donated_pool_serves_the_same(case, make_prompts, serve_mixed,
                                      monkeypatch):
    """The step programs donate the pool on an accelerator and not on the
    CPU (``engine.pool_donation``).  Forced on the CPU, the donated path
    serves the same tokens through preemption (swap and recompute), the
    prefix cache's page copies and speculative verify/commit: no step
    reads a pool a program has consumed."""
    from repro.configs import get_smoke_config
    from repro.models.api import build_model
    from repro.serve import engine as engine_mod
    cfg = get_smoke_config("qwen3_14b")
    shared = make_prompts(cfg, [64], seed=5)[0]
    prompts = [np.concatenate([shared, p]) if case == "prefix_cache" else p
               for p in make_prompts(cfg, [20, 35, 28, 40], seed=4)]
    ecfg, counter = DONATED_CASES[case]
    outs = []
    for donate in ((), (2,)):
        monkeypatch.setattr(engine_mod, "pool_donation", lambda d=donate: d)
        model = build_model(cfg)        # its own jitted step programs
        params = model.init(jax.random.PRNGKey(0))
        out, eng = serve_mixed(model, params, prompts, late_idx=3,
                               **ecfg)
        assert eng.stats[counter] > 0
        outs.append(out)
        old = jax.tree.leaves(eng.caches)[0]
        fresh = make_prompts(cfg, [20], seed=6)[0]    # no prefix hit
        eng.submit(Request(uid=9, prompt=fresh, max_new_tokens=2))
        eng.run_to_completion()
        assert old.is_deleted() == bool(donate)
    assert outs[0] == outs[1]
