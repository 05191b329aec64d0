"""Cross-family serving identity matrix: the paged ServeEngine must be
token-identical to ``generate_sequential`` for every cache family it
serves — MLA latent pages (deepseek_v2_lite), attention K/V pages beside
an expert FFN (llama4_maverick_400b), recurrent state checkpoints
(xlstm_350m) and hybrid attention+SSM stacks (hymba_1_5b) — under mixed
batching, late joiners, slot recycling, forced preemption (swap-out /
swap-in and recompute-replay) and n-gram speculative decoding.

Exactness knobs per family (the engine itself runs identically without
them; they only make the *oracle comparison* exact):

- sla2-mechanism families (deepseek, llama4, hymba) run at
  ``k_frac=1.0`` and ``quant_bits='none'``: the paged MLA/attention
  prefill is exact dense over the slot's pages (the sparse/linear split
  applies to decode), so token identity to the static sla2 prefill
  requires the routed mask to cover everything (then alpha is
  auto-forced to 1 on the empty complement).  Static sla2 prompt lengths
  must divide block_q=32.
- deepseek and llama4 serve through the dropless expert layer (models/
  moe.moe_layer: no capacity, so chunked prefill, batched decode and the
  static oracle's calls route alike; the smoke preset's
  ``capacity_factor`` is training's only) and additionally need a
  float32 page pool (EngineConfig.page_dtype + generate_sequential
  cache_dtype): the MoE gates amplify bf16 page rounding into expert
  flips.
"""
import numpy as np
import pytest

import jax

from repro.configs import get_smoke_config
from repro.models.api import build_model
from repro.models.moe import MoEConfig
from repro.serve import EngineConfig, Request, ServeEngine
from repro.serve.engine import generate_sequential

MAX_LEN = 192
MAX_NEW = 8

# family -> smoke-config overrides, engine kwargs, oracle kwargs,
# oracle-legal prompt lengths, and the pool size that forces preemption
# (squeeze_pages: one page short of the family's aggregate demand)
FAMILIES = {
    "mla": dict(
        arch="deepseek_v2_lite",
        overrides=dict(
            k_frac=1.0, quant_bits="none",
            moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                          num_shared=2, capacity_factor=8.0)),
        engine_kw=dict(page_dtype="float32"),
        oracle_kw=dict(cache_dtype="float32"),
        lengths=(32, 64, 32, 32), squeeze_pages=8),
    "moe": dict(
        arch="llama4_maverick_400b",
        overrides=dict(k_frac=1.0, quant_bits="none"),
        engine_kw=dict(page_dtype="float32"),
        oracle_kw=dict(cache_dtype="float32"),
        lengths=(32, 64, 32, 32), squeeze_pages=8),
    "ssm": dict(
        arch="xlstm_350m",
        overrides=dict(block_k=16),
        engine_kw={}, oracle_kw={},
        lengths=(8, 32, 16, 24), squeeze_pages=6),
    "hybrid": dict(
        arch="hymba_1_5b",
        overrides=dict(k_frac=1.0, quant_bits="none"),
        engine_kw={}, oracle_kw={},
        lengths=(32, 64, 32, 32), squeeze_pages=8),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    spec = FAMILIES[request.param]
    cfg = get_smoke_config(spec["arch"], **spec["overrides"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return request.param, spec, cfg, model, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def _oracle(spec, model, params, prompts, max_new=MAX_NEW):
    return [generate_sequential(model, params, p, max_new_tokens=max_new,
                                max_len=MAX_LEN, **spec["oracle_kw"])
            for p in prompts]


def _serve(spec, model, params, prompts, *, late_idx=None, max_new=MAX_NEW,
           **ecfg_kw):
    kw = dict(max_slots=2, max_len=MAX_LEN, prefill_chunk=32)
    kw.update(spec["engine_kw"])
    kw.update(ecfg_kw)
    eng = ServeEngine(model, EngineConfig(**kw))
    eng.load(params)
    for i, p in enumerate(prompts):
        if i == late_idx:
            continue
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    if late_idx is not None:
        for _ in range(3):
            eng.step()                      # slots busy: joiner lands later
        eng.submit(Request(uid=late_idx, prompt=prompts[late_idx],
                           max_new_tokens=max_new))
    done = eng.run_to_completion(max_steps=4000)
    assert sorted(r.uid for r in done) == list(range(len(prompts)))
    return {r.uid: r.output for r in done}, eng


def test_family_identity_with_late_joiner_and_recycled_slot(family):
    """Mixed lengths + late joiner + more requests than slots (the joiner
    and the 4th request land on recycled slots/pages): every request must
    match unbatched sequential decode token for token."""
    name, spec, cfg, model, params = family
    prompts = _prompts(cfg, spec["lengths"])
    ref = _oracle(spec, model, params, prompts)
    out, eng = _serve(spec, model, params, prompts, late_idx=3)
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"[{name}] request {i} diverged"
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_family_identity_under_forced_preemption_swap(family):
    """Pool below aggregate demand: slots get preempted, swap out to the
    host pool (pages and/or recurrent state checkpoints) and resume —
    outputs must stay identical to sequential decode."""
    name, spec, cfg, model, params = family
    prompts = _prompts(cfg, spec["lengths"][:3], seed=1)
    ref = _oracle(spec, model, params, prompts)
    out, eng = _serve(spec, model, params, prompts, max_slots=3,
                      num_pages=spec["squeeze_pages"])
    assert eng.stats["preemptions"] > 0, f"[{name}] pool never bound"
    assert eng.stats["swap_outs"] > 0
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"[{name}] request {i} diverged after swap"
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_family_identity_under_recompute_replay(family):
    """swap_pages=0 disables the host pool: preemption falls back to
    recompute — the victim's prompt AND generated prefix replay through
    chunked prefill (partial final chunks included) bit-compatibly."""
    name, spec, cfg, model, params = family
    prompts = _prompts(cfg, spec["lengths"][:3], seed=2)
    ref = _oracle(spec, model, params, prompts)
    out, eng = _serve(spec, model, params, prompts, max_slots=3,
                      num_pages=spec["squeeze_pages"], swap_pages=0)
    assert eng.stats["preemptions"] > 0, f"[{name}] pool never bound"
    assert eng.stats["swap_outs"] == 0
    for i in range(len(prompts)):
        assert out[i] == ref[i], \
            f"[{name}] request {i} diverged after recompute"


def test_family_identity_with_ngram_speculation(family):
    """The model-free n-gram drafter + multi-token paged verify must keep
    greedy outputs token-identical on every cache family (the verify
    window exercises mla_decode_window_paged / ssm window states /
    hybrid_commit_window)."""
    name, spec, cfg, model, params = family
    # repetitive prompts so the drafter actually proposes
    base = _prompts(cfg, spec["lengths"][:2], seed=3)
    prompts = [np.concatenate([p[: len(p) // 2]] * 2) for p in base]
    ref = _oracle(spec, model, params, prompts, max_new=12)
    out, eng = _serve(spec, model, params, prompts, max_new=12,
                      speculative="ngram", draft_len=3)
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"[{name}] request {i} diverged (ngram)"


def test_family_batching_is_output_invariant(family):
    """Mixed multi-slot serving must equal one-at-a-time single-slot
    serving (no oracle involved, so this also covers the default sparse
    k_frac routing and bf16 pools on the sla2 families)."""
    name, spec, cfg, model, params = family
    cfg2 = get_smoke_config(
        spec["arch"],
        **{k: v for k, v in spec["overrides"].items()
           if k in ("block_k", "moe")})
    model2 = build_model(cfg2)
    params2 = model2.init(jax.random.PRNGKey(0))
    prompts = _prompts(cfg2, spec["lengths"], seed=4)
    eng = ServeEngine(model2, EngineConfig(max_slots=1, max_len=MAX_LEN,
                                           prefill_chunk=32))
    eng.load(params2)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=MAX_NEW))
        eng.run_to_completion(max_steps=2000)
    seq = {r.uid: r.output for r in eng.completed}
    out, _ = _serve({"engine_kw": {}, "oracle_kw": {}}, model2, params2,
                    prompts, late_idx=3, max_slots=3)
    for i in range(len(prompts)):
        assert out[i] == seq[i], f"[{name}] request {i} varies with batching"


# ===========================================================================
# Pool invariants on heterogeneous per-layer cache kinds
# ===========================================================================

def _run_invariant_workload(seed, num_pages, swap, spec_mode):
    """Randomized hybrid-stack workload; checks the refcount/free-list
    invariants after EVERY engine step (heterogeneous kinds: the hybrid
    layers hold K/V pages AND per-slot SSM checkpoints behind one page
    table)."""
    from test_prefix_cache import _check_pool_invariants
    cfg = get_smoke_config("hymba_1_5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(1, cfg.vocab_size, 48).astype(np.int32)
    prompts = []
    for _ in range(4):
        tail = rng.integers(1, cfg.vocab_size,
                            int(rng.integers(4, 40))).astype(np.int32)
        prompts.append(np.concatenate([sys_p, tail]))
    eng = ServeEngine(model, EngineConfig(
        max_len=MAX_LEN, prefill_chunk=32, max_slots=3,
        num_pages=num_pages, swap_pages=swap, speculative=spec_mode,
        prefix_cache=True))
    eng.load(params)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    for _ in range(4000):
        n = eng.step()
        _check_pool_invariants(eng)
        if n == 0 and not eng._queue:
            break
    else:
        raise AssertionError("hybrid workload did not drain")
    assert len(eng.completed) == len(prompts)


@pytest.mark.parametrize("seed,num_pages,swap,spec_mode", [
    (0, 12, None, "off"),                   # swap path
    (1, 12, 0, "ngram"),                    # recompute + speculation
])
def test_hybrid_pool_invariants_deterministic(seed, num_pages, swap,
                                              spec_mode):
    """Deterministic twin of the hypothesis sweep below (always runs)."""
    _run_invariant_workload(seed, num_pages, swap, spec_mode)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # optional test dependency
    given = None

if given is not None:
    @given(seed=st.integers(0, 2 ** 16),
           num_pages=st.sampled_from([12, 16]),
           swap=st.sampled_from([0, None]),
           spec_mode=st.sampled_from(["off", "ngram"]))
    @settings(max_examples=6, deadline=None)
    def test_hybrid_pool_invariants_hold_after_every_step(
            seed, num_pages, swap, spec_mode):
        """Randomized preempt/swap/spec workloads on the hybrid stack:
        heterogeneous per-layer cache kinds must keep the pool refcount
        and free-list invariants after every step."""
        _run_invariant_workload(seed, num_pages, swap, spec_mode)
