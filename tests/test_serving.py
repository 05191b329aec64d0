"""Serving regression tests: the continuous-batching engine (paged KV,
per-slot offsets, chunked prefill) must be *invisible* in the outputs —
token-for-token identical to sequential unbatched decode — while requests
of different lengths join and leave mid-flight.  The serve harness lives
in conftest (``serve_mixed`` / ``make_prompts``, shared with
tests/test_preemption.py)."""
import numpy as np
import pytest

from repro.serve import (EngineConfig, PageAllocator, Request, ServeEngine,
                         generate_sequential)

MAX_LEN = 192
MAX_NEW = 8


def test_page_allocator_reuse_and_exhaustion():
    a = PageAllocator(5)                    # pages 1..4 usable, 0 = trash
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [1, 2, 3, 4] and a.available == 0
    with pytest.raises(RuntimeError):
        a.alloc()
    a.free([2, 4])
    assert a.available == 2 and a.alloc() in (2, 4)


def test_mixed_length_matches_sequential_decode(full_attn_smoke,
                                                make_prompts, serve_mixed):
    """Mixed-length batch + late joiner + chunked prefill + page reuse must
    reproduce plain (non-paged, unbatched) prefill+decode token for token."""
    cfg, model, params = full_attn_smoke
    prompts = make_prompts(cfg, [5, 37, 90, 17])
    ref = [generate_sequential(model, params, p, max_new_tokens=MAX_NEW,
                               max_len=MAX_LEN) for p in prompts]
    out, eng = serve_mixed(model, params, prompts, late_idx=3, max_slots=2,
                           num_pages=25)
    for i in range(len(prompts)):
        assert out[i] == ref[i], f"request {i} diverged"
    # every page went back to the free list
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_sla2_batching_is_output_invariant(qwen3_smoke, qwen3_params,
                                           make_prompts, serve_mixed):
    """SLA2 decode (router + linear complement states): serving requests
    mixed in a multi-slot batch with a late joiner must equal serving them
    one at a time through a single-slot engine — including slot recycling
    of the per-slot linear totals."""
    cfg, model = qwen3_smoke
    prompts = make_prompts(cfg, [7, 45, 80, 21], seed=1)
    seq = {}
    eng = ServeEngine(model, EngineConfig(max_slots=1, max_len=MAX_LEN,
                                          prefill_chunk=32))
    eng.load(qwen3_params)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=MAX_NEW))
        eng.run_to_completion(max_steps=2000)
    seq = {r.uid: r.output for r in eng.completed}
    mix, _ = serve_mixed(model, qwen3_params, prompts, max_slots=3,
                         late_idx=3)
    for i in range(len(prompts)):
        assert mix[i] == seq[i], f"request {i} diverged under batching"


def test_small_page_pool_defers_admission(full_attn_smoke, make_prompts,
                                          serve_mixed):
    """Admission with a pool too small for all requests at once defers
    (or preempts) instead of deadlocking; outputs stay exact and every
    usable page comes back free."""
    cfg, model, params = full_attn_smoke
    prompts = make_prompts(cfg, [20, 30, 25, 40], seed=2)
    ref = [generate_sequential(model, params, p, max_new_tokens=MAX_NEW,
                               max_len=MAX_LEN) for p in prompts]
    # worst case per request is ceil((40+8)/16)=3 pages; pool of 7 usable
    # pages can hold at most two such requests concurrently
    out, eng = serve_mixed(model, params, prompts, max_slots=4, num_pages=8)
    for i in range(len(prompts)):
        assert out[i] == ref[i]
    assert eng.allocator.available == 7


def test_engine_rejects_oversized_and_unsupported(qwen3_smoke, qwen3_params):
    cfg, model = qwen3_smoke
    eng = ServeEngine(model, EngineConfig(max_slots=1, max_len=64))
    eng.load(qwen3_params)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.arange(60, dtype=np.int32),
                           max_new_tokens=16))
    # every LM layer family carries a paged path now (MLA latent pages,
    # recurrent state checkpoints, hybrid composites) ...
    from repro.configs import get_smoke_config
    from repro.models.api import build_model
    for arch in ("deepseek_v2_lite", "xlstm_350m", "hymba_1_5b"):
        fam = build_model(get_smoke_config(arch))
        assert fam.decode_paged is not None, arch
        ServeEngine(fam, EngineConfig())
    # ... so the only stack the paged engine rejects is a non-LM one
    from repro.models import dit as D
    dit = build_model(D.DiTConfig())
    assert dit.decode_paged is None
    with pytest.raises(ValueError):
        ServeEngine(dit, EngineConfig())


def test_eos_frees_slot_early(full_attn_smoke, make_prompts):
    """An eos hit mid-decode releases the slot and its pages."""
    cfg, model, params = full_attn_smoke
    p = make_prompts(cfg, [12], seed=3)[0]
    ref = generate_sequential(model, params, p, max_new_tokens=24,
                              max_len=MAX_LEN)
    eos = ref[2]                            # force an early stop
    eng = ServeEngine(model, EngineConfig(max_slots=1, max_len=MAX_LEN,
                                          prefill_chunk=32))
    eng.load(params)
    eng.submit(Request(uid=0, prompt=p, max_new_tokens=24, eos_id=eos))
    done = eng.run_to_completion(max_steps=200)
    assert done[0].output == ref[:3]
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_fused_and_gather_paged_paths_agree_in_engine(qwen3_smoke,
                                                      qwen3_params,
                                                      make_prompts):
    """The fused Pallas paged kernels (decode + chunked prefill) and the jnp
    gather reference must serve token-identical outputs through ServeEngine,
    including a late joiner that lands on recycled slots/pages."""
    cfg, model = qwen3_smoke
    prompts = make_prompts(cfg, [7, 45, 80, 21], seed=4)

    def serve(impl):
        eng = ServeEngine(model, EngineConfig(
            max_slots=2, max_len=MAX_LEN, prefill_chunk=32, paged_impl=impl))
        assert eng.model.cfg.paged_impl == impl
        eng.load(qwen3_params)
        for i, p in enumerate(prompts[:3]):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=MAX_NEW))
        for _ in range(3):
            eng.step()                      # slots busy; joiner lands later
        eng.submit(Request(uid=3, prompt=prompts[3],
                           max_new_tokens=MAX_NEW))
        done = eng.run_to_completion(max_steps=2000)
        assert sorted(r.uid for r in done) == [0, 1, 2, 3]
        return {r.uid: r.output for r in done}

    fused, gather = serve("fused"), serve("gather")
    for i in range(len(prompts)):
        assert fused[i] == gather[i], f"request {i} diverged across impls"
