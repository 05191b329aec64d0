"""Shared fixtures: session-scoped model/param construction.

Building a smoke model and initialising its params is pure (no mutable
state leaks between tests), so the heavyweight pieces — param init and the
jit caches that accumulate on the model's closures — are shared across the
whole session instead of being rebuilt per test module.
"""
import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch.cache import use_compile_cache
from repro.models.api import build_model

# Persistent XLA compilation cache: the suite is compile-bound on CPU, and
# most of it is identical between runs.  Cold runs pay full price; the
# edit-test loop and cached CI runs skip recompiling unchanged graphs.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


@pytest.fixture(scope="session")
def qwen3_smoke():
    """(cfg, model) for the qwen3 smoke config — dense GQA x SLA2."""
    cfg = get_smoke_config("qwen3_14b")
    return cfg, build_model(cfg)


@pytest.fixture(scope="session")
def qwen3_params(qwen3_smoke):
    _, model = qwen3_smoke
    return model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="session")
def full_attn_smoke():
    """(cfg, model, params) for a dense-softmax (mechanism='full') smoke
    model — the reference for serving-identity tests."""
    cfg = get_smoke_config("qwen3_14b", mechanism="full")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="session")
def make_prompts():
    """Random prompts of the given lengths (shared serving-test helper)."""
    import numpy as np

    def _prompts(cfg, lengths, seed=0):
        rng = np.random.default_rng(seed)
        return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
                for n in lengths]
    return _prompts


@pytest.fixture(scope="session")
def serve_mixed():
    """Serve ``prompts`` through a fresh ServeEngine, optionally with one
    late-joining request; returns ({uid: output}, engine).  The shared
    harness for the serving-identity and preemption test suites."""
    from repro.serve import EngineConfig, Request, ServeEngine

    def _serve(model, params, prompts, *, late_idx=None, max_new=8,
               max_len=192, prefill_chunk=32, max_steps=4000, **ecfg_kw):
        eng = ServeEngine(model, EngineConfig(
            max_len=max_len, prefill_chunk=prefill_chunk, **ecfg_kw))
        eng.load(params)
        for i, p in enumerate(prompts):
            if i != late_idx:
                eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
        if late_idx is not None:
            for _ in range(3):              # others are already in flight
                eng.step()
            eng.submit(Request(uid=late_idx, prompt=prompts[late_idx],
                               max_new_tokens=max_new))
        done = eng.run_to_completion(max_steps=max_steps)
        assert sorted(r.uid for r in done) == list(range(len(prompts)))
        return {r.uid: r.output for r in done}, eng
    return _serve
