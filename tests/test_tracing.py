"""The engines' own measurement: host spans on the profiler's clock, named
programs, named scopes on the device side, and the logits byte counter.

Spans are ``jax.profiler.TraceAnnotation`` events; the tests step smoke
engines under a CPU profiler trace and read the events back from the
``.xplane.pb``.  Program names and scopes are read from the compiled HLO
text: the module is ``jit_<program>`` and each instruction's ``op_name``
carries the ``jax.named_scope`` path it was traced under.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.wan_dit_1_3b import smoke_config as dit_smoke_config
from repro.models.api import build_model
from repro.serve import EngineConfig, Request, ServeEngine
from repro.serve import diffusion as DS

N_LAT = 64


@pytest.fixture(scope="module")
def dit_model():
    model = build_model(dit_smoke_config())
    return model, model.init(jax.random.PRNGKey(0))


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; its host spans as (name, start,
    end, metadata) on the thread that ran it, sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("serve", "dit"):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(spans, i):
    """Name of the innermost span that encloses span ``i``, or None."""
    _, a, b, _ = spans[i]
    best = None
    for j, (name, s, e, _) in enumerate(spans):
        if j != i and s <= a and b <= e and (best is None
                                             or e - s < best[1]):
            best = (name, e - s)
    return best and best[0]


def _serve_engine(model, params, prompts, **kw):
    eng = ServeEngine(model, EngineConfig(max_slots=2, max_len=128,
                                          prefill_chunk=32, **kw))
    eng.load(params)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    return eng


@pytest.mark.parametrize("speculative", ["off", "linear"])
def test_serve_engine_spans_nest(tmp_path, qwen3_smoke, qwen3_params,
                                 make_prompts, speculative):
    """Every step is one serve.step; its phases nest inside it, finishing
    nests inside sampling, and each request's spans carry its uid."""
    cfg, model = qwen3_smoke
    kw = {"speculative": speculative, "draft_len": 2} \
        if speculative != "off" else {}
    eng = _serve_engine(model, qwen3_params, make_prompts(cfg, [40, 20]),
                        **kw)
    spans = _traced(tmp_path, eng.run_to_completion)
    names = {s[0] for s in spans}
    want = {"serve.step", "serve.admit", "serve.prefill", "serve.decode",
            "serve.logits_to_host", "serve.sample", "serve.finish"}
    if speculative != "off":
        want |= {"serve.draft", "serve.commit"}
    assert want <= names
    parents = {"serve.admit": "serve.step", "serve.prefill": "serve.step",
               "serve.decode": "serve.step", "serve.commit": "serve.step",
               "serve.logits_to_host": "serve.step",
               "serve.sample": "serve.step", "serve.finish": "serve.sample",
               "serve.draft": "serve.decode"}
    for i, (name, _, _, meta) in enumerate(spans):
        assert _parent(spans, i) == parents.get(name), name
        if name == "serve.prefill":
            assert set(meta) == {"uid", "offset", "n"}
        if name in ("serve.decode", "serve.draft"):
            assert meta["rows"] >= 1
    prefilled = {m["uid"] for n, _, _, m in spans if n == "serve.prefill"}
    finished = {m["uid"] for n, _, _, m in spans if n == "serve.finish"}
    assert prefilled == finished == {0, 1}
    n_steps = sum(1 for s in spans if s[0] == "serve.step")
    assert n_steps == eng.stats["engine_steps"]


def test_diffusion_engine_spans_nest(tmp_path, dit_model):
    """dit.step encloses one dit.admit per admitted request (with its
    uid), the denoise dispatch and the copy of finished latents."""
    model, params = dit_model
    eng = DS.DiffusionEngine(model, params, DS.DiffusionEngineConfig(
        max_slots=2, n_latent=N_LAT, max_steps=4))
    for r in DS.make_video_requests(3, model.cfg, n_latent=N_LAT,
                                    steps=(2, 3)):
        eng.submit(r)
    spans = _traced(tmp_path, eng.run_to_completion)
    for i, (name, _, _, _) in enumerate(spans):
        assert _parent(spans, i) == (None if name == "dit.step"
                                     else "dit.step"), name
    admitted = [m["uid"] for n, _, _, m in spans if n == "dit.admit"]
    assert sorted(admitted) == [0, 1, 2]
    count = {n: sum(1 for s in spans if s[0] == n) for n in
             ("dit.step", "dit.dispatch", "dit.latents_to_host")}
    assert count["dit.step"] == count["dit.dispatch"] \
        == eng.stats["engine_steps"]
    assert 1 <= count["dit.latents_to_host"] <= 3


# ---------------------------------------------------------------------------
# named programs and scopes
# ---------------------------------------------------------------------------

LM_SCOPES = {"lm.layers", "lm.attn", "lm.kv_write", "lm.kv_read",
             "lm.mlp", "lm.norm", "lm.head"}
DIT_SCOPES = {"dit.qkv", "dit.out_proj", "dit.cross_attn", "dit.mlp",
              "dit.modulate", "sla2.router", "sla2.sparse", "sla2.linear",
              "sla2.combine"}


def _compiled(fn, *args):
    """(module name, the named scopes in its op_name metadata)."""
    text = fn.lower(*args).compile().as_text()
    module = re.search(r"HloModule (\S+?),", text).group(1)
    scopes = {part for op in re.findall(r'op_name="([^"]+)"', text)
              for part in op.split("/") if re.match(r"(lm|dit|sla2)\.", part)}
    return module, scopes


def _lm_program(name, model, params):
    eng = ServeEngine(model, EngineConfig(max_slots=2, max_len=128,
                                          prefill_chunk=32,
                                          speculative="linear", draft_len=2))
    caches = jax.eval_shape(lambda: model.init_paged_caches(
        2, eng.allocator.num_pages))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)    # noqa: E731
    on = jax.ShapeDtypeStruct((2,), jnp.bool_)
    table, lengths = i32(2, eng.max_pages), i32(2)
    if name == "serve_prefill_chunk":
        batch = {"tokens": i32(1, 32), "page_row": i32(eng.max_pages),
                 "offset": i32(), "chunk_len": i32(), "slot": i32()}
        return eng._prefill_fn, (params, batch, caches)
    if name == "serve_decode":
        batch = {"token": i32(2), "page_table": table, "lengths": lengths,
                 "active": on}
        return eng._decode_fn, (params, batch, caches)
    if name == "serve_commit":
        return eng._commit_fn, (caches, table, lengths, i32(2), on, 3)
    batch = {"tokens": i32(2, 3), "page_table": table, "lengths": lengths,
             "active": on, "window_len": i32(2)}
    return eng._verify_fn, (params, batch, caches)


@pytest.mark.parametrize("name,scopes", [
    ("serve_prefill_chunk", LM_SCOPES),
    ("serve_decode", LM_SCOPES | {"sla2.router"}),
    ("serve_verify", LM_SCOPES),
    ("serve_commit", set()),
])
def test_lm_programs_named_and_scoped(qwen3_smoke, name, scopes):
    _, model = qwen3_smoke
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    fn, args = _lm_program(name, model, params)
    module, found = _compiled(fn, *args)
    assert module == f"jit_{name}"
    assert scopes <= found, scopes - found


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_dit_denoise_step_named_and_scoped(dit_model, attn_impl):
    """The SLA2 scopes hold in the O(N^2) reference and in the kernel
    operator the chip runs (interpreted here)."""
    model, params = dit_model
    cfg = DS.DiffusionEngineConfig(max_slots=2, n_latent=N_LAT, max_steps=4,
                                   attn_impl=attn_impl)
    eng = DS.DiffusionEngine(model, params, cfg)
    args = (eng.params, eng._latents, eng._kv_k, eng._kv_v, eng._mods_b,
            eng._mods_f, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.float32), jnp.zeros((2,), bool))
    module, found = _compiled(eng._step_fn, *args)
    assert module == "jit_dit_denoise_step"
    assert DIT_SCOPES <= found, DIT_SCOPES - found


@pytest.mark.parametrize("program", ["dit_text_kv", "dit_step_mods"])
def test_dit_admission_programs_named(dit_model, program):
    model, params = dit_model
    eng = DS.DiffusionEngine(model, params, DS.DiffusionEngineConfig(
        max_slots=2, n_latent=N_LAT, max_steps=4))
    fn, arg = {"dit_text_kv": (eng._kv_fn, jnp.zeros(
        (1, model.cfg.n_text, model.cfg.d_model))),
        "dit_step_mods": (eng._mods_fn, jnp.zeros((4,)))}[program]
    module, _ = _compiled(fn, params, arg)
    assert module == f"jit_{program}"


# ---------------------------------------------------------------------------
# the logits counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("speculative", ["off", "ngram"])
def test_logits_to_host_bytes_counts_every_pull(qwen3_smoke, qwen3_params,
                                                make_prompts, speculative):
    """The counter is the nbytes of every logits array the host pulled:
    each decode or verify dispatch's, and each prompt's final chunk's."""
    cfg, model = qwen3_smoke
    kw = {"speculative": speculative, "draft_len": 2} \
        if speculative != "off" else {}
    prompts = make_prompts(cfg, [40, 20, 33])
    eng = _serve_engine(model, qwen3_params, prompts, **kw)
    pulled, chunks = [], []

    def recording(fn, sink):
        def run(*args):
            logits, caches = fn(*args)
            sink.append(logits.nbytes)
            return logits, caches
        return run

    step_fn = "_verify_fn" if speculative != "off" else "_decode_fn"
    setattr(eng, step_fn, recording(getattr(eng, step_fn), pulled))
    eng._prefill_fn = recording(eng._prefill_fn, chunks)
    eng.run_to_completion()
    assert eng.stats["preemptions"] == 0 and pulled
    # one pull per decode dispatch, one per prompt (its last chunk)
    want = sum(pulled) + len(prompts) * chunks[0]
    assert eng.stats["logits_to_host_bytes"] == want
    assert chunks[0] == 4 * cfg.vocab_size              # (1, V) float32
