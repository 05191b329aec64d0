"""The paged layer scan (``models/transformer._paged_stack``) over the
stacked pool it carries.

MLA layers (an ``in_place`` mixer) read and write the carried stack in
place at the scan's layer index; every other mixer gets its layer's slice
and writes it back (``_mix_stacked``).  Checked on the CPU at smoke size:
the traced scan body holds no slice of one layer's MLA pool (and still
holds one for a dense pool), and the in-place MLA programs give logits
and pools bit-identical to a Python loop over the layers on hand-sliced
caches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.api import build_model

SLOTS, MAX_P, CHUNK, WINDOW = 3, 4, 32, 3
SLICE_OPS = ("dynamic_slice", "dynamic_update_slice")


def _stack(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_groups >= 2, "the scan must run over several groups"
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mla_stack():
    return _stack("deepseek_v2_lite")


@pytest.fixture(scope="module")
def dense_stack():
    return _stack("qwen3_14b")


def _page_table():
    """Slot s owns pages 1 + s * MAX_P ...; page 0 is the trash page."""
    return jnp.arange(1, SLOTS * MAX_P + 1, dtype=jnp.int32) \
        .reshape(SLOTS, MAX_P)


def _caches(model):
    return model.init_paged_caches(SLOTS, SLOTS * MAX_P + 1)


def _chunk(cfg, slot, offset, n, seed):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, CHUNK), 0,
                              cfg.vocab_size, jnp.int32)
    return {"tokens": toks, "page_row": _page_table()[slot],
            "offset": jnp.int32(offset), "chunk_len": jnp.int32(n),
            "slot": jnp.int32(slot)}


def _decode_batch(cfg, lengths, active, seed):
    return {"token": jax.random.randint(jax.random.PRNGKey(seed), (SLOTS,),
                                        0, cfg.vocab_size, jnp.int32),
            "page_table": _page_table(),
            "lengths": jnp.asarray(lengths, jnp.int32),
            "active": jnp.asarray(active)}


def _verify_batch(cfg, lengths, active, window_len, seed):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(seed),
                                         (SLOTS, WINDOW), 0, cfg.vocab_size,
                                         jnp.int32),
            "page_table": _page_table(),
            "lengths": jnp.asarray(lengths, jnp.int32),
            "active": jnp.asarray(active),
            "window_len": jnp.asarray(window_len, jnp.int32)}


def _program(name, cfg):
    """(model function name, its batch) for one step of each program."""
    if name == "prefill_chunk":
        # a second chunk: one more complete block joins the totals, the
        # padding rows hit the trash page
        return name, _chunk(cfg, 0, CHUNK, 20, seed=3)
    # slot 1 completes a block; slot 2 is inactive, so its rows write the
    # trash page
    if name == "decode_paged":
        return name, _decode_batch(cfg, [32, 47, 7], [True, True, False], 4)
    return name, _verify_batch(cfg, [32, 47, 7], [True, True, False],
                               [3, 2, 3], seed=5)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _scan_slices(fn, *args):
    """Shapes of the operands and results of every dynamic slice and
    dynamic update slice inside the layer scan's body, with a leading
    axis of 1 (a slice of the stack before it is squeezed) dropped."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    shapes = []
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name != "scan":
            continue
        for inner in _eqns(eqn.params["jaxpr"].jaxpr):
            if inner.primitive.name in SLICE_OPS:
                shapes += [tuple(v.aval.shape)[1:] if v.aval.shape[:1] == (1,)
                           else tuple(v.aval.shape)
                           for v in (*inner.invars, *inner.outvars)
                           if hasattr(v.aval, "shape")]
    return shapes


@pytest.mark.parametrize("program", ["decode_paged", "prefill_chunk"])
@pytest.mark.parametrize("stack", ["mla", "dense"])
def test_scan_body_slices_no_mla_pool(request, stack, program):
    """The scan body never slices one layer's MLA latent pages or pooled
    latents out of the stack, nor writes such a slice back; a dense stack
    still does both (through ``_mix_stacked``), as before."""
    cfg, model, params = request.getfixturevalue(f"{stack}_stack")
    caches = _caches(model)
    name, batch = _program(program, cfg)
    shapes = _scan_slices(getattr(model, name), params, batch, caches)
    group = caches["groups"]["l0"]
    if stack == "mla":
        per_layer = {group["mla"][k].shape[1:]
                     for k in ("k_pages", "pooled_lat")}
        assert not per_layer & set(shapes), (per_layer, shapes)
    else:
        per_layer = group["attn"]["k_pages"].shape[1:]
        assert shapes.count(per_layer) >= 2, (per_layer, shapes)


def _sliced_stack(params, cfg, x, caches, rows, op, **kw):
    """The layer stack as a Python loop over the layers, each mixer on its
    own hand-sliced params and cache: the semantics the scan keeps."""
    caches, total = dict(caches), None
    new_pref = []
    for i, kind in enumerate(cfg.first_kinds):
        x, lc, n = T._layer_paged(params["prefix_layers"][i], cfg, kind, x,
                                  caches["prefix_layers"][i], rows, op, kw)
        new_pref.append(lc)
        total = T._add_counters(total, n)
    if cfg.first_kinds:
        caches["prefix_layers"] = new_pref
    groups = []
    for g in range(cfg.n_groups):
        gp, gc = jax.tree.map(lambda a: a[g],
                              (params["groups"], caches["groups"]))
        new = {}
        for i, kind in enumerate(cfg.layer_kinds):
            x, new[f"l{i}"], n = T._layer_paged(gp[f"l{i}"], cfg, kind, x,
                                                gc[f"l{i}"], rows, op, kw)
            total = T._add_counters(total, n)
        groups.append(new)
    caches["groups"] = jax.tree.map(lambda *a: jnp.stack(a), *groups)
    return L.rmsnorm(params["final_norm"], x), caches, total


def _filled(model, cfg, params):
    """Slot 0 holds 32 tokens and slot 1 holds 47, prefilled in chunks
    through the scan; slot 2 is empty."""
    prefill = jax.jit(model.prefill_chunk)
    caches = _caches(model)
    for slot, offset, n, seed in ((0, 0, CHUNK, 1), (1, 0, CHUNK, 2),
                                  (1, CHUNK, 15, 6)):
        _, caches = prefill(params, _chunk(cfg, slot, offset, n, seed),
                            caches)
    return caches


@pytest.mark.parametrize("program",
                         ["prefill_chunk", "decode_paged", "decode_verify"])
def test_in_place_mla_matches_sliced_layers(mla_stack, monkeypatch,
                                            program):
    """Each MLA paged program through the scan (pool read and written in
    place) against the same program over hand-sliced layers: logits and
    every pool leaf, ``h_tot``/``z_tot`` and the trash page included, are
    equal bit for bit."""
    cfg, model, params = mla_stack
    caches = _filled(model, cfg, params)
    name, batch = _program(program, cfg)
    fn = getattr(model, name)
    got = jax.jit(lambda p, b, c: fn(p, b, c))(params, batch, caches)
    monkeypatch.setattr(T, "_paged_stack", _sliced_stack)
    want = jax.jit(lambda p, b, c: fn(p, b, c))(params, batch, caches)
    flat_got, tree_got = jax.tree.flatten_with_path(got)
    flat_want, tree_want = jax.tree.flatten_with_path(want)
    assert tree_got == tree_want
    for (path, a), (_, b) in zip(flat_got, flat_want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            jax.tree_util.keystr(path)
