"""Unified model API — one handle per architecture for train/serve/dry-run.

``build_model(cfg)`` dispatches on the config type and returns a ``Model``
with a uniform surface:

    model.init(key)                      -> params
    model.loss(params, batch)            -> (loss, metrics)
    model.train_inputs(seq, batch)       -> {name: ShapeDtypeStruct}
    model.init_caches(batch, max_len)    -> cache pytree (concrete zeros)
    model.prefill(params, batch, caches) -> (logits, caches)
    model.decode(params, batch, caches)  -> (logits, caches)
    model.prefill_inputs(seq, batch)     -> specs for the prefill batch
    model.decode_inputs(batch)           -> specs for one decode step

The *_inputs methods produce ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no allocation) — exactly what ``jit(...).lower()`` wants for the
multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import dit as D
from repro.models import encdec as E
from repro.models import transformer as T
from repro.models import vlm as V

f32 = jnp.float32
bf16 = jnp.bfloat16
i32 = jnp.int32
Spec = jax.ShapeDtypeStruct


@dataclasses.dataclass
class Model:
    """The uniform per-architecture handle ``build_model`` returns: config
    plus callables for train loss, static prefill/decode, paged serving
    (chunked prefill, per-slot decode, swap, speculative verify/commit/
    draft) and the ShapeDtypeStruct input builders for dry-run lowering.
    Optional fields are None when the architecture lacks that path."""
    kind: str                     # lm | vlm | audio | dit
    cfg: Any
    init: Callable
    loss: Callable
    train_inputs: Callable
    init_caches: Optional[Callable] = None
    prefill: Optional[Callable] = None
    decode: Optional[Callable] = None
    prefill_inputs: Optional[Callable] = None
    decode_inputs: Optional[Callable] = None
    # paged serving (continuous batching with per-slot offsets) covers every
    # LM layer kind: attention pages K/V, MLA pages the compressed latent,
    # recurrent mixers (mamba/mlstm/slstm, incl. hybrid blocks) ride the
    # same plumbing with per-slot state checkpoints.  The paged hot path is
    # selected by cfg.paged_impl: 'fused' runs the Pallas page-table kernels
    # (sla2_decode_paged), 'gather' the jnp reference; use with_overrides()
    # to switch on a built model.
    init_paged_caches: Optional[Callable] = None
    prefill_chunk: Optional[Callable] = None
    decode_paged: Optional[Callable] = None
    # page-granular slot extract/insert for the preemption scheduler's
    # swap-out/swap-in path (serve/engine.SwapPool)
    swap_out: Optional[Callable] = None
    swap_in: Optional[Callable] = None
    # prefix-cache support (serve/prefix_cache.py): per-slot linear-totals
    # snapshot extract/restore and the copy-on-write page duplication
    extract_totals: Optional[Callable] = None
    insert_totals: Optional[Callable] = None
    copy_page: Optional[Callable] = None
    # speculative decoding (serve/speculative.py): multi-token verify over
    # a draft window + deferred accepted-prefix commit, and the linear-
    # branch drafter (draft_* are None unless the mechanism carries a
    # linear branch, i.e. sla2, AND the stack is attention-only; the
    # model-free ngram drafter works for every family)
    decode_verify: Optional[Callable] = None
    commit_window: Optional[Callable] = None
    draft_init: Optional[Callable] = None
    draft_step: Optional[Callable] = None
    # True when any layer keeps per-slot state (SLA2 linear totals, MLA
    # totals, recurrent checkpoints) that the serving prefix cache must
    # snapshot on insert and restore on hit.
    has_slot_state: bool = False
    # diffusion serving (serve/diffusion.DiffusionEngine): per-request
    # constants precomputed once at admission (text cross-attention K/V,
    # per-timestep adaLN modulation tables) + the cached-path denoise
    # step.  None for every non-diffusion architecture.
    precompute_text_kv: Optional[Callable] = None
    precompute_step_mods: Optional[Callable] = None
    denoise: Optional[Callable] = None

    def with_overrides(self, **overrides) -> "Model":
        """Rebuild this model with config fields replaced — e.g.
        ``model.with_overrides(paged_impl='gather')`` for the serving
        baseline, or ``decode_quant_bits='int8'`` for low-bit decode."""
        return build_model(dataclasses.replace(self.cfg, **overrides))

    def abstract_params(self, key=None):
        """ShapeDtypeStruct pytree of the params (no allocation)."""
        k = jax.random.PRNGKey(0) if key is None else key
        return jax.eval_shape(self.init, k)

    def abstract_caches(self, batch: int, max_len: int):
        """ShapeDtypeStruct pytree of the static caches (no allocation)."""
        return jax.eval_shape(
            lambda: self.init_caches(batch, max_len))


# ---------------------------------------------------------------------------

def _lm_model(cfg: T.ModelConfig) -> Model:
    paged = dict(
        init_paged_caches=lambda batch, num_pages, **kw:
            T.init_paged_caches(cfg, batch, num_pages, **kw),
        prefill_chunk=lambda p, b, c: T.prefill_chunk(
            p, cfg, b["tokens"], c, page_row=b["page_row"],
            offset=b["offset"], chunk_len=b["chunk_len"], slot=b["slot"]),
        decode_paged=lambda p, b, c: T.decode_paged(
            p, cfg, b["token"], c, page_table=b["page_table"],
            lengths=b["lengths"], active=b["active"]),
        swap_out=lambda c, page_row, slot: T.swap_out_slot(
            cfg, c, page_row, slot),
        swap_in=lambda c, page_row, slot, state: T.swap_in_slot(
            cfg, c, page_row, slot, state),
        extract_totals=lambda c, slot: T.extract_linear_totals(cfg, c, slot),
        insert_totals=lambda c, slot, st: T.insert_linear_totals(
            cfg, c, slot, st),
        copy_page=lambda c, src, dst: T.copy_kv_page(cfg, c, src, dst),
        decode_verify=lambda p, b, c: T.decode_verify(
            p, cfg, b["tokens"], c, page_table=b["page_table"],
            lengths=b["lengths"], active=b["active"],
            window_len=b["window_len"]),
        commit_window=lambda c, page_table, lengths, accepted, active,
            window: T.commit_window(cfg, c, page_table, lengths, accepted,
                                    active, window),
        has_slot_state=T.has_slot_state(cfg),
    )
    if T.has_linear_drafter(cfg):
        paged.update(
            draft_init=lambda c, b: T.draft_init(
                cfg, c, b["page_table"], b["lengths"], b["active"]),
            draft_step=lambda p, b, st: T.draft_step(
                p, cfg, b["token"], st, positions=b["positions"],
                active=b["active"]),
        )
    return Model(
        kind="lm", cfg=cfg,
        init=lambda key: T.init_model(key, cfg),
        loss=lambda p, b: T.lm_loss(p, cfg, b),
        train_inputs=lambda seq, batch: {
            "tokens": Spec((batch, seq), i32),
            "labels": Spec((batch, seq), i32)},
        init_caches=lambda batch, max_len, **kw: T.init_caches(
            cfg, batch, max_len, **kw),
        prefill=lambda p, b, c: T.prefill(p, cfg, b["tokens"], c),
        decode=lambda p, b, c: T.decode_step(p, cfg, b["token"], c),
        prefill_inputs=lambda seq, batch: {"tokens": Spec((batch, seq), i32)},
        decode_inputs=lambda batch: {"token": Spec((batch,), i32)},
        **paged,
    )


def _vlm_model(cfg: T.ModelConfig) -> Model:
    n_img = cfg.prefix_len
    return Model(
        kind="vlm", cfg=cfg,
        init=lambda key: T.init_model(key, cfg),
        loss=lambda p, b: V.vlm_loss(p, cfg, b),
        train_inputs=lambda seq, batch: {
            "image_embeds": Spec((batch, n_img, cfg.d_model), bf16),
            "tokens": Spec((batch, seq - n_img), i32),
            "labels": Spec((batch, seq - n_img), i32)},
        init_caches=lambda batch, max_len: T.init_caches(cfg, batch, max_len),
        prefill=lambda p, b, c: V.vlm_prefill(p, cfg, b["image_embeds"],
                                              b["tokens"], c),
        decode=lambda p, b, c: V.vlm_decode_step(p, cfg, b["token"], c),
        prefill_inputs=lambda seq, batch: {
            "image_embeds": Spec((batch, n_img, cfg.d_model), bf16),
            "tokens": Spec((batch, seq - n_img), i32)},
        decode_inputs=lambda batch: {"token": Spec((batch,), i32)},
    )


def _audio_model(cfg: E.EncDecConfig) -> Model:
    return Model(
        kind="audio", cfg=cfg,
        init=lambda key: E.init_encdec(key, cfg),
        loss=lambda p, b: E.encdec_loss(p, cfg, b),
        train_inputs=lambda seq, batch: {
            "frames": Spec((batch, cfg.n_frames, cfg.d_model), bf16),
            "tokens": Spec((batch, seq), i32),
            "labels": Spec((batch, seq), i32)},
        init_caches=lambda batch, max_len: E.init_encdec_caches(
            cfg, batch, max_len),
        prefill=lambda p, b, c: E.prefill(p, cfg, b["frames"], b["tokens"],
                                          c),
        decode=lambda p, b, c: E.decode_step(p, cfg, b["token"], c),
        prefill_inputs=lambda seq, batch: {
            "frames": Spec((batch, cfg.n_frames, cfg.d_model), bf16),
            "tokens": Spec((batch, seq), i32)},
        decode_inputs=lambda batch: {"token": Spec((batch,), i32)},
    )


def _dit_model(cfg: D.DiTConfig) -> Model:
    def denoise(p, b, _c):
        x = D.denoise_step(p, cfg, b["latents"], b.get("text"),
                           b.get("time"), b["dt"],
                           text_kv=b.get("text_kv"), mods=b.get("mods"))
        return x, _c

    return Model(
        kind="dit", cfg=cfg,
        init=lambda key: D.init_dit(key, cfg),
        loss=lambda p, b: D.flow_matching_loss(p, cfg, b),
        train_inputs=lambda seq, batch: {
            "latents": Spec((batch, seq, cfg.c_latent), f32),
            "text": Spec((batch, cfg.n_text, cfg.d_model), bf16),
            "noise": Spec((batch, seq, cfg.c_latent), f32),
            "time": Spec((batch,), f32)},
        init_caches=lambda batch, max_len: {},   # diffusion: no KV cache
        prefill=denoise,                          # one denoise step == serve
        decode=denoise,
        prefill_inputs=lambda seq, batch: {
            "latents": Spec((batch, seq, cfg.c_latent), f32),
            "text": Spec((batch, cfg.n_text, cfg.d_model), bf16),
            "time": Spec((batch,), f32), "dt": Spec((batch,), f32)},
        decode_inputs=None,
        # diffusion-serving surface: admission-time precompute of the
        # per-request constants + the cached-path denoise dispatch
        precompute_text_kv=lambda p, text: D.precompute_text_kv(
            p, cfg, text),
        precompute_step_mods=lambda p, t: D.precompute_step_mods(
            p, cfg, t),
        denoise=denoise,
    )


def build_model(cfg) -> Model:
    """Dispatch a config dataclass to its Model handle (see module
    docstring for the uniform surface)."""
    if isinstance(cfg, D.DiTConfig):
        return _dit_model(cfg)
    if isinstance(cfg, E.EncDecConfig):
        return _audio_model(cfg)
    if isinstance(cfg, T.ModelConfig):
        if cfg.family == "vlm":
            return _vlm_model(cfg)
        return _lm_model(cfg)
    raise TypeError(f"unknown config type: {type(cfg)}")
