"""Step-level diffusion serving: continuous batching of DiT denoise steps.

The paper's headline workload is video diffusion — bidirectional SLA2 over
~32k latent tokens, re-routed every denoise step, with **no KV cache** —
which is a different serving problem from token decode:

  * the unit of scheduling is one *denoise step* (a full forward over the
    request's whole latent), not one generated token;
  * every request declares a fixed ``n_steps`` up front, so remaining work
    is exact — admission and SLO accounting never guess at output length;
  * a request's footprint is one constant batch slot (latents + cached
    constants); nothing grows, so there is no page pool, no preemption and
    no swap — the scheduler is pure FCFS admission over free slots.

One ``DiffusionEngine.step()`` = admit into free slots + ONE batched
denoise dispatch advancing every active request by exactly one Euler step
of the rectified-flow ODE.  Requests join and leave the batch between
steps; inactive slots are masked and their rows frozen.

Two per-request constants are precomputed once at admission instead of
inside every step (``models/dit.precompute_text_kv`` /
``precompute_step_mods``): the text cross-attention K/V projections and
the adaLN modulation table over the request's whole timestep schedule —
each step then *gathers* its modulation row.

The SLA2 hot path is the bidirectional block-sparse flash kernel
(``kernels/sla2_fwd.sparse_flash_fwd``); ``attn_impl`` mirrors the paged
engine's gather-vs-fused pattern: ``'fused'`` runs the Pallas kernel,
``'gather'`` the jnp gathered-tiles parity oracle, ``'reference'`` the
O(N^2) einsum, and ``'auto'`` resolves like ``paged_impl='auto'``
(gather on CPU, fused elsewhere).  ``mechanism`` overrides the model's
self-attention math per engine (``models/dit.MECHANISM_ATTENTION``) so
SALAD/SVG-EAR-style ablations run on the same harness.

Batched interleaved serving is **bit-identical** to per-request
sequential denoising (``denoise_sequential``): every op in the denoise
step is independent per batch row, the oracle runs the engine's compiled
batch width, and the cached constants are computed per request with
batch-1 shapes in both paths.  tests/test_diffusion.py
and every benchmarks/fig12_diffusion.py run assert this with
``np.array_equal``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

# attn_impl -> models/dit.DiTConfig.sla2_impl.  'fused' is the Pallas
# block-sparse flash kernel, 'gather' the jnp gathered-tiles parity
# oracle, 'reference' the O(N^2) einsum path.  tools/gen_path_matrix.py
# renders this table into docs/paths.md.
ATTN_IMPLS = {"fused": "kernel", "gather": "gather", "reference": "ref"}


def resolve_attn_impl(attn_impl: str) -> str:
    """Resolve ``attn_impl='auto'`` the same way the paged engine resolves
    ``paged_impl='auto'``: the jnp gather path on AUTO_GATHER_BACKENDS
    (CPU, where Pallas interprets), the fused kernel everywhere else."""
    if attn_impl != "auto":
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(f"unknown attn_impl {attn_impl!r}; one of "
                             f"{('auto', *ATTN_IMPLS)}")
        return attn_impl
    from repro.models.attention import AUTO_GATHER_BACKENDS
    return ("gather" if jax.default_backend() in AUTO_GATHER_BACKENDS
            else "fused")


@dataclasses.dataclass
class VideoRequest:
    """One video denoise request: initial noise latents (N, c_latent),
    the text conditioning embedding (n_text, d_model) and a fixed step
    count.  The engine fills the bookkeeping fields; ``output`` holds the
    final (N, c_latent) latents after exactly ``n_steps`` Euler steps."""
    uid: int
    latents: np.ndarray
    text: np.ndarray
    n_steps: int
    arrival: int = -1              # scheduler FCFS stamp
    steps_done: int = 0
    t_submit: int = -1             # engine step at submit()
    t_admit: int = -1              # engine step when a slot was taken
    t_finish: int = -1             # engine step after the last denoise step
    output: Optional[np.ndarray] = None


class StepScheduler:
    """Host-side step-level scheduler: FCFS admission over a fixed pool
    of batch slots, no preemption.

    Diffusion makes the scheduling problem exact: a request's footprint
    is one constant slot and its remaining work is ``n_steps -
    steps_done`` — so the only policy decision is admission order, and
    FCFS (ties broken by submit order) guarantees no starvation: slots
    free deterministically and the head of the queue always takes the
    next one.  Pure host logic, unit-testable without a model
    (tests/test_diffusion_scheduler.py)."""

    def __init__(self, max_slots: int):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_slots = max_slots
        self.waiting: deque = deque()
        self.active: Dict[int, VideoRequest] = {}
        self._clock = 0

    def submit(self, req: VideoRequest) -> None:
        """Stamp FCFS arrival order and enqueue."""
        req.arrival = self._clock
        self._clock += 1
        self.waiting.append(req)

    def admit(self) -> List[Tuple[int, VideoRequest]]:
        """Move waiting requests into free slots (FCFS, lowest slot
        first); returns the newly admitted (slot, request) pairs."""
        admitted = []
        free = [s for s in range(self.max_slots) if s not in self.active]
        while self.waiting and free:
            slot = free.pop(0)
            req = self.waiting.popleft()
            self.active[slot] = req
            admitted.append((slot, req))
        return admitted

    def advance(self, slots) -> List[Tuple[int, VideoRequest]]:
        """Credit one completed denoise step to each given active slot;
        requests reaching their configured ``n_steps`` are removed from
        the batch and returned as finished (slot, request) pairs."""
        finished = []
        for slot in slots:
            req = self.active[slot]
            req.steps_done += 1
            if req.steps_done >= req.n_steps:
                finished.append((slot, self.active.pop(slot)))
        return finished

    @property
    def idle(self) -> bool:
        """True when nothing is waiting or active."""
        return not self.waiting and not self.active


@dataclasses.dataclass(frozen=True)
class DiffusionEngineConfig:
    """Engine knobs.  ``n_latent`` is the (static) latent token count
    every request must carry; ``max_steps`` caps per-request step counts
    (it sizes the per-slot modulation tables); ``mechanism`` overrides
    the model's self-attention math (None keeps the model's own);
    ``attn_impl`` picks the SLA2 implementation (see module docstring);
    ``mesh`` places the params (model-axis only) and the per-slot arrays
    (slot axis over DP) with the distributed/sharding NamedShardings —
    the diffusion analogue of ``EngineConfig.mesh`` (there is no page
    pool here, a request's whole footprint is one batch slot)."""
    max_slots: int = 4
    n_latent: int = 64
    max_steps: int = 32
    mechanism: Optional[str] = None
    attn_impl: str = "auto"
    mesh: Optional[Any] = None


def _timestep_schedule(n_steps: int, max_steps: int) -> np.ndarray:
    """Linear rectified-flow schedule t_i = 1 - i/n_steps, padded with
    zeros to the (static) table length.  Shared by the engine and the
    sequential oracle so cached modulation rows are bit-identical."""
    t = np.zeros((max_steps,), np.float32)
    i = np.arange(n_steps, dtype=np.float32)
    t[:n_steps] = 1.0 - i / n_steps
    return t


def _resolved_model(model, mechanism: Optional[str], attn_impl: str):
    """The override model serving (mechanism, attn_impl), memoized on the
    base model object so engines and oracles share jit caches."""
    eff_mech = mechanism or model.cfg.mechanism
    sla2_impl = ATTN_IMPLS[resolve_attn_impl(attn_impl)]
    cache = model.__dict__.setdefault("_diffusion_models", {})
    key = (eff_mech, sla2_impl)
    if key not in cache:
        if (eff_mech, sla2_impl) == (model.cfg.mechanism,
                                     model.cfg.sla2_impl):
            cache[key] = model
        else:
            cache[key] = model.with_overrides(mechanism=eff_mech,
                                              sla2_impl=sla2_impl)
    return cache[key]


def _step_fns(model):
    """Jitted (denoise step, text-KV precompute, step-mods precompute)
    for an override model, built once and cached on it.  The engine and
    the sequential oracle both call the step fn at batch ``max_slots`` —
    one compiled program, per-row-independent ops, hence bit-identical
    rows."""
    if "_diffusion_fns" in model.__dict__:
        return model.__dict__["_diffusion_fns"]

    @jax.jit
    def dit_denoise_step(params, lat, kv_k, kv_v, mods_b, mods_f, step_idx,
                         dt, active):
        bi = jnp.arange(lat.shape[0])
        mods = {"blocks": mods_b[:, bi, step_idx],   # (L, B, 6d)
                "final": mods_f[bi, step_idx]}       # (B, 2d)
        x, _ = model.denoise(
            params, {"latents": lat, "dt": dt,
                     "text_kv": (kv_k, kv_v), "mods": mods}, None)
        return jnp.where(active[:, None, None], x, lat)

    @jax.jit
    def dit_text_kv(params, text):
        return model.precompute_text_kv(params, text)

    @jax.jit
    def dit_step_mods(params, t):
        return model.precompute_step_mods(params, t)

    fns = (dit_denoise_step, dit_text_kv, dit_step_mods)
    model.__dict__["_diffusion_fns"] = fns
    return fns


def _check_request(req: VideoRequest, mcfg, cfg: DiffusionEngineConfig):
    if req.n_steps < 1 or req.n_steps > cfg.max_steps:
        raise ValueError(f"request {req.uid}: n_steps={req.n_steps} "
                         f"outside [1, max_steps={cfg.max_steps}]")
    want_lat = (cfg.n_latent, mcfg.c_latent)
    want_text = (mcfg.n_text, mcfg.d_model)
    if tuple(req.latents.shape) != want_lat:
        raise ValueError(f"request {req.uid}: latents {req.latents.shape} "
                         f"!= {want_lat}")
    if tuple(req.text.shape) != want_text:
        raise ValueError(f"request {req.uid}: text {req.text.shape} "
                         f"!= {want_text}")


class DiffusionEngine:
    """Continuous step-level batching of DiT video denoise requests.

    One ``step()`` = FCFS admission into free slots + ONE batched denoise
    dispatch advancing every active request by one Euler step (the SLA2
    router re-routes inside the dispatch — routing is per step, never
    cached).  Per-request constants (text K/V, modulation tables) are
    computed at admission with batch-1 shapes and scattered into the slot
    arrays, so batched outputs stay bit-identical to sequential
    denoising.  See the module docstring for the full design."""

    def __init__(self, model, params, cfg: DiffusionEngineConfig):
        if model.kind != "dit":
            raise ValueError(f"DiffusionEngine needs a dit model, got "
                             f"{model.kind!r}")
        self.cfg = cfg
        self.base_model = model
        self.model = _resolved_model(model, cfg.mechanism, cfg.attn_impl)
        self.params = params
        mcfg = self.model.cfg
        need = {"sla2": "sla2", "sla": "sla"}.get(mcfg.mechanism)
        if need and need not in params["blocks"]:
            raise ValueError(
                f"mechanism={mcfg.mechanism!r} needs params['blocks']"
                f"[{need!r}] — init the model with that mechanism")
        if mcfg.mechanism != "full" and cfg.n_latent % mcfg.block_q:
            raise ValueError(f"n_latent={cfg.n_latent} must be a multiple "
                             f"of block_q={mcfg.block_q}")
        self._step_fn, self._kv_fn, self._mods_fn = _step_fns(self.model)
        self.scheduler = StepScheduler(cfg.max_slots)

        s, n, li = cfg.max_slots, cfg.n_latent, mcfg.n_layers
        h, dh, m = mcfg.num_heads, mcfg.head_dim, mcfg.n_text
        d = mcfg.d_model
        pdt = mcfg.param_dtype
        self._latents = jnp.zeros((s, n, mcfg.c_latent), jnp.float32)
        self._kv_k = jnp.zeros((li, s, h, m, dh), pdt)
        self._kv_v = jnp.zeros((li, s, h, m, dh), pdt)
        self._mods_b = jnp.zeros((li, s, cfg.max_steps, 6 * d), jnp.float32)
        self._mods_f = jnp.zeros((s, cfg.max_steps, 2 * d), jnp.float32)
        if cfg.mesh is not None:
            # slot arrays over DP (batch_specs shards dim 0, or dim 1 for
            # the layer-leading KV/mod tables), params model-axis only —
            # per-slot math is row-independent, so placement cannot
            # change the bit pattern of any slot's denoise trajectory
            from repro.distributed import sharding as shardlib
            slot_arrays = {"latents": self._latents, "kv_k": self._kv_k,
                           "kv_v": self._kv_v, "mods_b": self._mods_b,
                           "mods_f": self._mods_f}
            placed = jax.device_put(
                slot_arrays, shardlib.logical_to_shardings(
                    shardlib.batch_specs(slot_arrays, cfg.mesh), cfg.mesh))
            self._latents, self._kv_k, self._kv_v = (
                placed["latents"], placed["kv_k"], placed["kv_v"])
            self._mods_b, self._mods_f = placed["mods_b"], placed["mods_f"]
            self.params = jax.device_put(
                params, shardlib.serving_param_shardings(params, cfg.mesh))
        self._dt = np.zeros((s,), np.float32)
        self._clock = 0
        self.stats = {"engine_steps": 0, "denoise_steps": 0,
                      "admitted": 0, "completed": 0, "occupancy_sum": 0}

    def submit(self, req: VideoRequest) -> None:
        """Validate and enqueue a request (FCFS)."""
        _check_request(req, self.model.cfg, self.cfg)
        req.t_submit = self._clock
        self.scheduler.submit(req)

    def _admit(self) -> None:
        for slot, req in self.scheduler.admit():
            with TraceAnnotation("dit.admit", uid=req.uid):
                req.t_admit = self._clock
                self._latents = self._latents.at[slot].set(
                    jnp.asarray(req.latents, jnp.float32))
                kk, vv = self._kv_fn(self.params,
                                     jnp.asarray(req.text)[None])
                self._kv_k = self._kv_k.at[:, slot].set(kk[:, 0])
                self._kv_v = self._kv_v.at[:, slot].set(vv[:, 0])
                sched = jnp.asarray(
                    _timestep_schedule(req.n_steps, self.cfg.max_steps))
                mods = self._mods_fn(self.params, sched)
                self._mods_b = self._mods_b.at[:, slot].set(mods["blocks"])
                self._mods_f = self._mods_f.at[slot].set(mods["final"])
                self._dt[slot] = 1.0 / req.n_steps
                self.stats["admitted"] += 1

    def step(self) -> List[VideoRequest]:
        """Admit + one batched denoise dispatch.  Returns the requests
        that completed their final step this engine step (their
        ``output`` is filled and their slot freed)."""
        with TraceAnnotation("dit.step"):
            self._admit()
            active_slots = sorted(self.scheduler.active)
            if not active_slots:
                return []
            s = self.cfg.max_slots
            active = np.zeros((s,), bool)
            step_idx = np.zeros((s,), np.int32)
            for slot in active_slots:
                active[slot] = True
                step_idx[slot] = self.scheduler.active[slot].steps_done
            with TraceAnnotation("dit.dispatch", rows=len(active_slots)):
                self._latents = self._step_fn(
                    self.params, self._latents, self._kv_k, self._kv_v,
                    self._mods_b, self._mods_f, jnp.asarray(step_idx),
                    jnp.asarray(self._dt), jnp.asarray(active))
            self._clock += 1
            self.stats["engine_steps"] += 1
            self.stats["denoise_steps"] += len(active_slots)
            self.stats["occupancy_sum"] += len(active_slots)
            done = []
            finished = self.scheduler.advance(active_slots)
            if finished:
                with TraceAnnotation("dit.latents_to_host"):
                    lat = np.asarray(self._latents)   # one device->host copy
                for slot, req in finished:
                    req.output = lat[slot].copy()
                    req.t_finish = self._clock
                    self.stats["completed"] += 1
                    done.append(req)
            return done

    def run_to_completion(self, max_steps: int = 100_000,
                          livelock_after: int = 1_000
                          ) -> List[VideoRequest]:
        """Step until every submitted request completed.  Raises on
        livelock (steps without progress) instead of spinning."""
        finished: List[VideoRequest] = []
        stalled = 0
        while not self.scheduler.idle:
            if self.stats["engine_steps"] >= max_steps:
                raise RuntimeError(f"exceeded max_steps={max_steps}")
            done = self.step()
            finished.extend(done)
            progressed = bool(done) or bool(self.scheduler.active)
            stalled = 0 if progressed else stalled + 1
            if stalled > livelock_after:
                raise RuntimeError(
                    f"no progress for {livelock_after} engine steps "
                    f"({len(self.scheduler.waiting)} waiting)")
        return finished


def denoise_sequential(model, params, requests,
                       cfg: Optional[DiffusionEngineConfig] = None
                       ) -> Dict[int, np.ndarray]:
    """The exactness oracle: denoise each request alone, one dispatch per
    step, through the same cached-constants path as the engine.  Returns
    {uid: final latents}.  DiffusionEngine's batched interleaved outputs
    must match this bit-for-bit.

    The request fills every row of a ``cfg.max_slots``-row batch, so the
    oracle runs the program the engine compiled.  XLA does not promise
    the same rounding for programs compiled at different batch sizes (on
    a TPU v5e the batch-1 and batch-2 denoise steps differ), while within
    one program every row is computed alike."""
    cfg = cfg or DiffusionEngineConfig()
    m = _resolved_model(model, cfg.mechanism, cfg.attn_impl)
    step_fn, kv_fn, mods_fn = _step_fns(m)
    s = cfg.max_slots
    out: Dict[int, np.ndarray] = {}
    for req in requests:
        _check_request(req, m.cfg, cfg)
        kk, vv = kv_fn(params, jnp.asarray(req.text)[None])
        kk, vv = jnp.repeat(kk, s, axis=1), jnp.repeat(vv, s, axis=1)
        sched = jnp.asarray(
            _timestep_schedule(req.n_steps, cfg.max_steps))
        mods = mods_fn(params, sched)
        mods_b = jnp.repeat(mods["blocks"][:, None], s, axis=1)  # (L,s,S,6d)
        mods_f = jnp.repeat(mods["final"][None], s, axis=0)      # (s,S,2d)
        lat = jnp.repeat(jnp.asarray(req.latents, jnp.float32)[None], s,
                         axis=0)
        dt = jnp.full((s,), 1.0 / req.n_steps, jnp.float32)
        active = jnp.ones((s,), bool)
        for i in range(req.n_steps):
            lat = step_fn(params, lat, kk, vv, mods_b, mods_f,
                          jnp.full((s,), i, jnp.int32), dt, active)
        out[req.uid] = np.asarray(lat[0])
    return out


def make_video_requests(n: int, model_cfg, *, n_latent: int,
                        steps=(4, 8), seed: int = 0
                        ) -> List[VideoRequest]:
    """Deterministic mixed workload: ``n`` requests with cycling step
    counts, iid normal noise latents and text embeddings."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        reqs.append(VideoRequest(
            uid=i,
            latents=rng.standard_normal(
                (n_latent, model_cfg.c_latent)).astype(np.float32),
            text=rng.standard_normal(
                (model_cfg.n_text, model_cfg.d_model)).astype(np.float32),
            n_steps=int(steps[i % len(steps)]),
        ))
    return reqs
