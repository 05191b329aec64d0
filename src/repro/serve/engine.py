"""Continuous-batching serving engine over a block-paged KV cache.

Requests join and leave mid-flight: every slot carries its own sequence
offset, so a request admitted at engine step 400 decodes next to one that is
3000 tokens deep.  KV lives in a pool of physical pages of ``block_k``
tokens allocated from a free list — ``max_len`` memory is shared across
slots instead of reserved per slot — and a host-side page table maps
(slot, logical block) -> physical page (page 0 is a reserved trash page for
masked writes).  Prefill is *chunked*: each engine step runs at most one
``prefill_chunk``-token chunk of one joining prompt plus one decode
dispatch for every ongoing slot.  A decode dispatch advances each slot by
one token, or by a whole draft window (1 to ``draft_len + 1`` tokens) when
speculative decoding is on — the canonical definition of the step
granularity lives in docs/serving.md#engine-step-granularity.  Long
prompts therefore interleave with decode instead of stalling it.  Chunk
attention is exact (dense over paged history + chunk); SLA2's
sparse/linear split applies at decode where per-step cost matters.

Admission is optimistic (vLLM-style): requests are admitted against the
pages *actually* outstanding, pages are allocated lazily as sequences grow,
and on pool exhaustion the ``Scheduler`` preempts the youngest slot
(preempt-last, FCFS priority): its pages are either swapped to the host
``SwapPool`` (page-granular numpy mirror, plus the SLA2 per-slot linear
totals so the linear branch resumes exactly) or, when swap space is also
full, dropped and recomputed from the prompt + tokens generated so far.
Either way a resumed request continues token-identically.  See
docs/serving.md for the full state machine.  On CPU this serves small
models end-to-end (examples/serve_lm.py); on TPU the same jitted step
functions shard per distributed/sharding.cache_specs (page-axis sharded
pools).

Paged serving covers every LM layer kind: attention layers page K/V, MLA
layers page their COMPRESSED LATENT (the c_kv+k_rope vector per token),
recurrent mixers (mamba/mlstm/slstm, incl. Hymba hybrid blocks) ride the
same swap/recompute plumbing with per-slot state checkpoints instead of
pages.  ``generate_sequential`` is the unbatched static-cache oracle the
engine's outputs are tested against.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Request:
    """One generation request: a prompt, a decode budget and (engine-
    filled) output/accounting fields that survive preemption."""
    uid: int
    prompt: np.ndarray                 # (n,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # filled by the engine
    output: Optional[list] = None
    arrival: int = -1                  # FCFS priority (kept across preemption)
    n_preempt: int = 0                 # times this request was preempted
    t_submit: Optional[float] = None   # wall clock at submit / completion —
    t_finish: Optional[float] = None   # the benchmark latency probes


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """ServeEngine's knobs; field comments below are the authoritative
    reference (docs/serving.md walks through the serving-relevant ones)."""
    max_slots: int = 4
    max_len: int = 512                 # per-slot logical capacity
    page_size: Optional[int] = None    # defaults to model block_k
    prefill_chunk: int = 64            # tokens prefetched per engine step
    num_pages: Optional[int] = None    # pool size; default reserves worst case
    temperature: float = 0.0           # 0 => greedy
    seed: int = 0
    # override the model's paged attention path: 'fused' (Pallas page-table
    # kernels) | 'gather' (jnp reference) | 'auto' (fused on compiled
    # backends, gather on CPU); None keeps the model config
    paged_impl: Optional[str] = None
    # override the fused decode kernel's QAT tile path ('none'|'int8'|'fp8')
    decode_quant_bits: Optional[str] = None
    # page-pool STORAGE dtype ('none' | 'int8' | 'fp8'): K/V (and SLA2
    # pooled-key) pages held as low-bit codes with per-row f32 scales —
    # pool bytes, swap traffic and decode-step HBM reads shrink ~2x at
    # int8/fp8, so the same HBM budget holds ~2x the pages/slots.  None
    # keeps the model config.
    kv_quant: Optional[str] = None
    # unquantized page-pool element dtype (e.g. 'float32'); None keeps the
    # model default (bfloat16).  'float32' makes paged prefill read back
    # EXACTLY the values the static oracle attends, so engine outputs are
    # token-identical to generate_sequential even for MoE stacks whose
    # expert gates amplify bf16 page rounding into argmax flips (the
    # cross-family identity tests rely on this; ignored under kv_quant)
    page_dtype: Optional[str] = None
    # host swap-pool capacity in pages; None mirrors the device pool size,
    # 0 disables swapping (preemption always recomputes from the prompt)
    swap_pages: Optional[int] = None
    # speculative decoding: 'off' = every decode dispatch advances each
    # slot by exactly one token; 'linear' = draft `draft_len` tokens per
    # slot through the SLA2 linear branch (no page reads; requires
    # mechanism='sla2'); 'ngram' = model-free prompt-lookup drafting over
    # each slot's token history (works on ANY paged stack, incl.
    # mechanism='full').  Either way the whole window is verified in ONE
    # multi-token paged pass — a dispatch advances a slot by
    # 1..draft_len+1 tokens (see docs/speculative.md).  Greedy outputs
    # stay token-identical to 'off' for both drafters.
    speculative: str = "off"
    draft_len: int = 3
    # longest suffix n-gram the 'ngram' drafter tries to match
    ngram_max: int = 3
    # copy-on-write prefix caching (serve/prefix_cache.py): admission maps
    # the longest cached full-page prefix of a prompt into the slot's page
    # table (refcount+1) and skips that much chunked prefill; finished
    # prompts leave their pages behind in an LRU trie that pool pressure
    # evicts before preempting live slots
    prefix_cache: bool = False
    # sharded serving: a jax.sharding.Mesh (e.g. launch/mesh.make_host_mesh)
    # makes load() place params (model-axis only) and the page pool /
    # per-slot linear totals (page axis over all mesh axes, slot axis over
    # DP) with the distributed/sharding NamedShardings, and routes the
    # fused paged entries through shard_map (distributed/shard_paged).
    # The page table and the scheduler stay global on the host.
    mesh: Optional[Any] = None
    # 'auto' shards whenever a mesh is given; 'off' ignores the mesh
    shard: str = "auto"
    # heartbeat fault handling (armed only when a mesh is set): one
    # simulated host per mesh device; a host missing `heartbeat_misses`
    # deadlines is declared dead by check_faults(), which reshards the
    # engine onto the survivors instead of killing it
    heartbeat_deadline_s: float = 60.0
    heartbeat_misses: int = 2


def _sample_tokens(logits: np.ndarray, temperature: float,
                   rng: np.random.Generator) -> np.ndarray:
    """Greedy (temperature <= 0) or Gumbel-max sampling over (B, V) logits."""
    if temperature <= 0:
        return np.argmax(logits, axis=-1).astype(np.int32)
    z = rng.gumbel(size=logits.shape)
    return np.argmax(logits / temperature + z, axis=-1).astype(np.int32)


def make_mixed_requests(vocab_size: int, work, seed: int = 0,
                        uid0: int = 0) -> list[Request]:
    """Requests from a (prompt_len, max_new_tokens) work list — the shared
    builder for the mixed-length demo/benchmark workloads."""
    rng = np.random.default_rng(seed)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(1, vocab_size, n).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(work)]


class PageAllocator:
    """Reference-counted free list over pages 1..num_pages-1 (0 = trash).

    ``alloc`` hands out a page at refcount 1; ``incref`` adds a sharer (a
    prefix-cache node or a second slot mapping the same page); ``free`` is
    a DECREF — the page returns to the free list only when the last
    reference drops.  Freeing an unreferenced page raises: before
    refcounts, a double-free put the same physical page on the free list
    twice and handed it to two slots (silent cross-slot KV corruption).
    ``min_available`` tracks the pool's high-water mark (the footprint
    probe the prefix-cache benchmark reads)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        self._ref = np.zeros(num_pages, np.int32)
        self.min_available = num_pages - 1

    @property
    def available(self) -> int:
        """Pages currently free."""
        return len(self._free)

    def refcount(self, page: int) -> int:
        """Current reference count of a physical page (0 = free)."""
        return int(self._ref[page])

    def alloc(self) -> int:
        """Pop one free physical page id at refcount 1; raises when the
        pool is dry."""
        if not self._free:
            raise RuntimeError("page pool exhausted")
        page = self._free.pop()
        self._ref[page] = 1
        self.min_available = min(self.min_available, len(self._free))
        return page

    def incref(self, page: int) -> None:
        """Add a reference to an already-allocated page (page sharing)."""
        assert 0 < page < self.num_pages and self._ref[page] > 0, \
            f"incref of unallocated page {page}"
        self._ref[page] += 1

    def free(self, pages) -> None:
        """Drop one reference per page; pages whose count reaches zero go
        back to the free list.  Rejects freeing an already-free page (the
        double-free that used to corrupt the pool silently)."""
        for p in pages:
            p = int(p)
            assert 0 < p < self.num_pages
            if self._ref[p] == 0:
                raise RuntimeError(f"double free of physical page {p}")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)


@dataclasses.dataclass
class _Slot:
    req: Request
    tokens: np.ndarray                 # prompt tokens to prefill
    pos: int = 0                       # tokens prefilled so far
    budget: int = 0                    # decode tokens still to produce
    last_token: int = 0
    decoding: bool = False
    n_pages: int = 0                   # physical pages currently mapped
    # recompute-resume: already-sampled tokens to teacher-force through the
    # decode path (sampling is suppressed until the list drains).  Replaying
    # generated tokens through DECODE — not through chunked prefill — makes
    # the rebuilt cache bit-identical to the one the preemption dropped,
    # since it repeats the exact original computation.
    replay: Optional[list] = None
    # prefix-cache bookkeeping: the slot's first n_shared logical pages are
    # mapped from the trie (never written without copy-on-write, never
    # swapped); cache_node is the trie node at the hit depth; snaps
    # collects per-chunk-boundary linear-totals snapshots during prefill
    # for insertion once the prompt completes
    n_shared: int = 0
    cache_node: Any = None
    snaps: Optional[dict] = None
    # the trie node the slot pinned at hit time: held for the slot's whole
    # lifetime (across preemption) so eviction can never detach a node
    # whose page the slot maps — a detached node's page would be decreffed
    # to zero at preemption and reallocated before resume
    pinned_node: Any = None


@dataclasses.dataclass
class _ResumeState:
    """Where a preempted request left off (side table in the Scheduler).
    The evicted ``_Slot`` rides along verbatim — already reset for replay
    in recompute mode, untouched in swap mode — so resume reuses it
    instead of copying fields in and out."""
    mode: str                          # 'swap' | 'recompute'
    slot: _Slot
    length: int = 0                    # swap-only: tokens in the saved pages
    # swap-only, prefix-cache: the shared prefix is NOT swapped — its pages
    # stay alive under the trie node the slot keeps pinned — and is
    # re-increffed on resume; only the private suffix rides in the SwapPool
    n_shared: int = 0
    shared_phys: Optional[np.ndarray] = None


# The jitted swap-out graph extracts pages with a static (max_pages,)-padded
# page row, so the raw state carries trash-page copies for the padding rows.
# Host-side, those rows are trimmed before the state enters the SwapPool (so
# capacity accounting matches the memory actually held) and re-padded with
# zeros on swap-in (the padded rows only ever write the trash page).  Page
# axes are located name-by-position-from-the-end, matching the leaf layout
# of models/attention.extract_paged_state regardless of leading (e.g. group)
# axes: k/v pages are (..., P, Hkv, bk, Dh), pooled keys (..., P, Hkv, Dh),
# MLA's pooled latents (..., P, d); quantized pools add per-row scales
# (..., P, Hkv, bk) / (..., P, Hkv) / (..., P) that swap with their pages
# (codes + scales together keep the round trip bit-exact within the
# quantized representation).
_PAGE_AXIS_FROM_END = {"k_pages": 4, "v_pages": 4, "pooled_pages": 3,
                       "pooled_lat": 2, "k_scale": 3, "v_scale": 3,
                       "pooled_scale": 2, "pooled_lat_scale": 1}


def _map_page_leaves(state, fn):
    if isinstance(state, dict):
        return {k: fn(k, v) if k in _PAGE_AXIS_FROM_END
                else _map_page_leaves(v, fn) for k, v in state.items()}
    if isinstance(state, list):
        return [_map_page_leaves(v, fn) for v in state]
    return state


def _trim_swap_state(state, n_pages: int):
    def trim(name, arr):
        axis = arr.ndim - _PAGE_AXIS_FROM_END[name]
        return arr[(slice(None),) * axis + (slice(0, n_pages),)]
    return _map_page_leaves(state, trim)


def _pad_swap_state(state, max_pages: int):
    def pad(name, arr):
        axis = arr.ndim - _PAGE_AXIS_FROM_END[name]
        n = max_pages - arr.shape[axis]
        if n == 0:
            return arr
        shape = arr.shape[:axis] + (n,) + arr.shape[axis + 1:]
        return np.concatenate([arr, np.zeros(shape, arr.dtype)], axis=axis)
    return _map_page_leaves(state, pad)


class SwapPool:
    """Host-memory swap space for preempted slots, page-granular but
    capacity-accounted in BYTES.

    Holds numpy mirrors of a slot's device state — its K/V pages (+ SLA2
    per-page pooled router keys, + per-row scales when the pool is
    quantized) for every layer, plus the per-slot linear totals (h_tot,
    z_tot).  The capacity budget is ``capacity_pages`` REFERENCE
    (unquantized bf16) pages worth of host memory; ``configure_bytes``
    (called from ``ServeEngine.load`` with the actual cache layout) fixes
    both the actual and the reference per-page byte size, so a quantized
    pool's smaller pages pack proportionally more preempted slots into the
    same budget.  Unconfigured, both sizes default to 1 and the accounting
    degrades to the legacy page semantics.  ``can_hold`` gates the
    scheduler's swap-vs-recompute decision; a request whose pages don't
    fit falls back to recompute-from-prompt."""

    def __init__(self, capacity_pages: int):
        self.capacity_pages = max(0, int(capacity_pages))
        self.page_bytes = 1          # actual bytes of one swapped page
        self.capacity_bytes = self.capacity_pages
        self.used_bytes = 0
        self._store: dict[int, tuple[int, Any]] = {}   # arrival -> (n, state)

    def configure_bytes(self, page_bytes: int, ref_page_bytes: int) -> None:
        """Set the actual per-page byte size of swapped states and the
        reference per-page size the page budget was provisioned against
        (``capacity_bytes = capacity_pages * ref_page_bytes``)."""
        assert not self._store and self.used_bytes == 0
        self.page_bytes = max(1, int(page_bytes))
        self.capacity_bytes = self.capacity_pages * max(1,
                                                        int(ref_page_bytes))

    @property
    def capacity(self) -> int:
        """Capacity in ACTUAL pages (the byte budget / actual page size)."""
        return self.capacity_bytes // self.page_bytes

    @property
    def used(self) -> int:
        """Pages currently held (the byte usage / actual page size)."""
        return self.used_bytes // self.page_bytes

    @property
    def n_swapped(self) -> int:
        """Requests currently held in swap."""
        return len(self._store)

    def can_hold(self, n_pages: int) -> bool:
        """True when n_pages more pages' bytes fit in the capacity."""
        return self.used_bytes + n_pages * self.page_bytes \
            <= self.capacity_bytes

    def put(self, key: int, n_pages: int, state) -> None:
        """Store one slot's extracted state under the request's arrival
        id, charging n_pages * page_bytes against capacity."""
        assert key not in self._store and self.can_hold(n_pages)
        self._store[key] = (n_pages, state)
        self.used_bytes += n_pages * self.page_bytes

    def pop(self, key: int):
        """Remove and return a stored state, releasing its bytes."""
        n_pages, state = self._store.pop(key)
        self.used_bytes -= n_pages * self.page_bytes
        return state


def _pool_page_bytes(caches, reference: bool = False) -> int:
    """Bytes one physical page occupies across the whole cache pytree
    (every leaf keyed in ``_PAGE_AXIS_FROM_END`` contributes
    ``size / P * itemsize``; leading group axes fold the layer count in
    naturally).  With ``reference=True`` the page is sized as an
    UNQUANTIZED 2-byte pool would hold it — scale rows are dropped and
    1-byte code arrays count 2 bytes per element — giving the
    provisioning baseline for ``SwapPool.configure_bytes``."""
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, dict):
            for name, val in node.items():
                if name in _PAGE_AXIS_FROM_END and hasattr(val, "shape"):
                    axis = val.ndim - _PAGE_AXIS_FROM_END[name]
                    item = val.dtype.itemsize
                    if reference:
                        if name.endswith("_scale"):
                            continue
                        item = max(item, 2)
                    total += val.size // val.shape[axis] * item
                else:
                    walk(val)
        elif isinstance(node, (list, tuple)):
            for val in node:
                walk(val)

    walk(caches)
    return total


class Scheduler:
    """FCFS wait queue + preempt-last priority bookkeeping.

    Requests keep their original arrival order across preemption: a
    preempted request re-enters the queue sorted by arrival, so it resumes
    before anything that arrived after it (preempt-last / resume-first).
    Resume state rides in a side table keyed by arrival id (engine-unique,
    unlike user-chosen uids)."""

    def __init__(self):
        self.waiting: list[Request] = []
        self._resume: dict[int, _ResumeState] = {}
        self._arrivals = 0

    def enqueue(self, req: Request) -> None:
        """Admit a NEW request to the wait queue, stamping its arrival."""
        req.arrival = self._arrivals
        self._arrivals += 1
        self.waiting.append(req)

    def requeue(self, req: Request, resume: _ResumeState) -> None:
        """Re-queue a preempted request at its original arrival priority,
        parking its resume state in the side table."""
        self._resume[req.arrival] = resume
        i = 0
        while i < len(self.waiting) and self.waiting[i].arrival < req.arrival:
            i += 1
        self.waiting.insert(i, req)

    def head(self) -> Optional[Request]:
        """The next request to admit (FCFS), or None."""
        return self.waiting[0] if self.waiting else None

    def pop_head(self) -> Request:
        """Remove and return the queue head."""
        return self.waiting.pop(0)

    def peek_resume(self, req: Request) -> Optional[_ResumeState]:
        """Look at a request's parked resume state without claiming it."""
        return self._resume.get(req.arrival)

    def take_resume(self, req: Request) -> Optional[_ResumeState]:
        """Claim (remove and return) a request's parked resume state."""
        return self._resume.pop(req.arrival, None)

    def victim(self, slots: dict[int, _Slot]) -> int:
        """Preempt-last: the active slot with the newest arrival."""
        return max(slots, key=lambda s: slots[s].req.arrival)


def pool_donation() -> tuple:
    """The arguments the prefill and decode programs donate.  On an
    accelerator they donate the pool (argument 2) and update it in place,
    so device memory holds one pool.  On the CPU they donate nothing, so a
    step's input pool stays readable: a wrapped step that hands back its
    input pool (the benchmark's planted stale-decode fault) keeps serving.
    ``tests/test_preemption.py`` runs the donated path on the CPU too."""
    return () if jax.default_backend() == "cpu" else (2,)


class ServeEngine:
    """Mixed-length continuous batching over Model.prefill_chunk/decode_paged.

    Host-side bookkeeping (slot table, page table, free list, scheduler,
    swap pool) stays in numpy; the jitted device functions have static
    shapes — (1, prefill_chunk) for chunk prefill, (max_slots,) for the
    batched decode step, and (max_pages,)-padded page rows for swap-out/in
    — so the engine compiles a fixed handful of graphs regardless of
    workload mix or preemption pattern.
    """

    def __init__(self, model, ecfg: EngineConfig):
        if model.decode_paged is None:
            raise ValueError(
                f"{model.kind}/{getattr(model.cfg, 'layer_kinds', ())} has no "
                "paged serving path (LM stacks of dense/moe/mla_*/hybrid/"
                "mlstm/slstm layers all do)")
        if ecfg.shard not in ("auto", "off"):
            raise ValueError(f"unknown shard mode {ecfg.shard!r}")
        mesh = ecfg.mesh if ecfg.shard == "auto" else None
        overrides = {
            k: v for k, v in (("paged_impl", ecfg.paged_impl),
                              ("decode_quant_bits", ecfg.decode_quant_bits),
                              ("kv_quant", ecfg.kv_quant),
                              ("mesh", mesh))
            if v is not None and v != getattr(model.cfg, k, None)}
        if overrides:
            # rebuild so the jitted step fns close over the requested paged
            # attention path (fused Pallas kernels vs gather reference) —
            # memoized on the original model so engines constructed with
            # the same overrides share one rebuilt model and therefore one
            # set of jitted step/swap fns (a fresh rebuild per engine would
            # silently recompile everything each time)
            if not hasattr(model, "_override_models"):
                model._override_models = {}
            key = tuple(sorted(overrides.items()))
            if key not in model._override_models:
                model._override_models[key] = model.with_overrides(
                    **overrides)
            model = model._override_models[key]
        self.model = model
        bk = getattr(model.cfg, "block_k", 64)
        page = ecfg.page_size or bk
        if page != bk:
            # the attention-layer page pool is hard-wired to block_k tokens
            # per page; any other granularity would silently misindex
            raise ValueError(f"page_size must equal block_k ({bk})")
        self.page_size = page
        chunk = max(page, (ecfg.prefill_chunk // page) * page)
        self.chunk = chunk
        self.max_len = -(-ecfg.max_len // page) * page
        self.max_pages = self.max_len // page
        num_pages = ecfg.num_pages or ecfg.max_slots * self.max_pages + 1
        self.cfg = ecfg
        self.params = None
        self.caches = None
        self.allocator = PageAllocator(num_pages)
        self.scheduler = Scheduler()
        swap_cap = (num_pages - 1 if ecfg.swap_pages is None
                    else ecfg.swap_pages)
        self.swap = SwapPool(swap_cap)
        self.stats = {"preemptions": 0, "swap_outs": 0, "swap_ins": 0,
                      "recomputes": 0, "spec_steps": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "engine_steps": 0,
                      "prefill_tokens": 0, "prefix_hits": 0,
                      "prefix_misses": 0, "prefix_hit_tokens": 0,
                      "prefix_inserts": 0, "prefix_evictions": 0,
                      "cow_copies": 0,
                      # sharded-serving fault telemetry
                      "host_failures": 0, "reshards": 0,
                      # pool-pressure / swap telemetry, refreshed each step
                      "swap_bytes": 0, "min_available": num_pages - 1,
                      "pool_peak_pages": 0,
                      # bytes of every logits array the host pulled
                      "logits_to_host_bytes": 0,
                      # expert stacks: (row, expert) pairs the held
                      # experts computed, and held experts with a row,
                      # summed over expert layers and programs
                      "moe_held_rows": 0, "moe_active_experts": 0}
        # counters of programs whose logits stayed on the device; they
        # come to the host with the next logits the engine pulls
        self._pending_counters: list = []
        self.mesh = mesh
        self.monitor = None
        if mesh is not None:
            from repro.distributed.fault_tolerance import HeartbeatMonitor
            self.monitor = HeartbeatMonitor(
                deadline_s=ecfg.heartbeat_deadline_s,
                misses_allowed=ecfg.heartbeat_misses)
            # every mesh device is one simulated host, alive at t=0
            for h in range(len(list(mesh.devices.flat))):
                self.monitor.beat(h, now=0.0)
        # True when any layer keeps per-slot state (SLA2 linear totals,
        # MLA totals, recurrent checkpoints) the prefix cache must
        # snapshot at chunk boundaries and restore on hits
        self._slot_state = bool(getattr(model, "has_slot_state", False))
        self._pcache = None
        if ecfg.prefix_cache:
            from repro.serve.prefix_cache import PrefixCache
            self._pcache = PrefixCache(self.page_size,
                                       self.chunk // self.page_size,
                                       need_totals=self._slot_state)
        self._slots: dict[int, _Slot] = {}          # slot -> state
        self._prefill_order: list[int] = []         # FCFS chunked prefill
        self._page_table = np.zeros((ecfg.max_slots, self.max_pages),
                                    np.int32)
        self._lengths = np.zeros((ecfg.max_slots,), np.int32)
        self._rng = np.random.default_rng(ecfg.seed)
        self.completed: list[Request] = []
        if ecfg.speculative not in ("off", "linear", "ngram"):
            raise ValueError(f"unknown speculative mode {ecfg.speculative!r}")
        self._spec = ecfg.speculative != "off"
        if self._spec:
            from repro.serve.speculative import NGramDrafter
            if ecfg.draft_len < 1:
                raise ValueError("draft_len must be >= 1")
            if ecfg.speculative == "linear":
                if model.draft_init is None:
                    raise ValueError(
                        "speculative='linear' requires an SLA2 attention "
                        f"stack (got mechanism={model.cfg.mechanism!r})")
            else:
                # model-free drafting: any stack with a paged verify path
                self._drafter = NGramDrafter(model.cfg.vocab_size,
                                             max_ngram=ecfg.ngram_max,
                                             temperature=ecfg.temperature)
        self._bind_model_fns(model)

    def _bind_model_fns(self, model) -> None:
        """(Re)bind the jitted step / swap / verify / prefix fns (and the
        model-bound linear drafter) to ``model``.  Cached on the model
        object so engine restarts — and tests spinning up many engines —
        share compilations; jit retraces per (chunk, max_slots, pool)
        shape as needed.  The fault path calls this again after rebuilding
        the model on the surviving mesh."""
        self.model = model
        mesh = getattr(model.cfg, "mesh", None)

        def pin(caches):
            # keep the pool placed across steps: without the constraint
            # GSPMD is free to hand the updated caches back replicated
            # (it sometimes does on the shard_map path), silently undoing
            # the load()-time placement after the first step
            if mesh is None:
                return caches
            from repro.distributed import sharding as shardlib
            return jax.lax.with_sharding_constraint(
                caches, shardlib.logical_to_shardings(
                    shardlib.cache_specs(caches, mesh), mesh))

        # every program has a function name of its own, so a profiler
        # trace shows each as its own module (jit_serve_decode, ...)
        if not hasattr(model, "_paged_step_fns"):
            def serve_prefill_chunk(p, b, c):
                o, cc = model.prefill_chunk(p, b, c)
                return o, pin(cc)

            def serve_decode(p, b, c):
                o, cc = model.decode_paged(p, b, c)
                return o, pin(cc)

            donate = pool_donation()
            model._paged_step_fns = (
                jax.jit(serve_prefill_chunk, donate_argnums=donate),
                jax.jit(serve_decode, donate_argnums=donate))
        self._prefill_fn, self._decode_fn = model._paged_step_fns
        if model.swap_out is not None:
            if not hasattr(model, "_swap_fns"):
                def serve_swap_out(c, row, slot):
                    return model.swap_out(c, row, slot)

                def serve_swap_in(c, row, slot, st):
                    return pin(model.swap_in(c, row, slot, st))

                model._swap_fns = (jax.jit(serve_swap_out),
                                   jax.jit(serve_swap_in))
            self._swap_out_fn, self._swap_in_fn = model._swap_fns
        else:
            self._swap_out_fn = self._swap_in_fn = None
        if self._spec:
            if not hasattr(model, "_spec_step_fns"):
                def serve_verify(p, b, c):
                    o, cc = model.decode_verify(p, b, c)
                    return o, pin(cc)

                def serve_commit(c, pt, ln, acc, act, w):
                    return pin(model.commit_window(c, pt, ln, acc, act, w))

                model._spec_step_fns = (
                    jax.jit(serve_verify),
                    jax.jit(serve_commit, static_argnums=(5,)))
            self._verify_fn, self._commit_fn = model._spec_step_fns
            if self.cfg.speculative == "linear":
                from repro.serve.speculative import LinearDrafter
                self._drafter = LinearDrafter(model, self.cfg.temperature)
        if self._pcache is not None:
            if not hasattr(model, "_prefix_fns"):
                def serve_extract_totals(c, slot):
                    return model.extract_totals(c, slot)

                def serve_insert_totals(c, slot, st):
                    return pin(model.insert_totals(c, slot, st))

                def serve_copy_page(c, src, dst):
                    return pin(model.copy_page(c, src, dst))

                model._prefix_fns = (jax.jit(serve_extract_totals),
                                     jax.jit(serve_insert_totals),
                                     jax.jit(serve_copy_page))
            (self._extract_totals_fn, self._insert_totals_fn,
             self._copy_page_fn) = model._prefix_fns

    # ------------------------------------------------------------------
    @property
    def _queue(self) -> list[Request]:
        """The scheduler's wait queue (read-only view — external callers
        poll its truthiness to know whether work remains)."""
        return self.scheduler.waiting

    def _pool_dtype_kw(self) -> dict:
        """Extra init_paged_caches kwargs for cfg.page_dtype (exact-identity
        pools); empty when unset so models without a dtype knob still work."""
        if self.cfg.page_dtype is None:
            return {}
        return {"dtype": jnp.dtype(self.cfg.page_dtype)}

    def load(self, params):
        """Install model params and allocate the paged cache pools.  With
        a mesh, both leave the host already placed: params model-axis only
        (serving_param_shardings), pool + per-slot totals per cache_specs
        (page axis over all mesh axes, slot axis over DP)."""
        self.params = params
        # recurrent-mixer caches carry a verify-window state buffer sized
        # by the speculative draft window (1 when decode is single-token)
        window = self.cfg.draft_len + 1 if self._spec else 1
        self.caches = self.model.init_paged_caches(
            self.cfg.max_slots, self.allocator.num_pages, window=window,
            **self._pool_dtype_kw())
        if self.mesh is not None:
            self.params, self.caches = self._place_on_mesh(params,
                                                           self.caches)
        # Byte-accurate swap accounting: the swap budget is swap_cap
        # REFERENCE (2-byte) pages, so a quantized pool's smaller pages
        # pack ~2x more preempted slots into the same host memory.
        self.swap.configure_bytes(_pool_page_bytes(self.caches),
                                  _pool_page_bytes(self.caches,
                                                   reference=True))

    def _place_on_mesh(self, params, caches):
        """device_put params and caches onto ``self.mesh`` with the
        distributed/sharding placements (see load())."""
        from repro.distributed import sharding as shardlib
        params = jax.device_put(
            params, shardlib.serving_param_shardings(params, self.mesh))
        caches = jax.device_put(
            caches, shardlib.logical_to_shardings(
                shardlib.cache_specs(caches, self.mesh), self.mesh))
        return params, caches

    # ------------------------------------------------------------------
    # fault handling (sharded serving): one simulated host per mesh device
    # ------------------------------------------------------------------
    def heartbeat(self, host: int, now: Optional[float] = None) -> None:
        """Record a liveness beat from simulated host ``host``.  No-op
        without a mesh (single-host engines have nothing to monitor)."""
        if self.monitor is not None:
            self.monitor.beat(host, now)

    def check_faults(self, now: Optional[float] = None) -> list[int]:
        """Poll the HeartbeatMonitor; hosts past their miss budget are
        declared dead and the engine reshards onto the survivors
        (``_reshard_after_failure``) instead of dying.  Returns the dead
        host ids (hosts are renumbered 0..n-1 on the shrunk mesh
        afterwards).  Callers drive the clock via ``now`` the same way
        they drive ``heartbeat``."""
        if self.monitor is None:
            return []
        n = len(list(self.mesh.devices.flat))
        dead = sorted(h for h in self.monitor.check(now) if 0 <= h < n)
        if dead:
            self._reshard_after_failure(dead, now=now)
        return dead

    def _reshard_after_failure(self, dead: list[int],
                               now: Optional[float] = None) -> None:
        """Shrink the engine onto the surviving mesh devices.

        The dead host's pool shard is gone and the pool is re-initialised
        on the survivors, so EVERY occupied slot is preempted first —
        through the normal PR-3 machinery: slots whose pages all live on
        surviving shards swap out (the extracted state is read from
        surviving-shard data, bit-exact), slots touching a dead-shard page
        — or leaning on prefix-cache pages, which die with the pool — are
        forced onto the teacher-forced recompute path.  Then ElasticPlan
        shrinks the mesh (DP absorbs the loss, MP stays fixed), the model
        is rebuilt with the surviving mesh so the shard_map wrappers
        re-close over it, the jitted fns rebind, and a fresh pool is
        placed.  Greedy outputs are unchanged vs a never-failed run
        (tests/test_mesh_serving.py asserts token identity)."""
        from jax.sharding import Mesh
        from repro.distributed import fault_tolerance as ftlib
        from repro.distributed import sharding as shardlib
        devs = list(self.mesh.devices.flat)
        dead_set = set(dead)
        num_pages = self.allocator.num_pages
        n_shards = shardlib.pool_shard_count(num_pages, self.mesh)
        # pages whose shard sat on a dead host (empty when the pool fell
        # back to replication: every survivor still holds every page)
        lost = ({p for p in range(1, num_pages)
                 if shardlib.page_to_shard(p, num_pages, n_shards)
                 in dead_set} if n_shards > 1 else set())
        # parked swap states that lean on shared trie pages lose them with
        # the pool: demote them to recompute before rebuilding anything
        self._demote_trie_swaps()
        # preempt every occupied slot, oldest first (oldest carry the most
        # computed state, so they get first claim on the swap pool)
        for slot in sorted(self._slots,
                           key=lambda sl: self._slots[sl].req.arrival):
            s = self._slots[slot]
            row = self._page_table[slot]
            touched = any(int(p) in lost for p in row[row > 0])
            tied_to_trie = (self._pcache is not None
                            and (s.n_shared > 0 or s.pinned_node is not None
                                 or s.cache_node is not None))
            self._preempt(slot, force_recompute=touched or tied_to_trie)
        if self._pcache is not None:
            # the trie's pages die with the pool: start a fresh cache
            from repro.serve.prefix_cache import PrefixCache
            self._pcache = PrefixCache(self.page_size,
                                       self.chunk // self.page_size,
                                       need_totals=self._slot_state)
        survivors = [d for i, d in enumerate(devs) if i not in dead_set]
        assert len(self.mesh.axis_names) == 2, \
            "engine fault resharding expects a (data, model) host mesh"
        mp = int(self.mesh.shape.get("model", 1))
        plan = ftlib.ElasticPlan(old_devices=len(devs),
                                 new_devices=len(survivors))
        assert plan.reshardable
        shape = plan.new_mesh_shape(model_parallel=mp)
        self.mesh = Mesh(np.asarray(survivors).reshape(shape),
                         self.mesh.axis_names)
        self.monitor = ftlib.HeartbeatMonitor(
            deadline_s=self.cfg.heartbeat_deadline_s,
            misses_allowed=self.cfg.heartbeat_misses)
        for h in range(len(survivors)):
            self.monitor.beat(h, now=now)
        self._bind_model_fns(self.model.with_overrides(mesh=self.mesh))
        # fresh pool on the shrunk mesh; page bytes are unchanged so the
        # SwapPool keeps its byte budget (and its swapped-out states)
        self.allocator = PageAllocator(num_pages)
        window = self.cfg.draft_len + 1 if self._spec else 1
        self.caches = self.model.init_paged_caches(
            self.cfg.max_slots, num_pages, window=window,
            **self._pool_dtype_kw())
        if self.params is not None:
            self.params, self.caches = self._place_on_mesh(self.params,
                                                           self.caches)
        self._page_table[:] = 0
        self._lengths[:] = 0
        self.stats["host_failures"] += len(dead)
        self.stats["reshards"] += 1

    def submit(self, req: Request):
        """Validate and enqueue a request (it joins a slot at admission)."""
        n = len(req.prompt)
        if n == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        if n + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: {n}+{req.max_new_tokens} tokens exceed "
                f"max_len {self.max_len}")
        # the request's true, unclamped page demand against the pool
        if -(-(n + req.max_new_tokens) // self.page_size) \
                > self.allocator.num_pages - 1:
            raise ValueError(
                f"request {req.uid}: needs more pages than the pool holds")
        req.output = []
        req.t_submit = time.perf_counter()
        self.scheduler.enqueue(req)

    # ------------------------------------------------------------------
    def _pages_needed_now(self, req: Request,
                          resume: Optional[_ResumeState]) -> int:
        """Pages a request needs to make progress right after admission —
        the admission gate."""
        if resume is not None and resume.mode == "swap":
            s = resume.slot
            # the shared prefix is re-mapped by incref, not allocation —
            # only pages beyond it must come off the free list
            n_sh = resume.n_shared
            if s.decoding:
                if self._spec:
                    # a verify step consumes pages for its whole draft
                    # window up front, so admit only when the resumed
                    # window can be mapped (the saved pages may already
                    # cover part of it)
                    wlen = self._window_len(s)
                    blocks = (resume.length + wlen - 1) // self.page_size + 1
                    return max(s.n_pages, blocks) - n_sh
                boundary = resume.length % self.page_size == 0
                return s.n_pages + (1 if boundary else 0) - n_sh
            # mid-prefill: the saved pages may already cover part of the
            # next chunk (self-preemption mid-mapping), so take the max of
            # saved pages and total pages the resumed chunk reaches —
            # summing the two would double-count and could demand more
            # pages than the pool holds (permanent admission deadlock)
            nxt = min(self.chunk, len(s.tokens) - s.pos)
            return max(s.n_pages,
                       -(-(s.pos + nxt) // self.page_size)) - n_sh
        tokens = req.prompt if resume is None else resume.slot.tokens
        return -(-min(self.chunk, len(tokens)) // self.page_size)

    def _alloc_page(self, slot: int) -> Optional[int]:
        """One page off the free list, making room first by evicting LRU
        cached prefixes and then by preempting the youngest slot.  Returns
        None if ``slot`` itself was the youngest and got preempted (the
        caller must drop it)."""
        while self.allocator.available == 0:
            if self._pcache is not None \
                    and self._pcache.evict_one(self.allocator):
                # the evicted node's page only hits the free list once no
                # slot maps it; keep evicting / fall through to preemption
                self.stats["prefix_evictions"] += 1
                continue
            victim = self.scheduler.victim(self._slots)
            self._preempt(victim)
            if victim == slot:
                return None
        return self.allocator.alloc()

    def _ensure_page(self, slot: int, logical: int) -> bool:
        """Map (slot, logical) -> a physical page, preempting the youngest
        slot while the pool is exhausted.  Returns False if ``slot`` itself
        was the youngest and got preempted (caller must drop it)."""
        if self._page_table[slot, logical] != 0:
            return True
        page = self._alloc_page(slot)
        if page is None:
            return False
        self._page_table[slot, logical] = page
        self._slots[slot].n_pages += 1
        return True

    def _cow_page(self, slot: int, logical: int) -> bool:
        """Copy-on-write: give ``slot`` a private copy of a mapped shared
        page before a write lands on it.  If the slot is the page's sole
        owner (the cache entry was evicted meanwhile) the page is already
        private and nothing is copied.  Returns False if ``slot`` got
        preempted while allocating the private page."""
        old = int(self._page_table[slot, logical])
        if self.allocator.refcount(old) == 1:
            return True
        new = self._alloc_page(slot)
        if new is None:
            return False
        self.caches = self._copy_page_fn(
            self.caches, jnp.asarray(old, jnp.int32),
            jnp.asarray(new, jnp.int32))
        self._page_table[slot, logical] = new
        self.allocator.free([old])          # drop the shared reference
        self.stats["cow_copies"] += 1
        return True

    def _preempt(self, slot: int, *, force_recompute: bool = False) -> None:
        """Evict a slot: swap its pages + linear totals to the host pool if
        they fit, else drop them and schedule recompute-from-prompt.  The
        request re-enters the wait queue at its original priority.
        ``force_recompute`` skips the swap path even when it would fit —
        the fault reshard uses it for slots whose device state is (partly)
        on a dead host and therefore must not be trusted."""
        s = self._slots.pop(slot)
        if slot in self._prefill_order:
            self._prefill_order.remove(slot)
        row = self._page_table[slot].copy()
        self.stats["preemptions"] += 1
        s.req.n_preempt += 1
        n_sh = s.n_shared
        n_priv = s.n_pages - n_sh
        if (not force_recompute and self._swap_out_fn is not None
                and s.n_pages > 0 and self.swap.can_hold(n_priv)):
            # shared pages are never swapped out: they stay alive under
            # the (pinned) trie node and are re-mapped by incref on
            # resume.  Only the private suffix — plus the per-slot linear
            # totals riding in the extracted state — enters the SwapPool.
            ext_row = np.zeros_like(row)
            ext_row[:n_priv] = row[n_sh:s.n_pages]
            state = jax.device_get(self._swap_out_fn(
                self.caches, jnp.asarray(ext_row),
                jnp.asarray(slot, jnp.int32)))
            self.swap.put(s.req.arrival, n_priv,
                          _trim_swap_state(state, n_priv))
            self.stats["swap_outs"] += 1
            # s.pinned_node stays held: the shared pages survive on-device
            # under the trie's references until resume re-increfs them
            resume = _ResumeState(mode="swap", slot=s,
                                  length=int(self._lengths[slot]),
                                  n_shared=n_sh,
                                  shared_phys=row[:n_sh].copy())
        else:
            if s.n_pages > 0:
                # a zero-page victim is a pure de-admission — nothing was
                # computed yet, so nothing is recomputed
                self.stats["recomputes"] += 1
            if s.decoding:
                # drop everything: re-prefill the prompt (same chunking as
                # the original pass), then teacher-force every generated
                # token back through the decode path — bit-identical to the
                # dropped cache because it repeats the original computation
                s.replay = list(s.req.output)
                s.decoding = False
            s.pos = 0
            s.n_pages = 0
            # shared refs are dropped too (the cache's own reference keeps
            # the pages alive); the restarted prefill re-looks-up the trie
            s.n_shared = 0
            s.cache_node = None
            s.snaps = None
            if s.pinned_node is not None:
                self._pcache.unpin(s.pinned_node)
                s.pinned_node = None
            resume = _ResumeState(mode="recompute", slot=s)
        self.allocator.free(row[row > 0])
        self._page_table[slot] = 0
        self._lengths[slot] = 0
        self.scheduler.requeue(s.req, resume)

    def _swap_in(self, slot: int, req: Request,
                 resume: _ResumeState) -> None:
        """Restore a swapped-out request into ``slot``: allocate fresh pages
        for its logical blocks, copy the saved pages + linear totals back,
        and continue exactly where it stopped (decode or chunked prefill)."""
        s = resume.slot
        state = _pad_swap_state(self.swap.pop(req.arrival), self.max_pages)
        n_sh = resume.n_shared
        row = np.zeros((self.max_pages,), np.int32)
        for lg in range(n_sh):
            # the shared prefix never left the device pool: re-map the
            # same physical pages (kept alive by the pinned trie node)
            p = int(resume.shared_phys[lg])
            self.allocator.incref(p)
            row[lg] = p
        ins_row = np.zeros((self.max_pages,), np.int32)
        for i in range(s.n_pages - n_sh):
            row[n_sh + i] = self.allocator.alloc()
            ins_row[i] = row[n_sh + i]
        self.caches = self._swap_in_fn(
            self.caches, jnp.asarray(ins_row), jnp.asarray(slot, jnp.int32),
            state)
        self.stats["swap_ins"] += 1
        self._page_table[slot] = row
        self._lengths[slot] = resume.length
        self._slots[slot] = s
        if not s.decoding:
            self._prefill_order.append(slot)

    def _start_slot(self, slot: int, req: Request,
                    resume: Optional[_ResumeState]) -> None:
        """Fresh prefill (or recompute replay) into an empty slot."""
        s = (_Slot(req=req, tokens=np.asarray(req.prompt, np.int32))
             if resume is None else resume.slot)
        self._slots[slot] = s
        self._lengths[slot] = 0
        self._prefill_order.append(slot)
        if self._pcache is not None:
            self._try_prefix_hit(slot, s)

    def _try_prefix_hit(self, slot: int, s: _Slot) -> None:
        """Map the longest cached prefix of ``s``'s prompt into the slot
        (refcount+1 per page, no allocation), restore the linear-totals
        snapshot, and fast-forward prefill past the shared pages.  A hit
        covering the WHOLE (page-aligned) prompt still re-runs the final
        chunk — the last token's logits must be produced — over the shared
        pages, which the prefill write guard copy-on-writes first."""
        pages, node = self._pcache.lookup(s.tokens)
        if not pages:
            self.stats["prefix_misses"] += 1
            return
        n_hit = len(pages)
        pos = n_hit * self.page_size
        if pos == len(s.tokens):
            pos -= self.chunk
            if pos <= 0:        # nothing left to skip: treat as a miss
                self.stats["prefix_misses"] += 1
                return
        row = self._page_table[slot]
        for lg, p in enumerate(pages):
            self.allocator.incref(p)
            row[lg] = p
        s.n_pages = n_hit
        s.n_shared = n_hit
        s.cache_node = node
        self._pcache.pin(node)          # held until _finish / recompute
        s.pinned_node = node
        s.pos = pos
        self._lengths[slot] = pos
        if self._slot_state:
            totals = self._pcache.totals_at(node, pos // self.page_size)
            self.caches = self._insert_totals_fn(
                self.caches, jnp.asarray(slot, jnp.int32), totals)
        self.stats["prefix_hits"] += 1
        self.stats["prefix_hit_tokens"] += pos

    def _insert_prefix(self, slot: int, s: _Slot) -> None:
        """Register a completed prompt's chunk-aligned full pages in the
        trie (the cache increfs newly indexed pages; the slot keeps its
        own references until ``_finish`` decrefs them into the LRU)."""
        ppc = self.chunk // self.page_size
        n_ins = (len(s.tokens) // self.chunk) * ppc
        if n_ins == 0:
            return
        created, node = self._pcache.insert(
            s.tokens, self._page_table[slot], n_ins, s.snaps or {},
            self.allocator)
        self.stats["prefix_inserts"] += created
        if node is not None:
            s.cache_node = node
        s.snaps = None

    def _available_pages(self) -> int:
        """Pages admission can count on: the free list plus cached-prefix
        pages an eviction sweep could still reclaim (without the second
        term, a pool full of cold cached prefixes would refuse all new
        work forever — the actual evictions happen lazily in
        ``_alloc_page`` as pages are demanded)."""
        n = self.allocator.available
        if self._pcache is not None:
            n += self._pcache.evictable_pages(self.allocator)
        return n

    def _demote_trie_swaps(self) -> int:
        """Turn every parked swap state that maps shared trie pages into a
        recompute, releasing its pin on the trie; returns how many."""
        n = 0
        for arr, res in list(self.scheduler._resume.items()):
            if res.mode == "swap" and res.n_shared > 0:
                self.swap.pop(arr)
                s = res.slot
                if s.decoding:
                    s.replay = list(s.req.output)
                    s.decoding = False
                s.pos = 0
                s.n_pages = 0
                s.n_shared = 0
                s.cache_node = None
                s.snaps = None
                if s.pinned_node is not None:
                    self._pcache.unpin(s.pinned_node)
                    s.pinned_node = None
                self.stats["recomputes"] += 1
                self.scheduler._resume[arr] = _ResumeState(
                    mode="recompute", slot=s)
                n += 1
        return n

    def _admit(self):
        self._admit_fcfs(self.cfg.max_slots)
        if not self._slots and self.scheduler.head() is not None \
                and self._demote_trie_swaps():
            # Idle with the head blocked: the cached pages it needs are
            # pinned by swapped requests queued behind it, and no running
            # slot will ever free them.  Their swap states are demoted to
            # recompute (unpinned) and the head alone is admitted, so the
            # demoted requests cannot re-pin those pages before it runs.
            self._admit_fcfs(1)

    def _admit_fcfs(self, limit: int):
        free = [s for s in range(self.cfg.max_slots)
                if s not in self._slots][:limit]
        for slot in free:
            req = self.scheduler.head()
            if req is None:
                break
            resume = self.scheduler.peek_resume(req)
            if self._available_pages() < self._pages_needed_now(req, resume):
                break                       # not enough to progress (FCFS)
            self.scheduler.pop_head()
            resume = self.scheduler.take_resume(req)
            if resume is not None and resume.mode == "swap":
                self._swap_in(slot, req, resume)
            else:
                self._start_slot(slot, req, resume)

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        return _sample_tokens(logits, self.cfg.temperature, self._rng)

    # ------------------------------------------------------------------
    def _prefill_step(self):
        """Run ONE chunk of the oldest joining prompt (if any)."""
        if not self._prefill_order:
            return
        slot = self._prefill_order[0]
        s = self._slots[slot]
        n_chunk = min(self.chunk, len(s.tokens) - s.pos)
        with TraceAnnotation("serve.prefill", uid=s.req.uid, offset=s.pos,
                             n=n_chunk):
            logits = self._prefill_chunk(slot, s, n_chunk)
        if logits is None or s.pos < len(s.tokens):
            return
        # prompt done: first token
        if self._pcache is not None:
            self._insert_prefix(slot, s)
        self._prefill_order.pop(0)
        if s.replay:
            # recompute-resume: everything after the prompt was already
            # sampled before preemption; start teacher-forcing it back
            # through the decode path (budget was saved at preemption)
            s.last_token = s.replay.pop(0)
            s.decoding = True
            return
        logits = self._logits_to_host(logits)
        with TraceAnnotation("serve.sample"):
            tok = int(self._sample(logits)[0])
            s.req.output.append(tok)
            s.last_token = tok
            s.budget = s.req.max_new_tokens - 1
            s.decoding = True
            if s.budget <= 0 or (s.req.eos_id is not None
                                 and tok == s.req.eos_id):
                self._finish(slot)

    def _prefill_chunk(self, slot: int, s: _Slot, n_chunk: int):
        """Pages, batch and dispatch of one prefill chunk; the chunk's
        logits (on the device), or None when the slot self-preempted."""
        lo = s.pos // self.page_size
        hi = (s.pos + n_chunk - 1) // self.page_size
        if lo < s.n_shared:
            # this chunk rewrites pages the slot shares with the trie (the
            # full-prompt-hit re-run of the final chunk): copy-on-write
            # them into private pages first.  n_shared shrinks BEFORE the
            # loop so a self-preemption mid-loop treats already-copied
            # pages as private (their cache reference keeps them alive).
            end = s.n_shared
            s.n_shared = lo
            for lg in range(lo, end):
                if not self._cow_page(slot, lg):
                    return None             # self-preempted; resumes later
        for lg in range(lo, hi + 1):
            if not self._ensure_page(slot, lg):
                return None                 # self-preempted; resumes later
        tokens = np.zeros((1, self.chunk), np.int32)
        tokens[0, :n_chunk] = s.tokens[s.pos:s.pos + n_chunk]
        batch = {
            "tokens": jnp.asarray(tokens),
            "page_row": jnp.asarray(self._page_table[slot]),
            "offset": jnp.asarray(s.pos, jnp.int32),
            "chunk_len": jnp.asarray(n_chunk, jnp.int32),
            "slot": jnp.asarray(slot, jnp.int32),
        }
        out, self.caches = self._prefill_fn(self.params, batch, self.caches)
        logits = self._take_counters(out)
        s.pos += n_chunk
        self._lengths[slot] = s.pos
        self.stats["prefill_tokens"] += n_chunk
        if self._pcache is not None and s.pos % self.chunk == 0:
            # chunk boundary: capture the linear-totals snapshot that a
            # future hit at this depth will restore (None for dense stacks)
            if s.snaps is None:
                s.snaps = {}
            s.snaps[s.pos // self.page_size] = (
                jax.device_get(self._extract_totals_fn(
                    self.caches, jnp.asarray(slot, jnp.int32)))
                if self._slot_state else None)
        return logits

    def _take_counters(self, out):
        """The logits of a program's first output.  An expert stack's
        programs return their counters beside the logits (a dict); they
        wait on the device until ``_logits_to_host``."""
        if isinstance(out, dict):
            self._pending_counters.append(
                {k: v for k, v in out.items() if k != "logits"})
            return out["logits"]
        return out

    def _logits_to_host(self, logits) -> np.ndarray:
        """The blocking device->host copy of a step's logits, counted in
        ``stats['logits_to_host_bytes']``, and of the counters of the
        programs dispatched since the last copy (they ran before these
        logits, so the copy waits for nothing more)."""
        with TraceAnnotation("serve.logits_to_host"):
            out = np.asarray(logits)
            pending = jax.device_get(self._pending_counters)
        self._pending_counters = []
        for counters in pending:
            for k, v in counters.items():
                self.stats[k] += int(v)
        self.stats["logits_to_host_bytes"] += out.nbytes
        return out

    def _emit(self, slot: int, tok: int) -> bool:
        """Record one generated token for a slot; returns False once the
        slot finished (budget exhausted or eos hit)."""
        s = self._slots[slot]
        s.req.output.append(tok)
        s.last_token = tok
        s.budget -= 1
        if s.budget <= 0 or (s.req.eos_id is not None
                             and tok == s.req.eos_id):
            self._finish(slot)
            return False
        return True

    def _window_len(self, s: _Slot) -> int:
        """Valid rows of a slot's verify window this step: a verify emits
        up to window_len tokens, so the window is capped by the remaining
        budget — or, in replay mode, by the teacher-forced tokens left."""
        w = self.cfg.draft_len + 1
        if s.replay:
            return min(w, 1 + len(s.replay))
        return max(1, min(w, s.budget))

    def _decode_step(self):
        """One decode dispatch for every decoding slot — a single token
        per slot, or a whole draft window when speculative decoding is on
        (see docs/serving.md#engine-step-granularity)."""
        if self._spec:
            return self._decode_step_speculative()
        return self._decode_step_single()

    def _draft(self, tokens0, active):
        """Draft ``draft_len`` tokens per active slot through the
        configured drafter — the SLA2 linear branch ('linear') or prompt
        lookup over the slot token histories ('ngram').  Numpy results;
        patched out by the forced-reject tests."""
        history = None
        if getattr(self._drafter, "needs_history", False):
            history = [None] * self.cfg.max_slots
            for slot, s in self._slots.items():
                if active[slot]:
                    history[slot] = np.concatenate(
                        [s.tokens, np.asarray(s.req.output or [],
                                              np.int32)])
        return self._drafter.propose(
            self.params, self.caches,
            page_table=self._page_table, lengths=self._lengths,
            active=active, tokens0=tokens0, k=self.cfg.draft_len,
            rng=self._rng, history=history)

    def _decode_step_speculative(self):
        """One multi-token decode dispatch: draft through the linear
        branch, verify the window in one sparse paged pass, commit only
        the accepted prefix (rejected rows are never committed — their
        K/V bytes beyond the committed length are dead).  Page demand
        covers each slot's whole window up front, served oldest first, so
        pool exhaustion preempts youngest slots mid-draft — the window is
        simply not verified and the preempted slot resumes from its last
        COMMITTED state."""
        from repro.serve import speculative as speclib

        w = self.cfg.draft_len + 1
        dec = sorted((s for s, st in self._slots.items() if st.decoding),
                     key=lambda s: self._slots[s].req.arrival)
        if not dec:
            return
        with TraceAnnotation("serve.decode", rows=len(dec)):
            ready = []
            for slot in dec:
                if slot not in self._slots:     # preempted by an older slot
                    continue
                s = self._slots[slot]
                wlen = self._window_len(s)
                pos0 = int(self._lengths[slot])
                ok = True
                for lg in range(pos0 // self.page_size,
                                (pos0 + wlen - 1) // self.page_size + 1):
                    if not self._ensure_page(slot, lg):
                        ok = False              # self-preempted mid-window
                        break
                if ok and slot in self._slots:
                    ready.append(slot)
            ready = [s for s in ready if s in self._slots]
            if not ready:
                return
            tokens = np.zeros((self.cfg.max_slots, w), np.int32)
            wlens = np.zeros((self.cfg.max_slots,), np.int32)
            active = np.zeros((self.cfg.max_slots,), bool)
            draft_slots = []
            for slot in ready:
                s = self._slots[slot]
                wlen = self._window_len(s)
                tokens[slot, 0] = s.last_token
                wlens[slot] = wlen
                active[slot] = True
                if s.replay:
                    tokens[slot, 1:wlen] = s.replay[:wlen - 1]
                elif wlen > 1:
                    draft_slots.append(slot)
            d_logits = None
            if draft_slots:
                with TraceAnnotation("serve.draft", rows=len(draft_slots)):
                    d_toks, d_logits = self._draft(tokens[:, 0], active)
                for slot in draft_slots:
                    k_i = int(wlens[slot]) - 1
                    tokens[slot, 1:1 + k_i] = d_toks[slot, :k_i]
            batch = {
                "tokens": jnp.asarray(tokens),
                "page_table": jnp.asarray(self._page_table),
                "lengths": jnp.asarray(self._lengths),
                "active": jnp.asarray(active),
                "window_len": jnp.asarray(wlens),
            }
            out, self.caches = self._verify_fn(self.params, batch,
                                               self.caches)
        logits = self._logits_to_host(self._take_counters(out))  # (B, W, V)

        # --- host-side acceptance (greedy == plain decode, token-exact) --
        accepted = np.zeros((self.cfg.max_slots,), np.int32)
        plan = {}
        self.stats["spec_steps"] += 1
        with TraceAnnotation("serve.sample"):
            for slot in ready:
                s = self._slots[slot]
                wlen = int(wlens[slot])
                if s.replay:
                    # teacher-forced rows are correct by construction:
                    # cache the whole fed window (bit-identical
                    # recompute-resume)
                    plan[slot] = ("replay", wlen - 1)
                    accepted[slot] = wlen
                else:
                    k_i = wlen - 1
                    emitted, n_acc = speclib.rejection_sample(
                        tokens[slot, 1:1 + k_i],
                        None if d_logits is None else d_logits[slot, :k_i],
                        logits[slot, :k_i + 1],
                        temperature=self.cfg.temperature, rng=self._rng)
                    plan[slot] = ("emit", emitted)
                    accepted[slot] = n_acc + 1
                    self.stats["spec_drafted"] += k_i
                    self.stats["spec_accepted"] += n_acc

        # --- commit the accepted prefixes, then advance lengths ---
        # snapshot the host arrays: the commit dispatch is ASYNC and
        # jnp.asarray can alias numpy memory on CPU, while the lines right
        # below (and the next step's bookkeeping) mutate page table and
        # lengths — without the copies the in-flight commit may read the
        # advanced values (a rarely-losing data race)
        with TraceAnnotation("serve.commit"):
            self.caches = self._commit_fn(
                self.caches, jnp.asarray(self._page_table.copy()),
                jnp.asarray(self._lengths.copy()), jnp.asarray(accepted),
                jnp.asarray(active), w)
            for slot in ready:
                self._lengths[slot] += int(accepted[slot])

        # --- apply emissions / replay bookkeeping ---
        with TraceAnnotation("serve.sample"):
            for slot in ready:
                s = self._slots[slot]
                kind, payload = plan[slot]
                if kind == "replay":
                    m = payload
                    del s.replay[:m]
                    if s.replay:
                        s.last_token = s.replay.pop(0)
                    else:
                        # replay drained inside the window: the next REAL
                        # token comes from the last teacher-forced row
                        s.replay = None
                        t = int(self._sample(logits[slot, m][None])[0])
                        self._emit(slot, t)
                else:
                    for t in payload:
                        if not self._emit(slot, t):
                            break

    def _decode_step_single(self):
        """One token for every decoding slot.  Page demand is served oldest
        slot first, so pool exhaustion preempts the youngest slots (which
        drop out of this step and resume via the scheduler)."""
        dec = sorted((s for s, st in self._slots.items() if st.decoding),
                     key=lambda s: self._slots[s].req.arrival)
        if not dec:
            return
        with TraceAnnotation("serve.decode", rows=len(dec)):
            ready = []
            for slot in dec:
                if slot not in self._slots:     # preempted by an older slot
                    continue
                if self._lengths[slot] % self.page_size == 0 and \
                        not self._ensure_page(
                            slot, int(self._lengths[slot]) // self.page_size):
                    continue                    # self-preempted
                ready.append(slot)
            if not ready:
                return
            tokens = np.zeros((self.cfg.max_slots,), np.int32)
            active = np.zeros((self.cfg.max_slots,), bool)
            for slot in ready:
                tokens[slot] = self._slots[slot].last_token
                active[slot] = True
            batch = {
                "token": jnp.asarray(tokens),
                "page_table": jnp.asarray(self._page_table),
                "lengths": jnp.asarray(self._lengths),
                "active": jnp.asarray(active),
            }
            out, self.caches = self._decode_fn(self.params, batch,
                                               self.caches)
        logits = self._logits_to_host(self._take_counters(out))
        with TraceAnnotation("serve.sample"):
            tok = self._sample(logits)
            for slot in ready:
                st = self._slots[slot]
                self._lengths[slot] += 1    # input token entered the cache
                if st.replay:
                    # recompute catch-up: the sampled token is discarded —
                    # the real one was sampled before preemption and is
                    # next in line
                    st.last_token = st.replay.pop(0)
                    continue
                self._emit(slot, int(tok[slot]))

    def _finish(self, slot: int):
        s = self._slots[slot]
        with TraceAnnotation("serve.finish", uid=s.req.uid):
            if s.pinned_node is not None:
                self._pcache.unpin(s.pinned_node)
                s.pinned_node = None
            self.allocator.free(self._page_table[slot][
                self._page_table[slot] > 0])
            self._page_table[slot] = 0
            self._lengths[slot] = 0
            req = self._slots.pop(slot).req
            req.t_finish = time.perf_counter()
            self.completed.append(req)
            if slot in self._prefill_order:
                self._prefill_order.remove(slot)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine step: admit, one prefill chunk, one decode dispatch
        (single-token or speculative window — see
        docs/serving.md#engine-step-granularity).  Returns the number of
        occupied slots.  Steps that had work to do are counted in
        ``stats['engine_steps']`` (trailing no-op calls are not)."""
        with TraceAnnotation("serve.step"):
            if self._slots or self._queue:
                self.stats["engine_steps"] += 1
            with TraceAnnotation("serve.admit"):
                self._admit()
            self._prefill_step()
            self._decode_step()
            self.stats["swap_bytes"] = self.swap.used_bytes
            self.stats["min_available"] = self.allocator.min_available
            self.stats["pool_peak_pages"] = (self.allocator.num_pages - 1
                                             - self.allocator.min_available)
            return len(self._slots)

    def run_to_completion(self, max_steps: int = 10_000,
                          livelock_after: int = 50) -> list[Request]:
        """Step until every submitted request has drained.

        Raises RuntimeError instead of silently returning partial results
        when the engine stops making progress: either ``max_steps`` ran out
        with work still queued/active, or ``livelock_after`` consecutive
        steps changed nothing observable (no tokens emitted, no prefill
        advance, no scheduler transitions) while slots were occupied — the
        no-progress livelock a mis-sized pool or stuck admission produces.
        Previously both cases returned whatever had completed so far and
        callers mistook the partial list for a drained workload."""
        stalled, last_sig = 0, None
        for _ in range(max_steps):
            if self.step() == 0 and not self._queue:
                return self.completed
            sig = (len(self.completed), len(self.scheduler.waiting),
                   self.stats["preemptions"], self.stats["swap_ins"],
                   self.stats["prefill_tokens"],
                   tuple(int(x) for x in self._lengths),
                   sum(len(s.req.output or ())
                       for s in self._slots.values()))
            if sig == last_sig and self._slots:
                stalled += 1
                if stalled >= livelock_after:
                    raise RuntimeError(
                        f"engine livelock: {stalled} consecutive steps made "
                        f"no progress with {len(self._slots)} occupied "
                        f"slot(s) and {len(self._queue)} waiting request(s)")
            else:
                stalled, last_sig = 0, sig
        if self._slots or self._queue:
            raise RuntimeError(
                f"run_to_completion: max_steps={max_steps} exhausted with "
                f"{len(self._slots)} active slot(s) and {len(self._queue)} "
                f"waiting request(s)")
        return self.completed


# ===========================================================================
# Reference decode (regression oracle)
# ===========================================================================

def generate_sequential(model, params, prompt: np.ndarray, *,
                        max_new_tokens: int, max_len: int,
                        eos_id: Optional[int] = None,
                        cache_dtype=None) -> list[int]:
    """Unbatched greedy decode through the plain (non-paged) cache path:
    one model.prefill over the whole prompt, then model.decode one token at
    a time.  The continuous engine must match this token for token.
    ``cache_dtype`` overrides the static cache element dtype — pass
    'float32' alongside EngineConfig.page_dtype='float32' so oracle and
    engine store identical values on both sides of the comparison."""
    # jitted once per model (prefill re-traces per prompt length)
    if not hasattr(model, "_static_step_fns"):
        def static_prefill(p, b, c):
            return model.prefill(p, b, c)

        def static_decode(p, b, c):
            return model.decode(p, b, c)

        model._static_step_fns = (jax.jit(static_prefill),
                                  jax.jit(static_decode))
    prefill, decode = model._static_step_fns
    kw = {} if cache_dtype is None else {"dtype": jnp.dtype(cache_dtype)}
    caches = model.init_caches(1, max_len, **kw)
    logits, caches = prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, caches)
    out = [int(np.argmax(np.asarray(logits)[0]))]
    while len(out) < max_new_tokens and out[-1] != eos_id:
        logits, caches = decode(
            params, {"token": jnp.asarray([out[-1]], jnp.int32)}, caches)
        out.append(int(np.argmax(np.asarray(logits)[0])))
    return out
