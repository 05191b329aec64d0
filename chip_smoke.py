"""Bring-up smoke run: the two serving paths on a TPU, end to end.

    python chip_smoke.py [--seed 0]          # one chip: phases A and B
    python chip_smoke.py --four-chips        # sharded serving on 4 chips

Phase A denoises ``wan_dit_1_3b`` at its full published config (30
layers, 32k latent tokens, int8 QAT) through ``DiffusionEngine``; phase B
serves ``qwen3_14b`` at its published widths, cut to 4 layers, through
``ServeEngine``.  Weights are random, drawn from ``--seed``.  Each phase
checks its outputs against the repository's own references on the same
chip and prints compile seconds, run seconds (``block_until_ready``),
peak device memory and the parity numbers.  ``--four-chips`` runs only
phase B's model on a 4-device mesh against one device.

The script needs a TPU: on any other platform it exits non-zero before
running a phase.  Everything runs in this one process, and any failed
check raises.  The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Tolerances, each with its reason (printed beside the measured value).
DIT_GATHER_TOL = (
    5e-2, "relative L2 of final latents; int8 QAT tiles: each path rounds "
    "Q/K/P/V to 127 levels per tile, so the kernel's and XLA's different "
    "exp/accumulation order flip codes by one level, compounding over 30 "
    "layers x up to 4 Euler steps")
LM_GATHER_TOL = (
    2e-2, "relative L2 of first-decode logits; bf16 weights and "
    "activations: fused and gather attention differ only in f32 "
    "summation order, which flips single bf16 roundings (2^-8 relative) "
    "that compound over 4 layers")
LM_PROMPT_LENS = (512, 1024, 1536, 2048)
LM_NEW_TOKENS = 16


class SmokeFailure(RuntimeError):
    """A smoke check that did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile (or persistent
    cache load) durations while active."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


class FirstCall:
    """Wraps an engine's jitted step function: passes every call through
    and keeps the first call's arguments (and, for LM steps, the logits
    of every call), so the compiled program can be inspected after the
    run."""

    def __init__(self, fn, keep_logits: bool = False):
        self.fn, self.args, self.keep_logits = fn, None, keep_logits
        self.calls = []

    def __call__(self, *args):
        out = self.fn(*args)
        if self.args is None:
            self.args = args
        if self.keep_logits:
            batch = args[1]
            self.calls.append((np.asarray(batch["active"]),
                               np.asarray(batch["token"]),
                               np.asarray(out[0], np.float32)))
        return out

    def kernel_in_program(self, name: str) -> bool:
        text = self.fn.lower(*self.args).as_text()
        return "tpu_custom_call" in text and name in text


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# Phase A: DiT denoise (wan-dit-1.3b, 32k latent tokens)
# ---------------------------------------------------------------------------

ADALN_ZERO_WEIGHTS = ("['ada']['w']", "['final_ada']['w']",
                      "['patch_out']['w']")


def dit_params(model, seed: int):
    """Random DiT weights from ``seed``.  ``init_dit`` is adaLN-zero: the
    block modulations, the final modulation and the output projection
    start at zero, so the predicted velocity is 0 and no latent moves.  A
    trained model's are nonzero, so they are drawn at random here too
    (std fan_in ** -0.5) and every output depends on the attention path."""
    import jax
    import jax.numpy as jnp
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(paths)))

    def fill(path, leaf):
        key = next(keys)
        if not jax.tree_util.keystr(path).endswith(ADALN_ZERO_WEIGHTS):
            return leaf
        w = jax.random.normal(key, leaf.shape, jnp.float32)
        return (w * leaf.shape[-2] ** -0.5).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(fill, params)


def phase_dit(seed: int, clock: CompileClock, dev) -> dict:
    import jax
    from repro.configs import get_config
    from repro.kernels.ops import default_interpret
    from repro.models.api import build_model
    from repro.serve.diffusion import (DiffusionEngine, DiffusionEngineConfig,
                                       denoise_sequential,
                                       make_video_requests,
                                       resolve_attn_impl)

    cfg = get_config("wan_dit_1_3b")
    n_latent = cfg.max_target_len
    check(resolve_attn_impl("auto") == "fused",
          "DiT attn_impl='auto' did not resolve to the fused kernel")
    check(not default_interpret(), "Pallas kernels would run interpreted")
    model = build_model(cfg)
    params = dit_params(model, seed)
    ecfg = DiffusionEngineConfig(max_slots=2, n_latent=n_latent, max_steps=4)

    def requests():
        return make_video_requests(3, cfg, n_latent=n_latent,
                                   steps=(2, 3, 4), seed=seed)

    eng = DiffusionEngine(model, params, ecfg)
    check(eng.model.cfg.sla2_impl == "kernel",
          f"engine resolved sla2_impl={eng.model.cfg.sla2_impl!r}")
    eng._step_fn = probe = FirstCall(eng._step_fn)
    clock.lap()

    def serve():
        for r in requests():
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run_to_completion()
        out = {r.uid: r.output for r in done}    # host copies: synced
        return out, time.perf_counter() - t0

    cold, cold_s = serve()
    compile_s = clock.lap()
    warm, run_s = serve()
    warm_compile_s = clock.lap()
    check(sorted(cold) == [0, 1, 2], f"completed {sorted(cold)}")
    noise = {r.uid: r.latents for r in requests()}
    for uid, lat in cold.items():
        check(lat.shape == (n_latent, cfg.c_latent), f"shape {lat.shape}")
        check(bool(np.isfinite(lat).all()), f"request {uid}: non-finite")
        check(rel_l2(lat, noise[uid]) > 1e-2,
              f"request {uid}: denoising left the latents unchanged")
        check(np.array_equal(lat, warm[uid]),
              f"request {uid}: warm pass differs from cold pass")
    check(probe.kernel_in_program(f"sla2_sparse_fwd_{cfg.quant_bits}"),
          "the denoise step holds no compiled sparse_flash_fwd kernel")

    t0 = time.perf_counter()
    seq = denoise_sequential(model, params, requests(), ecfg)
    seq_s = time.perf_counter() - t0
    seq_compile_s = clock.lap()
    identical = all(np.array_equal(cold[u], seq[u]) for u in cold)

    t0 = time.perf_counter()
    gather = denoise_sequential(
        model, params, requests()[:1],
        dataclasses.replace(ecfg, attn_impl="gather"))[0]
    gather_s = time.perf_counter() - t0
    gather_compile_s = clock.lap()
    err = rel_l2(seq[0], gather)
    tol, why = DIT_GATHER_TOL
    res = {"compile_s": compile_s, "cold_s": cold_s, "run_s": run_s,
           "warm_compile_s": warm_compile_s,
           "engine_steps": eng.stats["engine_steps"] // 2,
           "peak_bytes_in_use": peak_bytes(dev),
           "bit_identical_to_sequential": identical,
           "sequential_s": seq_s, "sequential_compile_s": seq_compile_s,
           "gather_s": gather_s, "gather_compile_s": gather_compile_s,
           "fused_vs_gather_rel_l2": err,
           "fused_vs_gather_max_abs": float(np.abs(seq[0] - gather).max()),
           "tolerance": tol}
    log("dit", **res)
    log("dit", tolerance_reason=repr(why))
    check(identical, "batched outputs differ from denoise_sequential")
    check(err <= tol, f"fused vs gather rel L2 {err} > {tol}")
    return res


# ---------------------------------------------------------------------------
# Phase B: paged LM serving (qwen3-14b widths, 4 layers)
# ---------------------------------------------------------------------------

def lm_setup(seed: int):
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.serve import Request

    cfg = get_config("qwen3_14b", n_layers=4)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in LM_PROMPT_LENS]

    def requests():
        return [Request(uid=i, prompt=p, max_new_tokens=LM_NEW_TOKENS)
                for i, p in enumerate(prompts)]
    return cfg, model, params, requests


def lm_engine_config(**kw):
    from repro.serve import EngineConfig
    max_len = max(LM_PROMPT_LENS) + LM_NEW_TOKENS
    return EngineConfig(max_slots=len(LM_PROMPT_LENS), max_len=max_len, **kw)


def lm_serve(eng, requests) -> tuple[dict, float]:
    for r in requests():
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run_to_completion()
    eng.completed = []
    return {r.uid: list(r.output) for r in done}, time.perf_counter() - t0


def check_lm_outputs(cfg, outs: dict, what: str) -> None:
    check(sorted(outs) == list(range(len(LM_PROMPT_LENS))),
          f"{what}: completed {sorted(outs)}")
    for uid, toks in outs.items():
        check(len(toks) == LM_NEW_TOKENS, f"{what} {uid}: {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"{what} {uid}: token out of range")


def phase_lm(seed: int, clock: CompileClock, dev) -> dict:
    from repro.models.attention import resolve_paged_impl, use_fused
    from repro.serve import ServeEngine

    cfg, model, params, requests = lm_setup(seed)
    eng = ServeEngine(model, lm_engine_config())
    acfg = eng.model.cfg.attention_config()
    check(resolve_paged_impl(acfg) == "fused",
          "paged_impl='auto' did not resolve to the fused kernels")
    check(use_fused(acfg, "prefill") and use_fused(acfg, "decode"),
          "the fused paged prefill/decode entries are not dispatched")
    eng.load(params)
    eng._prefill_fn = prefill = FirstCall(eng._prefill_fn)
    eng._decode_fn = decode = FirstCall(eng._decode_fn, keep_logits=True)
    clock.lap()
    fused, cold_s = lm_serve(eng, requests)
    compile_s = clock.lap()
    first = decode.calls[0]
    decode.keep_logits = False
    warm, run_s = lm_serve(eng, requests)
    warm_compile_s = clock.lap()
    check_lm_outputs(cfg, fused, "fused")
    check(fused == warm, "warm pass tokens differ from cold pass")
    check(prefill.kernel_in_program("sla2_prefill_paged"),
          "the prefill step holds no compiled paged_flash_prefill kernel")
    check(decode.kernel_in_program("sla2_decode_paged"),
          "the decode step holds no compiled sla2_decode_fused kernel")

    ref = ServeEngine(model, lm_engine_config(paged_impl="gather"))
    ref.load(params)
    ref._decode_fn = ref_decode = FirstCall(ref._decode_fn, keep_logits=True)
    gather, gather_s = lm_serve(ref, requests)
    gather_compile_s = clock.lap()
    check_lm_outputs(cfg, gather, "gather")

    act, tok, logits = first
    r_act, r_tok, r_logits = ref_decode.calls[0]
    check(np.array_equal(act, r_act) and np.array_equal(tok[act], r_tok[act]),
          "first decode dispatch had different inputs in the two engines")
    check(bool(np.isfinite(logits[act]).all()), "non-finite logits")
    err = rel_l2(logits[act], r_logits[act])
    tol, why = LM_GATHER_TOL
    agree = float(np.mean([np.mean(np.equal(fused[u], gather[u]))
                           for u in fused]))
    res = {"compile_s": compile_s, "cold_s": cold_s, "run_s": run_s,
           "warm_compile_s": warm_compile_s,
           "engine_steps": eng.stats["engine_steps"] // 2,
           "prefill_tokens": eng.stats["prefill_tokens"] // 2,
           "peak_bytes_in_use": peak_bytes(dev),
           "gather_s": gather_s, "gather_compile_s": gather_compile_s,
           "first_decode_rows": int(act.sum()),
           "logits_rel_l2": err,
           "logits_max_abs": float(np.abs(logits[act] - r_logits[act]).max()),
           "tolerance": tol, "greedy_token_agreement": agree}
    log("lm", **res)
    log("lm", tolerance_reason=repr(why))
    check(err <= tol, f"fused vs gather logits rel L2 {err} > {tol}")
    return res


# ---------------------------------------------------------------------------
# Four chips: sharded paged serving against one device
# ---------------------------------------------------------------------------

def _spans_devices(tree, suffix: str, n: int) -> bool:
    """Every leaf named ``suffix`` is split into n distinct shards on n
    distinct devices (not replicated, not all on device 0)."""
    import jax
    leaves = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        tree)[0] if jax.tree_util.keystr(path).endswith(f"['{suffix}']")]
    if not leaves:
        return False
    for leaf in leaves:
        shards = leaf.addressable_shards
        if (len({s.device for s in shards}) != n
                or len({str(s.index) for s in shards}) != n):
            return False
    return True


def phase_mesh(seed: int, clock: CompileClock, n: int) -> dict:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine

    cfg, model, params, requests = lm_setup(seed)
    eng1 = ServeEngine(model, lm_engine_config())
    eng1.load(params)
    clock.lap()
    one, one_s = lm_serve(eng1, requests)
    one_compile_s = clock.lap()
    check_lm_outputs(cfg, one, "one device")

    # the page axis splits over the mesh: size the pool to a multiple of n
    pages = -(-eng1.allocator.num_pages // n) * n
    engn = ServeEngine(model, lm_engine_config(mesh=make_host_mesh(n),
                                               num_pages=pages))
    engn.load(params)
    check(_spans_devices(engn.caches, "k_pages", n),
          f"the page pool is not split over {n} devices")
    check(_spans_devices(engn.caches, "h_tot", n),
          f"the per-slot totals are not split over {n} devices")
    sharded, cold_s = lm_serve(engn, requests)
    compile_s = clock.lap()
    check(_spans_devices(engn.caches, "k_pages", n),
          "the page pool lost its placement while serving")
    check_lm_outputs(cfg, sharded, "sharded")
    identical = sharded == one
    # per request: index of the first token that differs (-1: none)
    first_diff = {u: next((i for i, (a, b) in enumerate(zip(sharded[u], one[u]))
                           if a != b), -1) for u in one}
    res = {"devices": n, "one_device_s": one_s,
           "one_device_compile_s": one_compile_s,
           "sharded_cold_s": cold_s, "sharded_compile_s": compile_s,
           "token_identical": identical, "first_diff": first_diff,
           "peak_bytes_in_use": [peak_bytes(d) for d in jax.devices()[:n]]}
    log("mesh", **res)
    check(identical, "sharded tokens differ from one device")
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded serving on a 4-device mesh")
    args = ap.parse_args()
    if args.four_chips:
        # Inside a fusion XLA may keep f32 between bf16 ops.  Which
        # roundings it skips depends on how a program fuses, so the sharded
        # and the one-device programs would differ in the last bf16 bit.
        # Token identity is checked with every bf16 rounding kept.
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            "--xla_allow_excess_precision=false")))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    log("device", kind=repr(dev.device_kind), count=len(devices),
        compile_cache=use_compile_cache())
    clock = CompileClock()
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 devices, "
              f"found {len(devices)}")
        phase_mesh(args.seed, clock, 4)
    else:
        phase_dit(args.seed, clock, dev)
        phase_lm(args.seed, clock, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
