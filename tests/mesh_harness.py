"""Mesh-serving scenarios, run in a SUBPROCESS with a forced multi-device
CPU platform (tests/test_mesh_serving.py sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before jax loads;
running this file directly sets it too).

Each scenario prints one JSON line (prefixed ``RESULT ``) and exits 0 on
success; any assertion failure propagates as a nonzero exit that the
pytest wrapper surfaces with this process's output.

    PYTHONPATH=src python tests/mesh_harness.py identity|fault|property|calibration
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

# mixed-length workload: prompts cross page (16-token) and prefill-chunk
# (32-token) boundaries; decode budgets keep several slots live at once
WORK = [(40, 8), (17, 8), (33, 8)]
LATE = [(64, 8)]                      # submitted mid-decode (late joiner)
MAX_LEN, CHUNK, SLOTS = 128, 32, 4


def _build(mechanism: str):
    import jax
    from repro.configs import get_smoke_config
    from repro.models.api import build_model
    kw = {} if mechanism == "sla2" else {"mechanism": "full"}
    cfg = get_smoke_config("qwen3_14b", **kw)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _pool_specs(caches) -> dict:
    """name -> PartitionSpec tuple for the placement-sensitive pool/total
    leaves (k_pages shards the page axis, h_tot the slot axis)."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        if key.endswith(("k_pages", "h_tot")) and "l0" in key:
            out[key] = tuple(leaf.sharding.spec)
    return out


def _assert_page_axis_sharded(eng, n_devices: int):
    """The load()-time placement must survive engine stepping: with a
    pool whose page count divides the mesh, k_pages stays sharded on the
    page axis (GSPMD would otherwise hand back replicated buffers —
    serve/engine pins every cache-returning jitted fn)."""
    if eng.allocator.num_pages % n_devices:
        return                      # replication fallback is the contract
    for key, spec in _pool_specs(eng.caches).items():
        if key.endswith("k_pages"):
            axes = [a for ax in spec if ax is not None
                    for a in (ax if isinstance(ax, tuple) else (ax,))]
            assert axes, f"{key} lost its page-axis sharding: {spec}"


def _run_engine(model, params, vocab: int, *, mesh, impl, num_pages,
                work=WORK, late=LATE, step_hook=None, **ekw):
    """Serve the mixed workload with a mid-decode late joiner; returns
    (uid -> greedy tokens, engine)."""
    from repro.serve import EngineConfig, ServeEngine
    from repro.serve.engine import make_mixed_requests
    eng = ServeEngine(model, EngineConfig(
        max_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
        num_pages=num_pages, paged_impl=impl, mesh=mesh, **ekw))
    eng.load(params)
    for r in make_mixed_requests(vocab, work):
        eng.submit(r)
    for _ in range(4):              # get decode going before the joiner
        eng.step()
        if step_hook:
            step_hook(eng)
    for r in make_mixed_requests(vocab, late, seed=7, uid0=len(work)):
        eng.submit(r)
    for _ in range(5000):
        n = eng.step()
        if step_hook:
            step_hook(eng)
        if n == 0 and not eng._queue:
            break
    else:
        raise AssertionError("workload did not drain")
    return {r.uid: list(r.output) for r in eng.completed}, eng


# ---------------------------------------------------------------------------
# scenario: identity — sharded == single-device across the path matrix
# ---------------------------------------------------------------------------

def scenario_identity() -> dict:
    """mechanism=full|sla2 x paged_impl=fused|gather on a 4-device host
    mesh: greedy outputs token-identical to the unsharded engine, with a
    late joiner and forced preemption (tight 12-page pool) in every cell;
    one extra sla2/fused cell runs on a 2-device sub-mesh so the prefill
    head-axis shard_map path (hkv=2 divides 2) is exercised too."""
    import jax
    from repro.launch.mesh import make_host_mesh
    assert len(jax.devices()) == 4, jax.devices()
    mesh4 = make_host_mesh(4)
    mesh2 = make_host_mesh(2)
    report = {}
    for mech in ("sla2", "full"):
        cfg, model, params = _build(mech)
        for impl in ("fused", "gather"):
            base, _ = _run_engine(model, params, cfg.vocab_size,
                                  mesh=None, impl=impl, num_pages=12)
            shard, eng = _run_engine(model, params, cfg.vocab_size,
                                     mesh=mesh4, impl=impl, num_pages=12)
            assert shard == base, f"{mech}/{impl} diverged on the mesh"
            assert eng.stats["preemptions"] > 0, \
                f"{mech}/{impl}: pool was not tight enough to preempt"
            _assert_page_axis_sharded(eng, 4)
            report[f"{mech}/{impl}"] = {
                "requests": len(base),
                "preemptions": eng.stats["preemptions"]}
        if mech == "sla2":
            shard2, _ = _run_engine(model, params, cfg.vocab_size,
                                    mesh=mesh2, impl="fused", num_pages=12)
            base_f, _ = _run_engine(model, params, cfg.vocab_size,
                                    mesh=None, impl="fused", num_pages=12)
            assert shard2 == base_f, "sla2/fused diverged on the 2-mesh"
            report["sla2/fused@2dev"] = {"requests": len(shard2)}
    return report


# ---------------------------------------------------------------------------
# scenario: fault — simulated host death mid-decode, reshard, token parity
# ---------------------------------------------------------------------------

def scenario_fault() -> dict:
    """Host 2 of 4 goes silent mid-decode (HeartbeatMonitor with an
    injected clock — no process dies); the engine must preempt into
    swap/recompute, reshard onto the 3 survivors and finish with tokens
    identical to a never-failed sharded run."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.serve import EngineConfig, ServeEngine
    from repro.serve.engine import make_mixed_requests
    assert len(jax.devices()) == 4
    cfg, model, params = _build("sla2")
    work = [(40, 12), (17, 12), (33, 12), (64, 12)]

    def run(fail: bool):
        eng = ServeEngine(model, EngineConfig(
            max_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
            num_pages=16, paged_impl="fused", mesh=make_host_mesh(4),
            heartbeat_deadline_s=1.0, heartbeat_misses=2))
        eng.load(params)
        for r in make_mixed_requests(cfg.vocab_size, work):
            eng.submit(r)
        steps = 0
        while True:
            n = eng.step()
            steps += 1
            if fail and steps == 8:
                # several slots are mid-decode here.  Drive the injected
                # clock: host 2's LAST beat is at t=0.5, then it goes
                # silent, misses two 1s deadlines and is declared dead.
                for h in (0, 1, 2, 3):
                    eng.heartbeat(h, now=0.5)
                assert eng.check_faults(now=1.1) == []
                for h in (0, 1, 3):
                    eng.heartbeat(h, now=2.5)
                assert eng.check_faults(now=2.6) == []      # miss 1
                for h in (0, 1, 3):
                    eng.heartbeat(h, now=4.0)
                dead = eng.check_faults(now=4.1)            # miss 2
                assert dead == [2], dead
                assert len(list(eng.mesh.devices.flat)) == 3
            if n == 0 and not eng._queue:
                break
            assert steps < 5000, "fault workload did not drain"
        return {r.uid: list(r.output) for r in eng.completed}, eng

    ok_out, _ = run(False)
    f_out, eng = run(True)
    assert f_out == ok_out, "tokens diverged across the host failure"
    st = eng.stats
    assert st["host_failures"] == 1 and st["reshards"] == 1
    assert st["preemptions"] >= 1 and st["recomputes"] + st["swap_ins"] >= 1
    return {"requests": len(f_out),
            "stats": {k: st[k] for k in ("host_failures", "reshards",
                                         "preemptions", "recomputes",
                                         "swap_ins", "swap_outs")}}


# ---------------------------------------------------------------------------
# scenario: property — per-step pool invariants + int8 round-trip on mesh
# ---------------------------------------------------------------------------

def scenario_property() -> dict:
    """PR 6's refcount/free-list/trie conservation law, extended to the
    mesh: after EVERY step of a sharded prefix-cache engine under pool
    pressure the invariants hold AND the pool keeps its page-axis
    placement; an int8-quantized sharded pool (storage round-trips
    through codes+scales on every shard) still matches the unsharded
    int8 engine token-for-token."""
    import jax
    from test_prefix_cache import _check_pool_invariants
    from repro.launch.mesh import make_host_mesh
    assert len(jax.devices()) == 4
    cfg, model, params = _build("sla2")
    mesh4 = make_host_mesh(4)
    rng = np.random.default_rng(3)
    sys_p = rng.integers(1, cfg.vocab_size, 32).astype(np.int32)
    steps = {"n": 0}

    def hook(eng):
        steps["n"] += 1
        _check_pool_invariants(eng)
        _assert_page_axis_sharded(eng, 4)

    # shared-prefix workload through a tight pool: hits, CoW and
    # preemption all fire while the invariants are checked per step
    from repro.serve import EngineConfig, Request, ServeEngine
    eng = ServeEngine(model, EngineConfig(
        max_slots=3, max_len=MAX_LEN, prefill_chunk=CHUNK, num_pages=12,
        paged_impl="fused", mesh=mesh4, prefix_cache=True))
    eng.load(params)
    prompts = [np.concatenate([sys_p, rng.integers(
        1, cfg.vocab_size, int(n)).astype(np.int32)])
        for n in (9, 17, 26, 12)]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
    for _ in range(5000):
        n = eng.step()
        hook(eng)
        if n == 0 and not eng._queue:
            break
    else:
        raise AssertionError("property workload did not drain")
    assert len(eng.completed) == len(prompts)
    assert eng.stats["prefix_hits"] >= 1

    # int8 pool round-trip: sharded quantized == unsharded quantized
    base, _ = _run_engine(model, params, cfg.vocab_size, mesh=None,
                          impl="fused", num_pages=12, kv_quant="int8")
    shard, qeng = _run_engine(model, params, cfg.vocab_size, mesh=mesh4,
                              impl="fused", num_pages=12, kv_quant="int8",
                              step_hook=lambda e: _check_pool_invariants(e))
    assert shard == base, "int8 pool diverged on the mesh"
    return {"steps_checked": steps["n"],
            "prefix_hits": eng.stats["prefix_hits"],
            "preemptions": eng.stats["preemptions"],
            "int8_requests": len(shard)}


# ---------------------------------------------------------------------------
# scenario: calibration — the >1-device checks tier-1 used to skip
# ---------------------------------------------------------------------------

def scenario_calibration() -> dict:
    """The SPMD calibration facts launch/roofline.py and the compression
    module rely on, measured on a real 4-device mesh (tier-1 runs on one
    device, where these used to skip):

      * cost_analysis flops and memory_analysis argument bytes are
        per-partition on an SPMD module;
      * _fit_to_shape drops mesh axes the dim size cannot divide;
      * int8_all_reduce_mean agrees with the bf16 psum baseline to
        quantization tolerance across a real 4-wide axis.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import sharding as shardlib
    from repro.distributed.compression import (bf16_all_reduce_mean,
                                               int8_all_reduce_mean)
    n = len(jax.devices())
    assert n == 4
    mesh = jax.make_mesh((n, 1), ("data", "model"))

    x = jax.ShapeDtypeStruct((n * 8, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    with mesh:
        c = jax.jit(lambda x, w: x @ w).lower(x, w).compile()
    ca = c.cost_analysis()
    flops = (ca[0] if isinstance(ca, (list, tuple)) else ca)["flops"]
    total = 2 * (n * 8) * 128 * 128
    np.testing.assert_allclose(flops, total / n, rtol=0.01)
    arg = c.memory_analysis().argument_size_in_bytes
    assert arg == 8 * 128 * 4 + 128 * 128 * 4

    spec = shardlib.spec_for_path("attn/wq", 2, mesh, (7, 13))
    assert all(s is None or s == "model" for s in spec)

    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((n, 64, 8)), jnp.float32)
    kw = dict(mesh=mesh, in_specs=P("data"), out_specs=P("data"),
              check_vma=False)
    q = jax.shard_map(lambda v: int8_all_reduce_mean(v[0], "data")[None],
                      **kw)(g)
    b = jax.shard_map(lambda v: bf16_all_reduce_mean(v[0], "data")[None],
                      **kw)(g)
    err = float(jnp.max(jnp.abs(q - b)))
    amax = float(jnp.max(jnp.abs(g)))
    assert err <= 2.5 * amax / 127, (err, amax)
    return {"per_device_flops": float(flops),
            "int8_vs_bf16_allreduce_max_err": err}


SCENARIOS = {"identity": scenario_identity, "fault": scenario_fault,
             "property": scenario_property,
             "calibration": scenario_calibration}


def main(argv):
    name = argv[1]
    out = SCENARIOS[name]()
    print("RESULT " + json.dumps({"scenario": name, "ok": True, **out}))


if __name__ == "__main__":
    main(sys.argv)
