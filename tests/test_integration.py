"""Integration tests: trainer fault tolerance, checkpoint semantics,
two-stage training, serving engine, gradient compression, schedules."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, latest_step, restore, save
from repro.configs import get_smoke_config
from repro.data import make_dataset
from repro.models.api import build_model
from repro.optim import AdamWConfig
from repro.serve import EngineConfig, Request, ServeEngine
from repro.train import (TrainConfig, Trainer, TrainerConfig,
                         init_train_state, make_train_step)
from repro.train.trainer import run_with_restarts


@pytest.fixture(scope="session")
def small_model(qwen3_smoke):
    return qwen3_smoke


@pytest.mark.slow
def test_trainer_crash_restart_resumes_deterministically(small_model):
    """A crash mid-run restarts from the checkpoint and the final state is
    IDENTICAL to an uninterrupted run (pure-function data pipeline)."""
    cfg, model = small_model
    ds = make_dataset(cfg, seq_len=64, global_batch=2, seed=3)

    def make(ckpt_dir, fault):
        return Trainer(model, TrainerConfig(
            train=TrainConfig(optimizer=AdamWConfig(lr=1e-3),
                              warmup_steps=2, total_steps=12),
            ckpt_dir=ckpt_dir, max_steps=10, ckpt_every=4,
            log_every=100), ds, fault_hook=fault, log_fn=lambda s: None)

    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        out_clean = make(d1, None).run()

        crashed = {"done": False}

        def fault(step):
            if step == 6 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("injected")

        out_crash = run_with_restarts(lambda: make(d2, fault))
        assert out_crash["restarts"] == 1
        for a, b in zip(jax.tree.leaves(out_clean["state"]["params"]),
                        jax.tree.leaves(out_crash["state"]["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


def test_checkpoint_atomic_keep_and_elastic_dtype():
    with tempfile.TemporaryDirectory() as d:
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.ones((2,), jnp.int32)}}
        for s in (1, 2, 3, 4):
            save(d, s, tree, keep=2)
        assert latest_step(d) == 4
        assert len(os.listdir(d)) == 2          # keep-k GC
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32)
            if x.dtype != jnp.int32 else x, tree)
        back = restore(d, 4, like)
        np.testing.assert_allclose(np.asarray(back["a"]),
                                   np.asarray(tree["a"]))
        # a stale .tmp directory must be invisible to restore
        os.makedirs(os.path.join(d, "step_00000009.tmp"))
        assert latest_step(d) == 4


def test_checkpointer_async_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=1)
        tree = {"w": jnp.full((4, 4), 3.0)}
        ck.save_async(7, tree)
        ck.wait()
        step, got = ck.restore_latest(tree)
        assert step == 7
        np.testing.assert_allclose(np.asarray(got["w"]), 3.0)


@pytest.mark.slow
def test_two_stage_training_improves_over_heuristic():
    """Stage-1 (router+alpha fit) must beat the SLA-style heuristic
    initialisation on hard-Top-k MSE."""
    from repro.core.router import RouterConfig
    from repro.core.sla2 import SLA2Config
    from repro.train.stage1 import (Stage1Config, capture_qkv_stream,
                                    run_stage1)
    key = jax.random.PRNGKey(0)
    cfg = SLA2Config(router=RouterConfig(block_q=32, block_k=16,
                                         k_frac=0.1, causal=False),
                     quant_bits="none", impl="ref")
    stream = capture_qkv_stream(key, batch=2, heads=2, seq=256, dim=32)
    params, hist = run_stage1(
        key, stream, cfg,
        Stage1Config(k_fracs=(0.1,), steps_per_k=40,
                     optimizer=AdamWConfig(lr=3e-3, weight_decay=0.0),
                     tau_start=0.5, tau_end=0.02),
        head_dim=32, num_heads=2, n_q_blocks=8, log_fn=lambda s: None)
    pk = hist["per_k"][0.1]
    assert pk["after"] < pk["before"] * 0.7


@pytest.mark.slow
def test_grad_compression_ef_converges(small_model):
    """EF-int8 compressed training reaches a loss close to uncompressed."""
    cfg, model = small_model
    ds = make_dataset(cfg, seq_len=64, global_batch=2, seed=1)
    losses = {}
    for mode in ("none", "int8_ef"):
        tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3), warmup_steps=2,
                         total_steps=30, compress_grads=mode)
        st = init_train_state(model, jax.random.PRNGKey(0), tc)
        fn = make_train_step(model, tc)
        for step in range(15):
            b = {k: jnp.asarray(v) for k, v in ds[step].items()}
            st, m = fn(st, b)
        losses[mode] = float(m["loss"])
    assert abs(losses["int8_ef"] - losses["none"]) < 0.15 * losses["none"]


def test_serving_engine_completes_requests(small_model, qwen3_params):
    cfg, model = small_model
    # shapes match tests/test_serving.py so the jitted step fns (cached on
    # the session-scoped model) are reused, not recompiled
    eng = ServeEngine(model, EngineConfig(max_slots=3, max_len=192,
                                          prefill_chunk=32))
    eng.load(qwen3_params)
    reqs = [Request(uid=i, prompt=np.arange(1, 7, dtype=np.int32),
                    max_new_tokens=5) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    for _ in range(100):
        if eng.step() == 0 and not eng._queue:
            break
    for r in reqs:
        assert r.output is not None and len(r.output) == 5
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_int8_all_to_all_reduce_roundtrip():
    """The wire-compressed all-reduce ~= psum mean (single-device uses a
    trivial 1-member axis via shard_map over a 1-sized mesh)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.compression import int8_all_reduce_mean
    mesh = jax.make_mesh((1,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(0), (64,))
    f = jax.shard_map(lambda a: int8_all_reduce_mean(a, "pod"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    y = f(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0.02)


def test_lr_schedule_shapes():
    from repro.optim.schedules import cosine_schedule
    s0 = float(cosine_schedule(0, 10, 100))
    s_peak = float(cosine_schedule(10, 10, 100))
    s_end = float(cosine_schedule(100, 10, 100))
    assert s0 < 0.2 and abs(s_peak - 1.0) < 0.01 and s_end <= 0.11


def test_straggler_and_heartbeat_policies():
    from repro.distributed.fault_tolerance import (ElasticPlan,
                                                   HeartbeatMonitor,
                                                   StragglerPolicy)
    hb = HeartbeatMonitor(deadline_s=1.0, misses_allowed=2)
    hb.beat(0, now=0.0)
    hb.beat(1, now=0.0)
    assert hb.check(now=0.5) == []
    hb.check(now=2.0)
    assert 0 in hb.check(now=4.0)

    sp = StragglerPolicy(factor=2.0, strikes=2)
    assert sp.observe(3, 0.1, ema=0.1) is None
    assert sp.observe(3, 1.0, ema=0.1) == "warn:3"
    assert sp.observe(3, 1.0, ema=0.1) == "evict:3"

    plan = ElasticPlan(512, 256)
    assert plan.new_mesh_shape(16) == (16, 16)
    assert plan.reshardable


def test_compile_cache_dir_env_wins_else_repo_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR is JAX's own to read: the helper sets no
    directory then.  Unset, the cache goes to the fixed <repo>/.jax_cache."""
    from repro.launch.cache import REPO_CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert (REPO_CACHE_DIR.parent / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_peaks_keyed_by_device_kind():
    from repro.launch.mesh import V5E, chip_peaks, make_host_mesh
    v5e = chip_peaks(V5E)
    assert v5e.ici_bw == 200e9                 # 1,600 Gbit/s
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
    with pytest.raises(ValueError, match="has only"):
        make_host_mesh(len(jax.devices()) + 1)
