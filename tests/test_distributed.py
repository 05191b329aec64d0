"""Distribution-layer tests: sharding rules, batch/cache spec ladders,
the fault-tolerance policy pieces the sharded serving engine wires in,
the int8 wire compression, and the SPMD cost/memory calibration the
roofline analysis relies on.  Everything here runs live on tier-1's
single device; the genuinely-multi-device variants run on a forced
4-device CPU platform in tests/mesh_harness.py (CI ``mesh`` job)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import fault_tolerance as ftlib
from repro.distributed import sharding as shardlib


@pytest.fixture(scope="module")
def mesh2d():
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def test_param_rules_match_paths(mesh2d):
    specs = {
        "embed/table": (100, 64),
        "groups/l0/attn/wq": (4, 64, 128),
        "groups/l0/attn/wo": (4, 128, 64),
        "groups/l0/mlp/w_up": (4, 64, 256),
        "groups/l0/moe/w_in": (4, 8, 64, 128),
        "groups/l0/attn/sla2/router/proj_q": (4, 64, 64),
        "groups/l0/ln1/scale": (4, 64),
    }
    for path, shape in specs.items():
        spec = shardlib.spec_for_path(path, len(shape), mesh2d, shape)
        assert isinstance(spec, P)
    # wq: trailing dims (DP, model), leading layer dim None
    wq = shardlib.spec_for_path("groups/l0/attn/wq", 3, mesh2d,
                                (4, 64, 128))
    assert wq[0] is None
    # norm scale: replicated
    ln = shardlib.spec_for_path("groups/l0/ln1/scale", 2, mesh2d, (4, 64))
    assert all(s is None for s in ln)


def test_fit_to_shape_drops_indivisible():
    # fixed-size fake mesh (the real-device variant runs in the mesh
    # harness): a 4-wide data axis cannot divide dim 7, so the wq rule's
    # data-parallel axis is dropped while 'model' (width 2, divides 8)
    # survives
    from unittest import mock
    mesh = mock.Mock()
    mesh.axis_names = ("data", "model")
    mesh.shape = {"data": 4, "model": 2}
    spec = shardlib.spec_for_path("attn/wq", 2, mesh, (7, 13))
    assert all(s is None for s in spec)
    spec = shardlib.spec_for_path("attn/wq", 2, mesh, (7, 8))
    assert spec[0] is None and spec[1] == "model"
    spec = shardlib.spec_for_path("attn/wq", 2, mesh, (8, 8))
    assert spec[0] == "data" and spec[1] == "model"


def test_batch_spec_ladder():
    # fixed-size fake mesh semantics: exercise the ladder logic with a
    # 4-wide data axis regardless of real device count
    import numpy as np
    from unittest import mock
    mesh = mock.Mock()
    mesh.axis_names = ("data", "model")
    mesh.shape = {"data": 4, "model": 2}
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "odd": jax.ShapeDtypeStruct((3, 16, 8), jnp.float32),
             "tiny": jax.ShapeDtypeStruct((1,), jnp.float32)}
    specs = shardlib.batch_specs(batch, mesh)
    assert specs["tokens"][0] == "data"             # batch over dp
    assert specs["odd"][0] is None and specs["odd"][1] == "data"  # seq
    assert all(s is None for s in specs["tiny"])
    # pure_dp: batch over ALL axes when divisible
    specs = shardlib.batch_specs(batch, mesh, pure_dp=True)
    assert specs["tokens"][0] == ("data", "model")


def test_cache_specs_handle_stacked_layers():
    from unittest import mock
    mesh = mock.Mock()
    mesh.axis_names = ("data", "model")
    mesh.shape = {"data": 4, "model": 2}
    cache = {"groups": {"l0": {"attn": {
        "k": jax.ShapeDtypeStruct((3, 8, 4, 64, 8), jnp.bfloat16),
        "length": jax.ShapeDtypeStruct((3,), jnp.int32)}}}}
    specs = shardlib.cache_specs(cache, mesh)
    kspec = specs["groups"]["l0"]["attn"]["k"]
    assert kspec[0] is None          # layer-stack axis never sharded
    assert kspec[1] == "data"        # batch over dp
    assert kspec[3] == "model"       # sequence model-sharded
    # B=1 long-context: sequence takes ALL axes
    cache2 = {"groups": {"l0": {"attn": {
        "k": jax.ShapeDtypeStruct((3, 1, 4, 64, 8), jnp.bfloat16)}}}}
    k2 = shardlib.cache_specs(cache2, mesh)["groups"]["l0"]["attn"]["k"]
    assert k2[3] == ("data", "model") and k2[1] is None


def test_cache_specs_shard_paged_pools():
    """Paged KV pools (no batch dim) shard the physical-page axis over all
    mesh axes; per-slot linear totals follow the batch ladder."""
    from unittest import mock
    mesh = mock.Mock()
    mesh.axis_names = ("data", "model")
    mesh.shape = {"data": 4, "model": 2}
    cache = {"groups": {"l0": {"attn": {
        "k_pages": jax.ShapeDtypeStruct((3, 64, 4, 16, 8), jnp.bfloat16),
        "v_pages": jax.ShapeDtypeStruct((3, 64, 4, 16, 8), jnp.bfloat16),
        "pooled_pages": jax.ShapeDtypeStruct((3, 64, 4, 8), jnp.float32),
        "h_tot": jax.ShapeDtypeStruct((3, 8, 4, 8, 8), jnp.float32),
    }}}}
    specs = shardlib.cache_specs(cache, mesh)["groups"]["l0"]["attn"]
    for name in ("k_pages", "v_pages", "pooled_pages"):
        assert specs[name][0] is None, name          # layer-stack axis
        assert specs[name][1] == ("data", "model"), name  # page axis
        assert all(s is None for s in specs[name][2:]), name
    assert specs["h_tot"][1] == "data"               # per-slot batch axis
    # an odd page count that no axis divides falls back to replication
    cache2 = {"k_pages": jax.ShapeDtypeStruct((7, 4, 16, 8), jnp.bfloat16)}
    k2 = shardlib.cache_specs(cache2, mesh)["k_pages"]
    assert all(s is None for s in k2)


def test_cost_and_memory_analysis_are_per_device(mesh2d):
    """Calibration for launch/roofline.py: on an SPMD module both
    cost_analysis flops and memory_analysis sizes are per-partition.
    Live at ANY device count (per-partition == total on tier-1's single
    device, a real 4-way split in the mesh harness) — this used to skip
    everywhere tier-1 ran."""
    n = len(jax.devices())
    x = jax.ShapeDtypeStruct((n * 8, 128), jnp.float32,
                             sharding=NamedSharding(mesh2d, P("data", None)))
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32,
                             sharding=NamedSharding(mesh2d, P()))
    with mesh2d:
        c = jax.jit(lambda x, w: x @ w).lower(x, w).compile()
    ca = c.cost_analysis()
    flops = (ca[0] if isinstance(ca, (list, tuple)) else ca)["flops"]
    total = 2 * (n * 8) * 128 * 128
    np.testing.assert_allclose(flops, total / n, rtol=0.01)
    arg = c.memory_analysis().argument_size_in_bytes
    per_dev = 8 * 128 * 4 + 128 * 128 * 4
    assert arg == per_dev


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[512]{0} all-reduce(%y), to_apply=%add
  %tuple = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%a, %b)
  %rs = f32[128]{0} reduce-scatter(%z), dimensions={0}
  %cp = u8[64]{0} collective-permute(%w)
  %not_a_coll = f32[8]{0} add(%p, %q)
"""
    out = parse_collectives(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 1024 * 2
    assert out["all-reduce"]["bytes"] == 512 * 4
    assert out["all-to-all"]["bytes"] == 2 * 16 * 4
    assert out["reduce-scatter"]["bytes"] == 128 * 4
    assert out["collective-permute"]["bytes"] == 64
    assert out["total_bytes"] == sum(
        out[k]["bytes"] for k in ("all-gather", "all-reduce", "all-to-all",
                                  "reduce-scatter", "collective-permute"))


# ===========================================================================
# serving placement helpers (PR 9)
# ===========================================================================

def test_serving_param_specs_strip_dp():
    """Inference weights shard the model axis only: every 'data' entry of
    the training specs is dropped, so the (N, 1) host mesh replicates."""
    from unittest import mock
    mesh = mock.Mock()
    mesh.axis_names = ("data", "model")
    mesh.shape = {"data": 4, "model": 2}
    params = {"embed": {"table": jax.ShapeDtypeStruct((100, 64),
                                                      jnp.float32)},
              "groups": {"l0": {"attn": {"wq": jax.ShapeDtypeStruct(
                  (4, 64, 128), jnp.float32)}}}}
    train = shardlib.param_specs(params, mesh)
    serve = shardlib.serving_param_specs(params, mesh)
    wq_t = train["groups"]["l0"]["attn"]["wq"]
    wq_s = serve["groups"]["l0"]["attn"]["wq"]
    assert "data" in jax.tree_util.tree_leaves(tuple(wq_t))
    flat = [a for ax in wq_s if ax is not None
            for a in (ax if isinstance(ax, tuple) else (ax,))]
    assert flat == [a for a in flat if a != "data"]
    assert "model" in flat                        # MP placement survives


def test_page_to_shard_partitioning():
    """XLA splits a sharded axis into equal contiguous blocks; the fault
    path's lost-page computation must agree with that layout."""
    assert shardlib.page_to_shard(0, 16, 4) == 0
    assert shardlib.page_to_shard(3, 16, 4) == 0
    assert shardlib.page_to_shard(4, 16, 4) == 1
    assert shardlib.page_to_shard(15, 16, 4) == 3
    counts = [sum(shardlib.page_to_shard(p, 16, 4) == s
                  for p in range(16)) for s in range(4)]
    assert counts == [4, 4, 4, 4]


def test_pool_shard_count_divisibility():
    from unittest import mock
    mesh = mock.Mock()
    mesh.axis_names = ("data", "model")
    mesh.shape = {"data": 3, "model": 1}
    assert shardlib.pool_shard_count(12, mesh) == 3
    assert shardlib.pool_shard_count(16, mesh) == 1   # replication fallback


# ===========================================================================
# fault-tolerance policy (PR 9 wires these into ServeEngine.check_faults)
# ===========================================================================

def test_heartbeat_monitor_declares_dead_after_misses():
    mon = ftlib.HeartbeatMonitor(deadline_s=1.0, misses_allowed=2)
    for h in range(3):
        mon.beat(h, now=0.0)
    assert mon.check(now=0.9) == []               # everyone inside deadline
    mon.beat(0, now=1.0)
    mon.beat(1, now=1.0)
    assert mon.check(now=1.5) == []               # host 2: miss 1
    mon.beat(0, now=2.0)
    mon.beat(1, now=2.0)
    assert mon.check(now=2.6) == [2]              # host 2: miss 2 -> dead
    # a beat resets the miss count
    mon2 = ftlib.HeartbeatMonitor(deadline_s=1.0, misses_allowed=2)
    mon2.beat(0, now=0.0)
    assert mon2.check(now=1.1) == []              # miss 1
    mon2.beat(0, now=1.2)
    assert mon2.check(now=2.0) == []              # reset, inside deadline
    assert mon2.check(now=2.4) == []              # miss 1 again, not dead


def test_straggler_policy_escalates():
    pol = ftlib.StragglerPolicy(factor=3.0, strikes=2)
    assert pol.observe(5, 1.0, ema=1.0) is None
    assert pol.observe(5, 4.0, ema=1.0) == "warn:5"
    assert pol.observe(5, 4.0, ema=1.0) == "evict:5"
    assert pol.observe(5, 1.0, ema=1.0) is None   # strike count resets


def test_elastic_plan_shrinks_dp_only():
    plan = ftlib.ElasticPlan(old_devices=4, new_devices=3)
    assert plan.reshardable
    assert plan.new_mesh_shape(model_parallel=1) == (3, 1)
    with pytest.raises(AssertionError):
        plan.new_mesh_shape(model_parallel=2)     # 3 % 2 != 0


# ===========================================================================
# int8 wire compression (live on tier-1's single device; the real 4-wide
# axis runs in the mesh harness)
# ===========================================================================

def test_int8_all_reduce_matches_bf16_baseline(mesh2d):
    from repro.distributed.compression import (bf16_all_reduce_mean,
                                               int8_all_reduce_mean)
    n = len(jax.devices())
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((n, 64, 8)), jnp.float32)
    kw = dict(mesh=mesh2d, in_specs=P("data"), out_specs=P("data"),
              check_vma=False)
    q = jax.shard_map(lambda v: int8_all_reduce_mean(v[0], "data")[None],
                      **kw)(g)
    b = jax.shard_map(lambda v: bf16_all_reduce_mean(v[0], "data")[None],
                      **kw)(g)
    # two quantisation roundings, each bounded by half an int8 step
    amax = float(jnp.max(jnp.abs(g)))
    assert float(jnp.max(jnp.abs(q - b))) <= 2.5 * amax / 127
    # the odd-size padding path round-trips exactly
    g3 = jnp.asarray(rng.standard_normal((n, 7)), jnp.float32)
    q3 = jax.shard_map(lambda v: int8_all_reduce_mean(v[0], "data")[None],
                       **kw)(g3)
    assert q3.shape == g3.shape
