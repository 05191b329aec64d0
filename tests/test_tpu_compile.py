"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's layout rules:
block shapes whose last two dims break the (8, 128) tiling, 1-D VMEM
scratch, or scalar-prefetch tables larger than SMEM.  These tests lower
each entry point with ``interpret=False`` at the widths the engines serve
(wan-dit-1.3b denoise, qwen3-14b paged serving) against a described
``v5e:2x2`` topology and compile it for one chip: the TPU compiler runs
here, no chip is needed, and nothing executes.

The topology is described inside a fixture (never at import), so only the
pytest worker that runs this file loads the TPU compiler library.
"""
import collections
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed import sharding as shardlib
from repro.kernels import sla2_decode_paged as KP
from repro.kernels.sla2_bwd import sparse_flash_bwd
from repro.kernels.sla2_fwd import sparse_flash_fwd

# wan-dit-1.3b denoise: 2 slots x 12 heads, 32k latent tokens, 128x64
# blocks, k_frac 0.05 (configs/wan_dit_1_3b.py)
DIT_BH, DIT_N, DIT_D, DIT_BQ, DIT_BK = 2 * 12, 32768, 128, 128, 64
DIT_KSEL = round(0.05 * (DIT_N // DIT_BK))
# qwen3-14b paged serving: 8 kv heads x 5 query heads, Dh 128, 64-token
# pages, 8 slots of 32k context, a 64-token prefill chunk
LM_B, LM_HKV, LM_REP, LM_DH, LM_BK = 8, 8, 5, 128, 64
LM_MAXP = 32768 // LM_BK
LM_PAGES = LM_B * LM_MAXP + 1          # + the trash page
LM_KSEL = round(0.05 * LM_MAXP)
LM_CHUNK = 64
LM_WINDOW = 4                          # speculative verify rows
POOL_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract argument placed on one chip of
    the described topology, with the persistent compile cache off while
    the module runs (an entry compiled for an absent chip cannot be read
    back, and would only warn on the next lookup)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(spec, kv_quant):
    """A qwen3-14b-width page pool and, for a low-bit pool, its scales."""
    dt = jnp.bfloat16 if kv_quant == "none" else POOL_DTYPES[kv_quant]
    pages = spec((LM_PAGES, LM_HKV, LM_BK, LM_DH), dt)
    scale = (None if kv_quant == "none"
             else spec((LM_PAGES, LM_HKV, LM_BK), jnp.float32))
    return pages, scale


@pytest.mark.parametrize("quant_bits", ["none", "int8", "fp8"])
def test_sparse_flash_fwd_compiles_at_dit_width(spec, quant_bits):
    x = spec((DIT_BH, DIT_N, DIT_D), jnp.bfloat16)
    sel = spec((DIT_BH, DIT_N // DIT_BQ, DIT_KSEL), jnp.int32)
    _compile(lambda q, k, v, i, va: sparse_flash_fwd(
        q, k, v, i, va, block_q=DIT_BQ, block_k=DIT_BK, causal=False,
        quant_bits=quant_bits, interpret=False), x, x, x, sel, sel)


@pytest.mark.parametrize("quant_bits", ["none", "int8", "fp8"])
def test_sparse_flash_fwd_compiles_with_chunked_selection(spec, quant_bits):
    """A causal training shape whose routed K/V tiles outgrow the VMEM
    budget: each query block walks its key blocks over two grid steps."""
    from repro.kernels.ops import kv_tiles_per_step
    bh, k_sel = 4, DIT_N // DIT_BK // 4
    assert kv_tiles_per_step(k_sel, DIT_BK, DIT_D, jnp.bfloat16) < k_sel
    x = spec((bh, DIT_N, DIT_D), jnp.bfloat16)
    sel = spec((bh, DIT_N // DIT_BQ, k_sel), jnp.int32)
    _compile(lambda q, k, v, i, va: sparse_flash_fwd(
        q, k, v, i, va, block_q=DIT_BQ, block_k=DIT_BK, causal=True,
        quant_bits=quant_bits, interpret=False), x, x, x, sel, sel)


def test_sparse_flash_bwd_compiles_at_dit_width(spec):
    x = spec((DIT_BH, DIT_N, DIT_D), jnp.bfloat16)
    sel = spec((DIT_BH, DIT_N // DIT_BQ, DIT_KSEL), jnp.int32)
    lse = spec((DIT_BH, DIT_N), jnp.float32)
    _compile(lambda q, k, v, i, va, o, l, do: sparse_flash_bwd(
        q, k, v, i, va, o, l, do, block_q=DIT_BQ, block_k=DIT_BK,
        causal=False, interpret=False), x, x, x, sel, sel, x, lse, x)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_paged_flash_prefill_compiles_at_qwen3_width(spec, kv_quant):
    pages, scale = _pool(spec, kv_quant)
    q = spec((LM_HKV * LM_REP, LM_CHUNK, LM_DH), jnp.bfloat16)
    row = spec((LM_MAXP,), jnp.int32)
    off = spec((), jnp.int32)
    _compile(lambda q, kp, vp, r, o, ks, vs: KP.paged_flash_prefill(
        q, kp, vp, r, offset=o, block_k=LM_BK, n_rep=LM_REP,
        kv_quant=kv_quant, k_scale=ks, v_scale=vs, interpret=False),
        q, pages, pages, row, off, scale, scale)


@pytest.mark.parametrize("window", [1, LM_WINDOW])
@pytest.mark.parametrize("quant_bits,kv_quant",
                         [("none", "none"), ("int8", "none"),
                          ("none", "int8")])
def test_sla2_decode_compiles_at_qwen3_width(spec, window, quant_bits,
                                             kv_quant):
    pages, scale = _pool(spec, kv_quant)
    w = () if window == 1 else (window,)
    q = spec((LM_B, LM_HKV, *w, LM_REP, LM_DH), jnp.bfloat16)
    sel = spec((LM_B, LM_HKV, *w, LM_KSEL), jnp.int32)
    t_new = spec((LM_B, *w), jnp.int32)
    h_tot = spec((LM_B, LM_HKV, *w, LM_DH, LM_DH), jnp.float32)
    z_tot = spec((LM_B, LM_HKV, *w, LM_DH), jnp.float32)
    alpha = spec((LM_B, LM_HKV, LM_REP), jnp.float32)
    entry = KP.sla2_decode_fused if window == 1 else KP.sla2_decode_verify
    _compile(lambda q, kp, vp, ph, jl, va, co, tn, h, z, a, ks, vs: entry(
        q, kp, vp, ph, jl, va, co, tn, h, z, a, block_k=LM_BK,
        quant_bits=quant_bits, kv_quant=kv_quant, k_scale=ks, v_scale=vs,
        interpret=False),
        q, pages, pages, sel, sel, sel, sel, t_new, h_tot, z_tot, alpha,
        scale, scale)


@pytest.mark.parametrize("window", [1, LM_WINDOW])
@pytest.mark.parametrize("quant_bits,kv_quant",
                         [("none", "none"), ("int8", "none"),
                          ("none", "int8")])
def test_dense_decode_compiles_at_qwen3_width(spec, window, quant_bits,
                                              kv_quant):
    pages, scale = _pool(spec, kv_quant)
    w = () if window == 1 else (window,)
    q = spec((LM_B, LM_HKV, *w, LM_REP, LM_DH), jnp.bfloat16)
    table = spec((LM_B, LM_MAXP), jnp.int32)
    t_new = spec((LM_B, *w), jnp.int32)
    entry = KP.dense_decode_fused if window == 1 else KP.dense_decode_verify
    _compile(lambda q, kp, vp, pt, tn, ks, vs: entry(
        q, kp, vp, pt, tn, block_k=LM_BK, quant_bits=quant_bits,
        kv_quant=kv_quant, k_scale=ks, v_scale=vs, interpret=False),
        q, pages, pages, table, t_new, scale, scale)


# bf16 matmul outputs and float all-reduces fed by a dot in compiled HLO
_MATMUL = re.compile(r"= bf16\[([0-9,]*)\]\S* convolution\(")
_DOT_ALL_REDUCE = re.compile(
    r"= (?:f32|bf16)\[[0-9,]*\]\S* all-reduce\(.*op_name=\"[^\"]*dot_general")


def _sharded_lm_steps(topo, n: int):
    """Compiled HLO of the qwen3-14b-width (one layer) prefill and decode
    steps on an (n, 1) serving mesh of the described topology, placed as
    ``ServeEngine`` places them; n == 1 is the single-chip engine."""
    from repro.models.api import build_model
    mesh = Mesh(np.asarray(topo.devices[:n]).reshape(n, 1), ("data", "model"))
    base = build_model(get_config("qwen3_14b", n_layers=1))
    model = base.with_overrides(paged_impl="fused",
                                mesh=mesh if n > 1 else None)

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)
    pshape = jax.eval_shape(base.init, jax.random.PRNGKey(0))
    params = placed(pshape, shardlib.serving_param_shardings(pshape, mesh))
    cshape = jax.eval_shape(lambda: model.init_paged_caches(4, 136, window=1))
    csh = shardlib.logical_to_shardings(shardlib.cache_specs(cshape, mesh),
                                        mesh)
    caches = placed(cshape, csh)
    rep = NamedSharding(mesh, P())

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def pinned(step):
        def fn(p, b, c):
            out, c = step(p, b, c)
            return out, jax.lax.with_sharding_constraint(c, csh)
        return jax.jit(fn)
    prefill = {"tokens": arg((1, LM_CHUNK), jnp.int32),
               "page_row": arg((33,), jnp.int32),
               "offset": arg((), jnp.int32),
               "chunk_len": arg((), jnp.int32), "slot": arg((), jnp.int32)}
    decode = {"token": arg((4,), jnp.int32),
              "page_table": arg((4, 33), jnp.int32),
              "lengths": arg((4,), jnp.int32), "active": arg((4,), jnp.bool_)}
    return {name: pinned(step).lower(params, batch, caches).compile().as_text()
            for name, step, batch in (("prefill", model.prefill_chunk, prefill),
                                      ("decode", model.decode_paged, decode))}


def test_sharded_serving_keeps_single_chip_matmuls(spec, monkeypatch, topo):
    """On a 4-chip serving mesh only the fused kernels run split.  Every
    bf16 matmul of the prefill and decode steps has as many elements as on
    one chip (no projection runs on one slot or one head group per chip),
    and no all-reduce sums partial products of a split contraction.  Either
    would change the rounding, and the sharded engine would stop matching
    the single-chip engine token for token."""
    monkeypatch.setattr(KP, "default_interpret", lambda i=None: bool(i))
    one, four = _sharded_lm_steps(topo, 1), _sharded_lm_steps(topo, 4)
    for name in ("prefill", "decode"):
        assert "tpu_custom_call" in four[name]
        sizes = [collections.Counter(
            math.prod(map(int, m.split(","))) for m in _MATMUL.findall(t))
            for t in (one[name], four[name])]
        assert sizes[0] and sizes[0] == sizes[1], (name, sizes)
        assert not _DOT_ALL_REDUCE.search(four[name]), name


# deepseek-v2-lite serving (bench/configs/deepseek_v2_lite.json): d 2048,
# 16 heads, latent 512 + 64, 8 of 64 experts held, expert width 1408,
# top-6; 32 decode slots, 256-token prefill chunks, 5,120-token slots
DS_D, DS_FF, DS_HELD, DS_TOPK, DS_EXPERTS = 2048, 1408, 8, 6, 64
DS_SLOTS, DS_CHUNK, DS_MAXP = 32, 256, 5120 // 64


@pytest.mark.parametrize("rows", [DS_SLOTS * DS_TOPK, DS_CHUNK * DS_TOPK])
def test_moe_experts_compiles_at_deepseek_width(spec, rows):
    """The grouped expert FFN for a decode step's and a prefill chunk's
    routed pairs, reading one layer's experts out of the 26-layer stack
    in HBM: two slots of a whole expert's weights (3 x 2048 x 1408 bf16)
    fit the VMEM the kernel asks for."""
    from repro.kernels import moe_experts as K
    bf = jnp.bfloat16
    _compile(lambda x, wi, wo, g, layer: K.moe_experts(
        x, wi, wo, g, layer, tm=K.tile_rows(rows, DS_EXPERTS),
        interpret=False),
        spec((rows, DS_D), bf), spec((26, DS_HELD, DS_D, 2 * DS_FF), bf),
        spec((26, DS_HELD, DS_FF, DS_D), bf), spec((DS_HELD,), jnp.int32),
        spec((), jnp.int32))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_mla_programs_compile_at_deepseek_width(spec, monkeypatch, program):
    """The MLA latent prefill-chunk and decode programs at published
    widths (the dense first layer and one expert layer), with the expert
    kernel in the program: the path the chip serves."""
    from repro.models.api import build_model
    from repro.models.moe import MoEConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("deepseek_v2_lite", n_layers=2, moe=MoEConfig(
        num_experts=DS_EXPERTS, top_k=DS_TOPK, d_ff_expert=DS_FF,
        num_shared=2, held_experts=DS_HELD))
    model = build_model(cfg)
    place = lambda t: jax.tree.map(lambda a: spec(a.shape, a.dtype), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: model.init_paged_caches(DS_SLOTS, 2 * DS_MAXP + 1)))
    i32 = lambda *s: spec(s, jnp.int32)
    if program == "prefill":
        fn, batch = model.prefill_chunk, {
            "tokens": i32(1, DS_CHUNK), "page_row": i32(DS_MAXP),
            "offset": i32(), "chunk_len": i32(), "slot": i32()}
    else:
        fn, batch = model.decode_paged, {
            "token": i32(DS_SLOTS), "page_table": i32(DS_SLOTS, DS_MAXP),
            "lengths": i32(DS_SLOTS), "active": spec((DS_SLOTS,), jnp.bool_)}
    text = _compile(fn, params, batch, caches).as_text()
    assert "moe_experts_bfloat16" in text
