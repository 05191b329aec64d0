"""The dropless expert layer (models/moe.moe_layer) and its grouped FFN
kernel (kernels/moe_experts).

- The Pallas kernel (interpreted) and its jnp path agree bit for bit.
- One device's share: across the shares of a layer, the held experts'
  parts plus the shared experts counted once add up to the uncut layer,
  which is checked against a plain dense computation of the equations.
- No served row depends on the batch: a row's output is the same whatever
  the other rows hold, and alone or in a batch.
- The counters the serving engine reads count what the layer computed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import moe_experts as K
from repro.models import layers as L
from repro.models import moe as M
from repro.models.api import build_model
from repro.serve import EngineConfig, Request, ServeEngine

D, FF, E, TOP_K = 64, 32, 8, 2


def _weights(seed, e, d=D, ff=FF, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    w_in = rng.standard_normal((e, d, 2 * ff)) / np.sqrt(d)
    w_out = rng.standard_normal((e, ff, d)) / np.sqrt(ff)
    return jnp.asarray(w_in, dtype), jnp.asarray(w_out, dtype)


@pytest.mark.parametrize("layer", [None, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes", [(5, 0, 17, 3), (0, 0, 0, 0),
                                   (40, 0, 0, 0), (1, 2, 3, 4),
                                   (0, 16, 0, 16), (0, 0, 0, 7)])
def test_moe_experts_kernel_matches_reference(sizes, dtype, layer):
    """Tiles of one expert, empty experts (first and last too), a group
    spanning several tiles and rows past the held groups (zeros) all
    agree with the jnp path, for one layer's weights and for a layer
    picked out of a stack of three."""
    w_in, w_out = _weights(0, len(sizes), dtype=dtype)
    if layer is not None:
        w_in = jnp.stack([w_in[::-1], w_in * 0.5, w_in])
        w_out = jnp.stack([w_out[::-1], w_out * 2.0, w_out])
    x = jnp.asarray(np.random.default_rng(1).standard_normal((40, D)), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = K.moe_experts(x, w_in, w_out, gs, layer, tm=16, interpret=True)
    want = K.moe_experts_reference(x, w_in, w_out, gs, layer)
    if layer is not None:
        np.testing.assert_array_equal(
            np.asarray(want, np.float32), np.asarray(
                K.moe_experts_reference(x, w_in[layer], w_out[layer], gs),
                np.float32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert not np.asarray(got[sum(sizes):], np.float32).any()


def test_tile_rows_follow_expected_rows_per_expert():
    assert K.tile_rows(32 * 6, 64) == 16        # decode: ~3 rows an expert
    assert K.tile_rows(256 * 6, 64) == 32       # a 256-token prefill chunk
    assert K.tile_rows(1 << 16, 8) == 128


def _plain_layer(params, x, cfg):
    """The equations of the uncut layer, densely: every expert on every
    row, weighted by its top-k softmax gate (0 off the top k)."""
    xt = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(xt @ params["router"], -1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    y = L.mlp(params["shared"], xt)
    ff = cfg.d_ff_expert
    for e in range(cfg.num_experts):
        g = jnp.where(ids == e, gates, 0.0).sum(-1)
        w = params["w_in"][e]
        h = jax.nn.silu(xt @ w[:, :ff]) * (xt @ w[:, ff:])
        y = y + g[:, None] * (h @ params["w_out"][e])
    return y.reshape(x.shape)


def test_shares_sum_to_the_uncut_layer():
    """Four devices holding two experts each: their parts, plus the shared
    experts once, give the uncut layer.  Tolerance 2e-5: float32 rounding
    of the same sums taken in another order (the gates are not
    renormalised, so each part is its gate times its expert's output)."""
    cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                      num_shared=2)
    params = M.init_moe(jax.random.PRNGKey(3), D, cfg)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((3, 5, D)),
                    jnp.float32)
    uncut, _ = M.moe_layer(params, x, cfg)
    np.testing.assert_allclose(np.asarray(uncut),
                               np.asarray(_plain_layer(params, x, cfg)),
                               rtol=2e-5, atol=2e-5)
    held = 2
    parts = jnp.zeros_like(x)
    total_rows = 0
    for off in range(0, E, held):
        share_cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                                held_experts=held, expert_offset=off)
        share = {"router": params["router"],
                 "w_in": params["w_in"][off:off + held],
                 "w_out": params["w_out"][off:off + held]}
        y, n = M.moe_layer(share, x, share_cfg)
        parts = parts + y
        total_rows += int(n["moe_held_rows"])
    assert total_rows == x.shape[0] * x.shape[1] * TOP_K
    shared = L.mlp(params["shared"], x)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(uncut),
                               rtol=2e-5, atol=2e-5)


def test_gates_are_not_renormalised():
    """The published DeepSeek-V2 routing: each held expert's part is its
    softmax probability times its output, the top-k gates not rescaled
    to sum to one."""
    cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                      held_experts=1, expert_offset=5)
    full = M.init_moe(jax.random.PRNGKey(5), D, M.MoEConfig(
        num_experts=E, top_k=TOP_K, d_ff_expert=FF))
    params = {"router": full["router"], "w_in": full["w_in"][5:6],
              "w_out": full["w_out"][5:6]}
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 64, D)),
                    jnp.float32)
    y, _ = M.moe_layer(params, x, cfg)
    probs = jax.nn.softmax(x[0] @ full["router"], -1)
    _, ids = jax.lax.top_k(probs, TOP_K)
    picked = (ids == 5).any(-1)
    assert picked.any() and not picked.all()
    one = K.moe_experts_reference(x[0], params["w_in"], params["w_out"],
                                  jnp.asarray([64], jnp.int32))
    want = jnp.where(picked[:, None], probs[:, 5:6] * one, 0.0)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_gates_renormalised_where_the_config_asks():
    """``norm_topk_prob`` (Llama 4's top-1): the k gates are rescaled to
    sum to one, so a top-1 expert enters with weight 1."""
    cfg = M.MoEConfig(num_experts=E, top_k=1, d_ff_expert=FF,
                      norm_topk_prob=True)
    params = M.init_moe(jax.random.PRNGKey(11), D, cfg)
    x = jnp.asarray(np.random.default_rng(12).standard_normal((1, 16, D)),
                    jnp.float32)
    y, _ = M.moe_layer(params, x, cfg)
    _, gates, ids = M.route(params, x[0], cfg)
    np.testing.assert_array_equal(np.asarray(gates), 1.0)
    want = K.moe_experts_reference(
        x[0], params["w_in"][ids[:, 0]].reshape(16, 1, D, 2 * FF)[:, 0],
        params["w_out"][ids[:, 0]], jnp.ones((16,), jnp.int32))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_layer_of_a_stack_reads_the_whole_stack():
    """A scanned layer hands ``moe_layer`` the whole stack's expert
    weights (``split_stack``) and its index: the same result, bit for
    bit, as the layer's own slice."""
    cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                      num_shared=1, held_experts=4, expert_offset=4)
    layers = [M.init_moe(jax.random.PRNGKey(20 + i), D, cfg)
              for i in range(3)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    sliced, whole = M.split_stack(stacked)
    assert set(whole) == {"w_in", "w_out"} and not set(whole) & set(sliced)
    x = jnp.asarray(np.random.default_rng(13).standard_normal((2, 8, D)),
                    jnp.float32)
    for i, own in enumerate(layers):
        lp = jax.tree.map(lambda a: a[i], sliced)
        got, n = M.moe_layer(lp, x, cfg, stack=(whole, jnp.int32(i)))
        want, n_want = M.moe_layer(own, x, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(n["moe_held_rows"]) == int(n_want["moe_held_rows"])


def test_row_output_does_not_depend_on_the_batch():
    """A row's output is bit-identical whatever the other rows of the call
    hold, and the same alone as in a batch.  Alone against batched allows
    1e-6: XLA's CPU matmuls may round a row in the last place differently
    for another number of rows (seen on the shared experts' MLP at one
    row against three); no row's routing or experts change."""
    cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                      num_shared=2, held_experts=4, expert_offset=2)
    params = M.init_moe(jax.random.PRNGKey(7), D, cfg)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((6, 4, D)), jnp.float32)
    other = x.at[1:].set(jnp.asarray(rng.standard_normal((5, 4, D)),
                                     jnp.float32))
    layer = jax.jit(lambda x: M.moe_layer(params, x, cfg)[0])
    y = layer(x)
    np.testing.assert_array_equal(np.asarray(y[0]), np.asarray(layer(other)[0]))
    np.testing.assert_allclose(np.asarray(layer(x[:1])[0]), np.asarray(y[0]),
                               rtol=1e-6, atol=1e-6)


def test_masked_rows_are_not_routed():
    """Rows outside ``rows`` (padding, idle slots) get only the shared
    experts and are not counted."""
    cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                      num_shared=2)
    params = M.init_moe(jax.random.PRNGKey(9), D, cfg)
    x = jnp.asarray(np.random.default_rng(10).standard_normal((2, 8, D)),
                    jnp.float32)
    rows = jnp.arange(8)[None, :] < jnp.asarray([[5], [0]])
    y, n = M.moe_layer(params, x, cfg, rows)
    y_all, n_all = M.moe_layer(params, x, cfg)
    assert int(n["moe_held_rows"]) == 5 * TOP_K
    assert int(n_all["moe_held_rows"]) == 16 * TOP_K
    shared = L.mlp(params["shared"], x)
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(shared[1]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(y[0, :5]),
                                  np.asarray(y_all[0, :5]))
    assert 1 <= int(n["moe_active_experts"]) <= E


def test_gshard_layer_holds_every_expert():
    cfg = M.MoEConfig(num_experts=E, top_k=TOP_K, d_ff_expert=FF,
                      held_experts=4)
    params = M.init_moe(jax.random.PRNGKey(0), D, cfg)
    with pytest.raises(ValueError, match="holds every expert"):
        M.moe_ffn(params, jnp.zeros((1, 4, D)), cfg)


@pytest.mark.parametrize("arch,expect", [("deepseek_v2_lite", True),
                                         ("qwen3_14b", False)])
def test_engine_counts_expert_rows(arch, expect):
    """``stats['moe_held_rows']`` / ``['moe_active_experts']`` come back
    with the logits of both programs; a stack without expert layers
    keeps them at zero and its programs return bare logits."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, EngineConfig(max_slots=2, max_len=128,
                                          prefill_chunk=32))
    eng.load(params)
    rng = np.random.default_rng(0)
    for uid, n in enumerate((40, 20)):
        eng.submit(Request(uid=uid, prompt=rng.integers(
            1, cfg.vocab_size, n).astype(np.int32), max_new_tokens=5))
    eng.run_to_completion()
    rows = eng.stats["moe_held_rows"]
    if not expect:
        assert rows == 0 and eng.stats["moe_active_experts"] == 0
        return
    n_moe = cfg.n_layers - len(cfg.first_kinds)
    # every prompt token and every decoded input token, k experts each
    routed = (40 + 20 + 2 * 4) * cfg.moe.top_k * n_moe
    assert rows == routed
    assert 0 < eng.stats["moe_active_experts"] <= rows
    assert not eng._pending_counters
