"""Grouped gated FFN of the experts one device holds (``moe_experts``).

Rows arrive sorted by expert: the first ``group_sizes[0]`` rows go to held
expert 0, the next ``group_sizes[1]`` to expert 1, and so on; rows past
``sum(group_sizes)`` belong to no held expert and come back as zeros.
Each expert's rows run through its gated FFN

    y = (silu(x W_gate) * (x W_up)) W_down      W_gate | W_up = w_in[e]

with f32 accumulation and the hidden activation rounded to the weights'
dtype before the down projection.

The Pallas kernel pads each expert's rows to whole tiles of ``tm`` rows,
so every tile belongs to one expert, and runs one grid step per tile, in
order.  The weights stay in HBM: ``w_in`` / ``w_out`` may be a stack of
layers, (L, E, ...), with ``layer`` picking one, so a layer scan hands the
kernel the whole stack and no per-layer slice is copied out of it.  The
kernel copies each active expert's weights (gate | up and down, 3 x d x
ff) into one of two VMEM slots itself: the first tile of an expert starts
the copy of the next active expert into the other slot, then waits on its
own, so two experts' weights are in flight while the rows compute.
Scalar-prefetched tables give each tile's expert, the next active expert
and the slot.  Empty experts copy nothing and tiles past the last used
one do nothing.  This is the megablox pattern (``jax.experimental.
pallas.ops.tpu.megablox``: group offsets by scalar prefetch, empty tiles
skipped) with the three matmuls of the gated FFN fused into one step.
Decode routes a few rows to each expert, so every active expert's weights
are read once a call and HBM bandwidth is the bound; all of that reading
happens inside the kernel.

``moe_experts_reference`` is the jnp path (the CPU, tests): each row
multiplies its own expert's weights, so a row's result does not depend on
how many other rows the call holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

F32 = jnp.float32


def tile_rows(n_rows: int, num_experts: int) -> int:
    """Rows per tile: the expected rows of one expert (``n_rows`` routed
    pairs spread over ``num_experts``), as a power of two in [16, 128]
    (16 is bf16's sublane tile)."""
    per = max(1, -(-n_rows // num_experts))
    return min(128, max(16, 1 << (per - 1).bit_length()))


def _plan(group_sizes, n_rows: int, tm: int):
    """The tile layout of ``n_rows`` sorted rows.  Returns per tile its
    expert, the next active expert after its own (``E``: none) and a flag
    word (bit 0 the weight slot, bit 1 set on an expert's first tile);
    the number of used tiles; each padded row's source row (and whether
    it has one); and each sorted row's padded position."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_used = tile_end[-1]
    n_tiles = -(-n_rows // tm) + e
    j = jnp.arange(n_tiles, dtype=jnp.int32)
    experts = jnp.arange(e, dtype=jnp.int32)
    tile_e = jnp.minimum(
        jnp.searchsorted(tile_end, j, side="right").astype(jnp.int32), e - 1)
    active = tiles > 0
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1     # among active ones
    in_order = jnp.concatenate([jnp.sort(jnp.where(active, experts, e)),
                                jnp.full((1,), e, jnp.int32)])
    tile_next = in_order[rank[tile_e] + 1]
    first = (j < n_used) & ((j == 0) | (tile_e != jnp.roll(tile_e, 1)))
    flags = (rank[tile_e] % 2) | (first.astype(jnp.int32) << 1)
    row_end = jnp.cumsum(sizes)
    row_start = row_end - sizes
    pad_start = (tile_end - tiles) * tm
    p = jnp.arange(n_tiles * tm, dtype=jnp.int32)
    pe = tile_e[p // tm]
    rank_in = p - pad_start[pe]
    has_src = (p // tm < n_used) & (rank_in < sizes[pe])
    src = jnp.where(has_src, row_start[pe] + rank_in, 0)
    i = jnp.arange(n_rows, dtype=jnp.int32)
    ie = jnp.minimum(
        jnp.searchsorted(row_end, i, side="right").astype(jnp.int32), e - 1)
    dst = pad_start[ie] + i - row_start[ie]
    held = i < row_end[-1]
    return ((tile_e, tile_next, flags), n_used, src, has_src,
            jnp.where(held, dst, 0), held)


def _kernel(expert_ref, next_ref, flags_ref, info_ref, x_ref, w_in_hbm,
            w_out_hbm, o_ref, w_in_buf, w_out_buf, sems, *, num_experts,
            ff):
    j = pl.program_id(0)
    layer = info_ref[1]

    def copies(e, slot):
        return (pltpu.make_async_copy(w_in_hbm.at[layer, e],
                                      w_in_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(w_out_hbm.at[layer, e],
                                      w_out_buf.at[slot], sems.at[1, slot]))

    @pl.when(j < info_ref[0])
    def _():
        e, flags = expert_ref[j], flags_ref[j]
        slot = flags & 1

        @pl.when(flags >= 2)            # the expert's first tile
        def _():
            @pl.when(j == 0)
            def _():
                for cp in copies(e, slot):
                    cp.start()
            nxt = next_ref[j]

            @pl.when(nxt < num_experts)
            def _():
                for cp in copies(nxt, 1 - slot):
                    cp.start()
            for cp in copies(e, slot):
                cp.wait()

        x = x_ref[...]
        g = jnp.dot(x, w_in_buf[slot, :, :ff], preferred_element_type=F32)
        u = jnp.dot(x, w_in_buf[slot, :, ff:], preferred_element_type=F32)
        a = (jax.nn.silu(g) * u).astype(w_out_buf.dtype)
        o_ref[...] = jnp.dot(a, w_out_buf[slot],
                             preferred_element_type=F32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts(x, w_in, w_out, group_sizes, layer=None, *, tm: int,
                interpret: bool | None = None):
    """x (N, d) rows sorted by held expert; w_in (E, d, 2 ff) gate | up
    and w_out (E, ff, d), or stacks of them (L, E, ...) with ``layer``
    (a scalar) picking one; group_sizes (E,) int32.  Returns (N, d) in
    x's dtype, zeros past ``sum(group_sizes)``."""
    interpret = default_interpret(interpret)
    if w_in.ndim == 3:
        w_in, w_out, layer = w_in[None], w_out[None], 0
    n, d = x.shape
    _, e, ff, _ = w_out.shape
    tables, n_used, src, has_src, dst, held = _plan(group_sizes, n, tm)
    n_tiles = tables[0].shape[0]
    info = jnp.stack([n_used, jnp.asarray(layer, jnp.int32)])
    x_pad = jnp.where(has_src[:, None], x[src], 0).astype(x.dtype)
    wbytes = jnp.dtype(w_in.dtype).itemsize
    x_bytes = jnp.dtype(x.dtype).itemsize
    # two slots of one expert's weights, the row tiles double-buffered
    vmem = 2 * (3 * d * ff * wbytes + 2 * tm * d * x_bytes) + (8 << 20)
    rows = lambda j, *_: (j, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tm, d), rows),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((tm, d), rows),
        scratch_shapes=[pltpu.VMEM((2, d, 2 * ff), w_in.dtype),
                        pltpu.VMEM((2, ff, d), w_out.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, num_experts=e, ff=ff),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * tm, d), x.dtype),
        # a tile starts the next expert's copies: walk the tiles in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name=f"moe_experts_{jnp.dtype(w_in.dtype).name}",
    )(*tables, info, x_pad, w_in, w_out)
    return jnp.where(held[:, None], out[dst], 0).astype(x.dtype)


def moe_experts_reference(x, w_in, w_out, group_sizes, layer=None):
    """The jnp twin of ``moe_experts``: each row through its own expert's
    weights, gathered per row."""
    if layer is not None:
        w_in, w_out = w_in[layer], w_out[layer]
    n = x.shape[0]
    e, ff, _ = w_out.shape
    row_end = jnp.cumsum(group_sizes.astype(jnp.int32))
    i = jnp.arange(n, dtype=jnp.int32)
    ex = jnp.minimum(jnp.searchsorted(row_end, i, side="right"), e - 1)
    w = w_in[ex]                                         # (N, d, 2 ff)
    g = jnp.einsum("nd,ndf->nf", x, w[..., :ff], preferred_element_type=F32)
    u = jnp.einsum("nd,ndf->nf", x, w[..., ff:], preferred_element_type=F32)
    a = (jax.nn.silu(g) * u).astype(w_out.dtype)
    y = jnp.einsum("nf,nfd->nd", a, w_out[ex], preferred_element_type=F32)
    return jnp.where((i < row_end[-1])[:, None], y, 0).astype(x.dtype)
