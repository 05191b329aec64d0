"""shard_map wrappers for the five fused paged Pallas entry points.

``distributed.sharding.cache_specs`` places the page pool with its page
axis split over every mesh axis; this module is the compute side of that
placement: each fused decode / verify / prefill entry gets a ``shard_map``
wrapper so every device runs the SAME kernel over its LOCAL portion of the
per-slot work, while the page table, the schedulers and all admission
bookkeeping stay global on the host (serve/engine.py never sees a device
id).

What is sharded where (``ENTRY_AXES``):

* decode / verify entries (``sla2_decode_fused``, ``sla2_decode_verify``,
  ``dense_decode_fused``, ``dense_decode_verify``) shard the SLOT axis —
  every per-slot operand (queries, routed page ids, page-table rows,
  lengths, linear totals, alpha) splits dim 0 across the mesh, so a
  device runs the whole fused kernel for its local slots only.
* ``paged_flash_prefill`` has no batch dim (one slot's chunk) — it shards
  the query-HEAD axis, and the pool's kv-head axis with it, so each
  device prefills its own GQA groups against its own kv heads.

The pool operands enter the decode wrappers replicated (``P()``): XLA
re-gathers the page shards at the shard_map boundary.  That is the price
of keeping per-slot attention math EXACTLY the arithmetic of the
single-device engine — no cross-device softmax combine, no float
reassociation, so greedy outputs stay token-identical (asserted by
tests/test_mesh_serving.py).  A production kernel would DMA only the
pages the slot's table references; the roofline treats the pool bytes as
HBM-local either way (benchmarks/fig13_mesh_scaling.py).

Only the kernel itself runs split.  Its other operands and its output,
and in the model the residual stream, the rope positions and q/k/v, are
constrained whole on every device (``replicate``).  Without that GSPMD
carries the split into the XLA code around the kernel: after the
head-split prefill the output projection sums bf16 partial products in
an all-reduce, and the decode projections run on one slot per device as
multiply + reduce instead of a matmul.  Either changes the rounding, and
at bf16 on a TPU that flips greedy tokens
(tests/test_tpu_compile.py checks the compiled matmul sizes).  At bf16,
identity also needs ``--xla_allow_excess_precision=false`` in
``XLA_FLAGS``: otherwise XLA may skip bf16 roundings inside a fusion,
and the two programs fuse differently.

Wrappers gate on divisibility at call time: when the sharded axis does
not divide the mesh size (e.g. 2 kv heads on a 4-device mesh) the bare
entry runs instead and GSPMD alone places the computation — same math,
same tokens, just without the explicit per-device kernel dispatch.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# fused entry name -> which axis its wrapper shards across the mesh.
# tools/gen_path_matrix.py probes this table for the docs/paths.md shard
# column; renaming an entry without updating it fails the docs job.
ENTRY_AXES: dict[str, str] = {
    "paged_flash_prefill": "heads",
    "dense_decode_fused": "slots",
    "dense_decode_verify": "slots",
    "sla2_decode_fused": "slots",
    "sla2_decode_verify": "slots",
}


def mesh_size(mesh: Mesh) -> int:
    """Total device count of ``mesh`` (product over all axes)."""
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def _all_axes(mesh: Mesh):
    names = tuple(mesh.axis_names)
    return names if len(names) > 1 else names[0]


def replicate(x, mesh: Mesh | None):
    """``x`` constrained whole on every device of ``mesh``; unchanged
    without a mesh.  The serving layers call this on the residual stream
    and the q/k/v projections so the split stays inside the kernels."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def _wrap_slots(fn, mesh: Mesh):
    """Slot-axis wrapper for the decode/verify entries: every positional
    operand after the two pool arrays is per-slot (dim 0 = B) and splits
    over the mesh; pools and scales stay whole per device."""
    ax = _all_axes(mesh)
    n = mesh_size(mesh)

    def wrapped(q, k_pages, v_pages, *rest, k_scale=None, v_scale=None,
                **static):
        if n <= 1 or q.shape[0] % n:
            return fn(q, k_pages, v_pages, *rest,
                      k_scale=k_scale, v_scale=v_scale, **static)
        has_k, has_v = k_scale is not None, v_scale is not None
        scales = tuple(s for s in (k_scale, v_scale) if s is not None)
        nrest = len(rest)

        def body(q_, kp, vp, *ops):
            kw = dict(static)
            sc = ops[nrest:]
            if has_k:
                kw["k_scale"] = sc[0]
            if has_v:
                kw["v_scale"] = sc[-1]
            return fn(q_, kp, vp, *ops[:nrest], **kw)

        slot = P(ax)
        in_specs = (slot, P(), P()) + (slot,) * nrest + (P(),) * len(scales)
        sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=slot, check_vma=False)
        rest = [replicate(r, mesh) for r in rest]
        out = sm(replicate(q, mesh), k_pages, v_pages, *rest, *scales)
        return replicate(out, mesh)
    return wrapped


def _wrap_prefill(fn, mesh: Mesh):
    """Head-axis wrapper for ``paged_flash_prefill``: q is (H, C, Dh) with
    heads laid out kv-major (head h belongs to kv head h // n_rep), so
    splitting H and the pool's kv-head axis the same number of ways keeps
    each device's GQA groups aligned with its local kv heads.  Requires
    Hkv to divide the mesh size; falls back to the bare entry otherwise."""
    ax = _all_axes(mesh)
    n = mesh_size(mesh)

    def wrapped(q, k_pages, v_pages, page_row, *, offset,
                k_scale=None, v_scale=None, **static):
        hkv = k_pages.shape[1]
        if n <= 1 or hkv % n:
            return fn(q, k_pages, v_pages, page_row, offset=offset,
                      k_scale=k_scale, v_scale=v_scale, **static)
        has_k, has_v = k_scale is not None, v_scale is not None
        scales = tuple(s for s in (k_scale, v_scale) if s is not None)

        def body(q_, kp, vp, row, off, *sc):
            kw = dict(static)
            if has_k:
                kw["k_scale"] = sc[0]
            if has_v:
                kw["v_scale"] = sc[-1]
            return fn(q_, kp, vp, row, offset=off, **kw)

        heads = P(ax, None, None)
        pool = P(None, ax, None, None)
        in_specs = (heads, pool, pool, P(), P()) \
            + (P(None, ax, None),) * len(scales)
        sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=heads, check_vma=False)
        out = sm(replicate(q, mesh), k_pages, v_pages, page_row, offset,
                 *scales)
        return replicate(out, mesh)
    return wrapped


def wrap_entry(name: str, fn, mesh: Mesh):
    """The shard_map wrapper for fused entry ``name`` on ``mesh`` — the
    single composition point ``models/attention`` uses when an
    ``AttentionConfig.mesh`` is set.  Unknown names raise (the dispatch
    table and this module must agree)."""
    kind = ENTRY_AXES[name]
    return _wrap_prefill(fn, mesh) if kind == "heads" \
        else _wrap_slots(fn, mesh)
