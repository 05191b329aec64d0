"""Named sharding rules: param/opt/cache PartitionSpecs by key-path.

Mesh axes:
    single-pod : ("data", "model")            = (16, 16)
    multi-pod  : ("pod", "data", "model")     = (2, 16, 16)

``pod`` folds into the data-parallel group: batch and FSDP shards span
(pod, data); tensor/expert/sequence parallelism stays pod-local on "model"
(gradient all-reduce is the only pod-crossing collective — see DESIGN §5).

Rules are (regex over the '/'-joined key path, spec for the TRAILING dims).
The spec is right-aligned against the leaf's shape, leading dims (e.g. the
scanned layer axis) padded with None — so one rule covers both stacked and
unstacked variants of a layer.  First match wins; no match => replicated.

The resulting tree feeds ``jax.jit(in_shardings=...)`` and
``jax.lax.with_sharding_constraint`` — GSPMD then materialises the
all-gather / reduce-scatter / all-to-all schedule the roofline analysis
reads back out of the compiled HLO.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, trailing-dims spec). "DP" is replaced by the folded
# data-parallel axes tuple at rule-application time.
PARAM_RULES: list[tuple[str, tuple]] = [
    # --- embeddings / unembedding ---
    (r"embed/table$",            ("model", "DP")),
    (r"lm_head$",                ("DP", "model")),
    (r"pos_dec$",                (None, None)),
    # --- attention projections ---
    (r"attn/w[qkv]$",            ("DP", "model")),
    (r"attn/wo$",                ("model", "DP")),
    (r"self_attn/w[qkv]$",       ("DP", "model")),
    (r"self_attn/wo$",           ("model", "DP")),
    (r"cross/w[qkv]$",           ("DP", "model")),
    (r"cross/wo$",               ("model", "DP")),
    # --- DiT blocks ---
    (r"blocks/w[qkv]$",          ("DP", "model")),
    (r"blocks/wo$",              ("model", "DP")),
    (r"blocks/x[qkv]$",          ("DP", "model")),
    (r"blocks/xo$",              ("model", "DP")),
    (r"ada/w$",                  (None, "model")),
    (r"patch_(in|out)/w$",       (None, None)),
    # --- SLA2 (router projections are tiny; alpha heads over model) ---
    (r"sla2/router/proj_[qk]$",  (None, None)),
    (r"sla2/alpha_logit$",       ("model", None)),
    (r"sla/proj_l$",             (None, None)),
    # --- MLA ---
    (r"mla/w_dkv$",              ("DP", None)),
    (r"mla/w_q$",                ("DP", "model")),
    (r"mla/w_uq$",               (None, "model")),
    (r"mla/w_dq$",               ("DP", None)),
    (r"mla/w_uk$",               (None, "model")),
    (r"mla/w_uv$",               (None, "model")),
    (r"mla/w_o$",                ("model", "DP")),
    # --- dense MLP ---
    (r"mlp/w_(up|gate)$",        ("DP", "model")),
    (r"mlp/w_down$",             ("model", "DP")),
    # --- MoE: experts over model (EP), FSDP inside each expert ---
    (r"moe/router$",             (None, None)),
    (r"moe/w_in$",               ("model", "DP", None)),
    (r"moe/w_out$",              ("model", None, "DP")),
    (r"moe/shared/w_(up|gate)$", ("DP", "model")),
    (r"moe/shared/w_down$",      ("model", "DP")),
    # --- SSM / hybrid mixers ---
    (r"(ssm|core)/w_(x|gate|b|c)$",  ("DP", "model")),
    (r"(ssm|core)/w_(q|k|v)$",       ("DP", "model")),
    (r"(ssm|core)/w_out$",           ("model", "DP")),
    (r"(ssm|core)/w_in$",            ("DP", "model")),
    (r"(ssm|core)/w_(dt|i|f)$",      ("DP", None)),
    (r"core/r$",                     (None, None, None)),
    # norms / scalars / biases: replicated (fall-through default)
]


def dp_axes(mesh: Mesh):
    """The folded data-parallel axes: ('pod', 'data') or ('data',)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _materialize(spec: Sequence, ndim: int, mesh: Mesh) -> P:
    dp = dp_axes(mesh)
    dp_ax = dp if len(dp) > 1 else (dp[0] if dp else None)
    out = [dp_ax if s == "DP" else s for s in spec]
    # right-align: pad leading dims (layer-stack axes) with None
    return P(*([None] * (ndim - len(out)) + out))


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _fit_to_shape(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on axes the dimension size cannot divide evenly
    (e.g. 6 heads over a 16-way model axis: replicate instead)."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        out.append(ax if dim % _axis_size(mesh, ax) == 0 else None)
    return P(*out)


def spec_for_path(path: str, ndim: int, mesh: Mesh, shape=None) -> P:
    """PartitionSpec for one leaf: first PARAM_RULES regex that matches the
    '/'-joined ``path`` wins, right-aligned against ``ndim`` dims; pass
    ``shape`` to drop axes the dim size cannot divide (replication
    fallback).  No match => fully replicated."""
    for pat, spec in PARAM_RULES:
        if re.search(pat, path):
            if len(spec) > ndim:   # scalar-ish leaf, rule too wide
                return P()
            full = _materialize(spec, ndim, mesh)
            return _fit_to_shape(full, shape, mesh) if shape else full
    return P(*([None] * ndim)) if ndim else P()


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(params_shape, mesh: Mesh):
    """PartitionSpec tree for a params (or shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_path(_path_str(path), len(leaf.shape),
                                         mesh, leaf.shape),
        params_shape)


def param_shardings(params_shape, mesh: Mesh):
    """``param_specs`` materialised as a NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def _strip_dp(ax, dp: frozenset):
    if ax is None:
        return None
    if isinstance(ax, tuple):
        kept = tuple(a for a in ax if a not in dp)
        return kept[0] if len(kept) == 1 else (kept or None)
    return None if ax in dp else ax


def serving_param_specs(params_shape, mesh: Mesh):
    """Inference weight placement: ``param_specs`` with the data-parallel
    axes dropped, so only tensor-parallel ('model') dims stay sharded.

    Decode is latency-bound — FSDP-sharded contracting dims would force a
    per-step all-gather (or a DP psum whose float reassociation breaks the
    bit-identity guarantee vs the single-device engine), while the 'data'
    axis already earns its keep sharding slots and the page pool.  On a
    (N, 1) host mesh this replicates the weights outright."""
    dp = frozenset(dp_axes(mesh))
    return jax.tree.map(
        lambda s: P(*(_strip_dp(ax, dp) for ax in s)),
        param_specs(params_shape, mesh),
        is_leaf=lambda x: isinstance(x, P))


def serving_param_shardings(params_shape, mesh: Mesh):
    """``serving_param_specs`` as a NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        serving_param_specs(params_shape, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def opt_specs(opt_shape, mesh: Mesh):
    """Optimizer state mirrors the params tree under m/ and v/."""
    def one(path, leaf):
        ps = _path_str(path)
        ps = re.sub(r"^(m|v)/", "", ps)
        return spec_for_path(ps, len(leaf.shape), mesh, leaf.shape)
    return jax.tree_util.tree_map_with_path(one, opt_shape)


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------

def batch_specs(batch_shape, mesh: Mesh, *, pure_dp: bool = False):
    """Training/prefill batch sharding ladder, per leaf:

    1. pure_dp (tiny replicated models, e.g. whisper): batch over ALL mesh
       axes if it divides — the model axis has no TP work to do.
    2. batch over the folded DP axes if it divides.
    3. fall back to sharding dim 1 (sequence) over DP — covers small-batch
       long-sequence cells like denoise_32k (B=8 on a 16-wide data axis).
    4. replicate.
    """
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    dp_ax = dp if len(dp) > 1 else dp[0]
    all_ax = tuple(mesh.axis_names)
    all_size = int(np.prod([mesh.shape[a] for a in all_ax]))

    def one(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        if pure_dp and leaf.shape[0] % all_size == 0:
            return P(*([all_ax] + [None] * (nd - 1)))
        if leaf.shape[0] % dp_size == 0:
            return P(*([dp_ax] + [None] * (nd - 1)))
        if nd >= 2 and leaf.shape[1] % dp_size == 0 and leaf.shape[1] > 1:
            return P(*([None, dp_ax] + [None] * (nd - 2)))
        return P(*([None] * nd))
    return jax.tree.map(one, batch_shape)


def cache_specs(cache_shape, mesh: Mesh):
    """Decode caches.  Big sequence-length tensors (KV blocks, pooled keys)
    are sequence-sharded flash-decoding style; when the batch does not cover
    the DP axes (long_500k has B=1) the sequence takes ALL mesh axes.
    Paged pools (k_pages/v_pages/pooled_pages/pooled_lat, no batch dim)
    shard their page axis over ALL mesh axes — the paged analogue of
    sequence sharding: the page table is replicated host state and a
    slot's logical blocks scatter across devices like flash-decoding
    splits."""
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    dp_ax = dp if len(dp) > 1 else dp[0]
    all_ax = tuple(mesh.axis_names)

    def one(path, leaf):
        nd = len(leaf.shape)
        name = _path_str(path)
        if nd == 0:
            return P()
        # caches under a scanned stack carry a leading layer axis
        off = 1 if name.split("/")[0] in ("groups", "decoder", "encoder") \
            else 0
        if nd <= off:
            return P(*([None] * nd))
        # paged pools: leading (post-stack) axis is the physical page id
        if re.search(r"/(k_pages|v_pages)$", name) and nd - off == 4 \
                or re.search(r"/pooled_pages$", name) and nd - off == 3 \
                or re.search(r"/pooled_lat$", name) and nd - off == 2:
            spec = [None] * nd
            spec[off] = all_ax
            return _fit_to_shape(P(*spec), leaf.shape, mesh)
        batch_ok = leaf.shape[off] % dp_size == 0
        # sequence-carrying cache tensors (shapes AFTER the stack offset):
        #   k/v/pooled_k : (B, H, S, D);  k_lat : (B, S, D)
        #   enc_k/enc_v  : (B, H, S, D)
        seq_axis = None
        if re.search(r"/(k|v|pooled_k|enc_k|enc_v)$", name) \
                and nd - off == 4:
            seq_axis = off + 2
        elif re.search(r"/k_lat$", name) and nd - off == 3:
            seq_axis = off + 1
        spec = [None] * nd
        if seq_axis is not None:
            if batch_ok:
                spec[off] = dp_ax
                spec[seq_axis] = "model"
            else:
                spec[seq_axis] = all_ax   # B=1: all 512 ways over sequence
            return _fit_to_shape(P(*spec), leaf.shape, mesh)
        # states / totals: batch over DP when possible, else replicate
        if batch_ok:
            spec[off] = dp_ax
        return _fit_to_shape(P(*spec), leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def logical_to_shardings(spec_tree, mesh: Mesh):
    """Materialise a PartitionSpec tree as NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# page → shard bookkeeping (host side)
# ---------------------------------------------------------------------------

def pool_shard_count(num_pages: int, mesh: Mesh) -> int:
    """How many ways ``cache_specs`` actually splits the page axis: the
    full mesh size when it divides ``num_pages`` evenly, else 1 (the
    ``_fit_to_shape`` replication fallback kicked in)."""
    n = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    return n if n > 0 and num_pages % n == 0 else 1


def page_to_shard(page: int, num_pages: int, n_shards: int) -> int:
    """Which pool shard owns physical page id ``page``.  XLA partitions a
    sharded dim into equal contiguous blocks, so shard i owns pages
    [i*num_pages/n_shards, (i+1)*num_pages/n_shards).  The engine's fault
    path uses this to decide which slots lost state with a dead host."""
    assert n_shards > 0 and num_pages % n_shards == 0
    return int(page) // (num_pages // n_shards)
