"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the shapes come from
the program's parameter tree (``jax.eval_shape`` of its init), the
values from ``--seed`` by one rule per leaf name, so the program's own
initialiser (adaLN-zero: gates and output projection at zero) cannot
make a comparison vacuous.  The plain references read the same arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (PRNGKey keeps 32 bits only)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf(name: str, key, shape, dtype):
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":                      # norm gains
        x = 1.0 + 0.1 * normal
    elif name in ("b", "bias"):              # biases
        x = 0.02 * normal
    elif name == "alpha_logit":              # SLA2 combine ratio in (0.5, 0.95)
        a = jax.random.uniform(key, shape, jnp.float32, 0.5, 0.95)
        x = jnp.log(a / (1.0 - a))
    elif name in ("proj_q", "proj_k"):       # router projections
        x = jnp.eye(shape[-1], dtype=jnp.float32) + 0.1 * normal / math.sqrt(
            shape[-1])
    elif name == "table":                    # embedding rows
        x = normal
    else:                                    # matrices: std fan_in ** -0.5
        x = normal / math.sqrt(shape[-2])
    return x.astype(dtype)


def make(init_fn, seed: int):
    """Weights with the tree, shapes and dtypes of ``init_fn(key)``."""
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, s) in zip(keys, flat):
            last = path[-1]
            name = getattr(last, "key", getattr(last, "name", str(last)))
            leaves.append(_leaf(str(name), k, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(build)(seed_key(seed))
