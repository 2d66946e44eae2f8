"""Random weights from the run seed, made on the device in one jitted call,
in the dtype they are served in.

The tree has the structure the program's model consumes (its
``abstract_params()``: shapes and dtypes only); the values are the
benchmark's own, so the plain reference reads weights that the program
did not make.  Leaves are filled by name: RMSNorm ``scale`` ones, ``bias``
zeros, the embedding tables N(0, 0.02^2), every other matrix
N(0, 1 / fan_in) with fan_in its second-to-last dimension.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.spec import seed_words


def _leaf(key, path, sds):
    name = jax.tree_util.keystr(path[-1:])
    shape, dt = sds.shape, sds.dtype
    if "scale" in name:
        return jnp.ones(shape, dt)
    if "bias" in name:
        return jnp.zeros(shape, dt)
    if "embed" in name:
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dt)
    std = 1.0 / (shape[-2] ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def make(abstract, seed: int):
    """Weights shaped like ``abstract`` (a pytree of ShapeDtypeStructs)."""
    lo, hi = seed_words(seed)
    leaves = jax.tree_util.tree_flatten_with_path(abstract)[0]
    treedef = jax.tree_util.tree_structure(abstract)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, p, s) for k, (p, s) in zip(keys, leaves)])

    return build(jax.random.fold_in(jax.random.key(lo), hi))
