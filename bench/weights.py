"""Weights from a seed, made on the device in one jitted call, in the
program's parameter layout and in the type they are used in.

The benchmark makes the weights, not the program: the program and the plain
reference are given the same arrays.  Each leaf is drawn from the seed and
its path, so the values do not depend on the order of the leaves.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from common import seed_words


def _leaf(key, path: str, shape, d_model: int, dtype):
    name = path.rsplit("/", 2)
    if path.endswith("/scale"):                       # RMSNorm gains
        return jnp.ones(shape, dtype)
    if path.endswith("/b"):                           # projection biases
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name[-2] in ("embed", "unembed"):              # (padded vocab, d)
        std = d_model ** -0.5
    else:                                             # (..., fan_in, fan_out)
        std = shape[-2] ** -0.5
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]
    return paths, [leaf for _, leaf in flat], treedef


def builder(abstract, dtype: str, d_model: int):
    """``fn(lo, hi)`` that draws arrays shaped like ``abstract`` (a pytree of
    ShapeDtypeStructs) from the seed words ``lo``, ``hi``; float leaves in
    ``dtype``.  Traceable, so a jitted check can draw them again."""
    paths, leaves, treedef = _paths(abstract)
    dt = jnp.dtype(dtype)

    def build(lo, hi):
        base = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
        out = []
        for path, leaf in zip(paths, leaves):
            key = jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            out.append(_leaf(key, path, leaf.shape, d_model, dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def seed_args(seed: int):
    lo, hi = seed_words(seed)
    return jnp.uint32(lo), jnp.uint32(hi)


def leaf_paths(tree) -> list[str]:
    return _paths(tree)[0]
