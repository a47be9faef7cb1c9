"""Seeded weights, drawn by the benchmark and never by the program.

Every leaf is named by its '/'-joined path in the program's parameter tree
and drawn from ``fold_in(seed key, crc32(tree), crc32(path))``.  A stacked
leaf (one slice per layer or block, named by the configuration's
``init.stacked`` patterns) draws slice ``i`` from ``fold_in(leaf key, i)``,
so the reference can draw one layer alone and get the same numbers.  The
first rule of ``init.rules`` whose pattern matches the path sets the leaf:
``ones``, ``zeros``, ``normal`` with the given std, or ``fan_in``: a normal
of std ``scale / sqrt(rows)``, rows being the input width.
"""
from __future__ import annotations

import math
import re
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, including ones beyond 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _crc(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _rule(init: dict, path: str) -> tuple[str, float]:
    for pattern, kind, *scale in init["rules"]:
        if re.search(pattern, path):
            return kind, (scale[0] if scale else 1.0)
    raise KeyError(f"no init rule matches {path!r}")


def is_stacked(init: dict, path: str) -> bool:
    return any(re.search(p, path) for p in init["stacked"])


def _draw(key, kind: str, scale: float, shape) -> jax.Array:
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "fan_in":
        scale = scale / math.sqrt(shape[-2])
    elif kind != "normal":
        raise ValueError(f"unknown init kind {kind!r}")
    return jax.random.normal(key, shape, jnp.float32) * scale


def leaf_key(key, tree: str, path: str) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, _crc(tree)), _crc(path))


def leaf(key, tree: str, path: str, shape, init: dict) -> jax.Array:
    """The whole leaf, in float32."""
    k = leaf_key(key, tree, path)
    kind, scale = _rule(init, path)
    if is_stacked(init, path):
        return jax.vmap(lambda i: _draw(jax.random.fold_in(k, i), kind,
                                        scale, shape[1:]))(
            jnp.arange(shape[0]))
    return _draw(k, kind, scale, shape)


def layer_slice(key, tree: str, path: str, shape, init: dict,
                index: int) -> jax.Array:
    """Slice ``index`` of a stacked leaf of full ``shape``, in float32."""
    kind, scale = _rule(init, path)
    k = jax.random.fold_in(leaf_key(key, tree, path), index)
    return _draw(k, kind, scale, shape[1:])


def path_of(keypath) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in keypath)


def make_tree(key, tree: str, shapes, init: dict, dtype=None):
    """Draw every leaf of ``shapes`` (a pytree of ShapeDtypeStructs); cast
    to ``dtype`` where given, else to each leaf's own dtype."""
    def one(kp, s):
        x = leaf(key, tree, path_of(kp), s.shape, init)
        return x.astype(dtype or s.dtype)
    return jax.tree_util.tree_map_with_path(one, shapes)
