"""Weights from ``--seed``, made on the device in one jitted call, in the
type they are served or trained in. The value of a leaf depends only on
(seed, leaf name, shape), so the plain reference can make any subset of
them again for itself and takes nothing from the program."""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

#: every projection, the embedding and the head: N(0, STD^2); norm scales:
#: 1 + N(0, NORM_STD^2), so a norm that is left out changes the result
STD = 0.02
NORM_STD = 0.1


def seed_key(seed):
    """A key from any whole number up to a little over 2**31 (and far
    beyond): the low 31 bits make the key, the rest is folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def name_hash(name):
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _leaf(key, h, shape, dtype, is_norm):
    x = jax.random.normal(jax.random.fold_in(key, h), shape, jnp.float32)
    x = 1.0 + NORM_STD * x if is_norm else STD * x
    return x.astype(dtype)


@functools.lru_cache(maxsize=None)
def _builder(shapes, norms, dtype, shardings):
    """One compiled program per list of shapes: the leaves' names go in as
    numbers, so every layer of a model shares its program."""
    def build(key, hashes):
        return [_leaf(key, hashes[i], s, dtype, n)
                for i, (s, n) in enumerate(zip(shapes, norms))]
    if shardings is not None:
        return jax.jit(build, out_shardings=list(shardings))
    return jax.jit(build)


def make(seed, specs, dtype=jnp.bfloat16, shardings=None):
    """``specs``: [(name, shape)]. One jitted call; returns the arrays in
    order. ``shardings`` (one per leaf) lays each leaf out as it is made,
    so no chip ever holds more than its share."""
    shapes = tuple(tuple(int(d) for d in s) for _, s in specs)
    norms = tuple(n.endswith("norm.weight") for n, _ in specs)
    hashes = jnp.asarray([name_hash(n) for n, _ in specs], jnp.int32)
    fn = _builder(shapes, norms, jnp.dtype(dtype),
                  tuple(shardings) if shardings is not None else None)
    return fn(seed_key(seed), hashes)
