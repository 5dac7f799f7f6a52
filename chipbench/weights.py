"""Seeded draws that every architecture family makes its weights from.

The benchmark makes the weights itself, so that its plain reference can
make the very same values again from the seed without reading anything
the program under test has made. Every leaf is drawn from a uniform
distribution with the variance of the usual fan-in initialisation, from a
key that depends only on the seed, the layer and the leaf's name. Norm
weights are stored as deltas from 1, which is how the program applies
them (``x * (1 + w)``). Which leaves there are, and how the tree the
program reads is laid out, is the family's (``chipbench/families/``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

NORM_HALF_WIDTH = 0.1      # norm deltas: uniform in [-0.1, 0.1]
EMBED_STD = 0.02


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    return jax.random.PRNGKey(zlib.crc32(str(int(seed)).encode()) & 0x7FFFFFFF)


def leaf_key(key, *names):
    for n in names:
        key = jax.random.fold_in(key, zlib.crc32(str(n).encode()) & 0x7FFFFFFF)
    return key


def uniform(key, shape, half_width: float, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -half_width,
                              half_width).astype(dtype)


def fan_in(shape) -> float:
    return (3.0 / shape[0]) ** 0.5        # std = fan_in ** -0.5


def half_width(shape) -> float:
    """Norm deltas for vectors, fan-in variance for matrices."""
    return NORM_HALF_WIDTH if len(shape) == 1 else fan_in(shape)


def nest(flat: dict) -> dict:
    """A tree from leaves keyed by their path."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def blocked(key, shape, half_width, dtype, blocks: int = 8):
    """A large matrix made block by block along its first axis, so that
    no float32 copy of the whole matrix is ever held."""
    if shape[0] % blocks:
        blocks = 1
    rows = shape[0] // blocks

    def one(b):
        return uniform(jax.random.fold_in(key, b), (rows,) + shape[1:],
                       half_width, dtype)
    return jax.lax.map(one, jnp.arange(blocks)).reshape(shape)
