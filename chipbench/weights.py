"""Model weights made from a seed, on the device, in the served dtype.

The benchmark makes the weights itself, so that its plain reference can
make the very same values again from the seed without reading anything
the program under test has made. Every leaf is drawn from a uniform
distribution with the variance of the usual fan-in initialisation, from a
key that depends only on the seed, the layer and the leaf's name. The
tree has the layout the paged executor reads: top-level ``embed``,
``final_norm`` and ``head``, and the decoder layers stacked on a leading
axis under ``layers``. Norm weights are stored as deltas from 1, which is
how the program applies them (``x * (1 + w)``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

NORM_HALF_WIDTH = 0.1      # norm deltas: uniform in [-0.1, 0.1]
EMBED_STD = 0.02


def _key(seed: int):
    """A JAX key from any whole number (seeds may exceed 32 bits)."""
    return jax.random.PRNGKey(zlib.crc32(str(int(seed)).encode()) & 0x7FFFFFFF)


def _leaf_key(key, *names):
    for n in names:
        key = jax.random.fold_in(key, zlib.crc32(str(n).encode()) & 0x7FFFFFFF)
    return key


def _uniform(key, shape, half_width: float, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -half_width,
                              half_width).astype(dtype)


def _fan_in(shape) -> float:
    return (3.0 / shape[0]) ** 0.5        # std = fan_in ** -0.5


def layer_shapes(dims: dict) -> dict:
    """Shapes of one decoder layer's leaves, keyed by their path."""
    d, h, kv, hd, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                       dims["head_dim"], dims["d_ff"])
    shapes = {("ln1",): (d,), ("ln2",): (d,),
              ("attn", "wq"): (d, h * hd), ("attn", "wk"): (d, kv * hd),
              ("attn", "wv"): (d, kv * hd), ("attn", "wo"): (h * hd, d),
              ("mlp", "w_gate"): (d, f), ("mlp", "w_up"): (d, f),
              ("mlp", "w_down"): (f, d)}
    if dims["qk_norm"]:
        shapes[("attn", "q_norm")] = (hd,)
        shapes[("attn", "k_norm")] = (hd,)
    return shapes


def _half_width(shape) -> float:
    return NORM_HALF_WIDTH if len(shape) == 1 else _fan_in(shape)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def layer_weights(key, layer, dims: dict, dtype=jnp.bfloat16) -> dict:
    """One decoder layer's weights (a traced ``layer`` index is fine)."""
    lk = jax.random.fold_in(key, layer + 1000)
    return _nest({path: _uniform(_leaf_key(lk, *path), shape,
                                 _half_width(shape), dtype)
                  for path, shape in layer_shapes(dims).items()})


def _blocked(key, shape, half_width, dtype, blocks: int = 8):
    """A large matrix made block by block along its first axis, so that
    no float32 copy of the whole matrix is ever held."""
    if shape[0] % blocks:
        blocks = 1
    rows = shape[0] // blocks

    def one(b):
        return _uniform(jax.random.fold_in(key, b), (rows,) + shape[1:],
                        half_width, dtype)
    return jax.lax.map(one, jnp.arange(blocks)).reshape(shape)


def top_weights(key, dims: dict, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) output head."""
    d, v = dims["d_model"], dims["vocab_size"]
    top = {"embed": _blocked(_leaf_key(key, "embed"), (v, d),
                             EMBED_STD * 3 ** 0.5, dtype),
           "final_norm": _uniform(_leaf_key(key, "final_norm"), (d,),
                                  NORM_HALF_WIDTH, dtype)}
    if not dims["tie_embeddings"]:
        top["head"] = _blocked(_leaf_key(key, "head"), (d, v),
                               _fan_in((d, v)), dtype)
    return top


def make_params(seed: int, dims: dict, device, dtype=jnp.bfloat16) -> dict:
    """The whole tree in one jitted call, placed on ``device``."""
    key = _key(seed)

    def build():
        params = top_weights(key, dims, dtype)
        params["layers"] = jax.lax.map(
            lambda l: layer_weights(key, l, dims, dtype),
            jnp.arange(dims["n_layers"]))
        return params

    return jax.jit(build, out_shardings=SingleDeviceSharding(device))()
