"""The dense decoder: Qwen2 / Qwen3 (``"family": "dense"``).

Every layer is alike: RMSNorm, grouped-query causal attention with rotary
embeddings on the two halves of each head (per-head q/k RMSNorm for
Qwen3), RMSNorm and a SwiGLU MLP; then a final norm and an untied output
head. The configuration file keeps the source's own key names.

The contract this file keeps is in ``chipbench/families/__init__.py``.
``rope`` and ``attention`` serve any family whose attention is the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

from chipbench import reference as R
from chipbench import weights as W
from chipbench.families import paged


# ---------------------------------------------------------------------------
# sizes and the program's model
# ---------------------------------------------------------------------------

def dims(config: dict) -> dict:
    """The sizes the benchmark computes with, from a config file written
    with the source's own key names."""
    return {"family": config["family"],
            "d_model": config["hidden_size"],
            "n_layers": config["num_hidden_layers"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"]),
            "qk_norm": config["model_type"] == "qwen3",
            "tie_embeddings": bool(config["tie_word_embeddings"])}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a config file."""
    from repro.configs.base import ModelConfig
    d = dims(config)
    if config["torch_dtype"] != "bfloat16":
        raise ValueError("the paged path serves bfloat16 only")
    return ModelConfig(
        name=config["name"], arch_type="dense", n_layers=d["n_layers"],
        d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], head_dim=d["head_dim"],
        qk_norm=d["qk_norm"], rope_theta=d["rope_theta"],
        norm_eps=d["norm_eps"], tie_embeddings=d["tie_embeddings"],
        dtype="bfloat16")


# ---------------------------------------------------------------------------
# the program's executor: the paged one, warmed and freed as ``paged`` says
# ---------------------------------------------------------------------------

warm_phases = paged.warm_phases
device_state = paged.device_state


# ---------------------------------------------------------------------------
# weights: top-level ``embed``, ``final_norm`` and ``head``, and the
# decoder layers stacked on a leading axis under ``layers``
# ---------------------------------------------------------------------------

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


def layer_weights(key, layer, dims: dict, dtype=jnp.bfloat16) -> dict:
    """One decoder layer's weights (a traced ``layer`` index is fine)."""
    lk = jax.random.fold_in(key, layer + 1000)
    return W.nest({path: W.uniform(W.leaf_key(lk, *path), shape,
                                   W.half_width(shape), dtype)
                   for path, shape in layer_shapes(dims).items()})


def top_weights(key, dims: dict, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) output head."""
    d, v = dims["d_model"], dims["vocab_size"]
    top = {"embed": W.blocked(W.leaf_key(key, "embed"), (v, d),
                              W.EMBED_STD * 3 ** 0.5, dtype),
           "final_norm": W.uniform(W.leaf_key(key, "final_norm"), (d,),
                                   W.NORM_HALF_WIDTH, dtype)}
    if not dims["tie_embeddings"]:
        top["head"] = W.blocked(W.leaf_key(key, "head"), (d, v),
                                W.fan_in((d, v)), dtype)
    return top


def make_params(seed: int, dims: dict, device, dtype=jnp.bfloat16) -> dict:
    """The whole tree in one jitted call, placed on ``device``."""
    key = W.seed_key(seed)

    def build():
        params = top_weights(key, dims, dtype)
        params["layers"] = jax.lax.map(
            lambda l: layer_weights(key, l, dims, dtype),
            jnp.arange(dims["n_layers"]))
        return params

    return jax.jit(build, out_shardings=SingleDeviceSharding(device))()


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------

def rope(x, pos, theta):
    """x [T, H, D]; rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [T, half]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, n_valid, quant):
    """Causal GQA over one sequence. q [T, H, D]; k, v [T, Kv, D]."""
    t, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    if quant:
        q, k, v = R.q8(q, -1), R.q8(k, -1), R.q8(v, 0)
    kpos = jnp.arange(t)
    qg = q.reshape(t // R.Q_BLOCK, R.Q_BLOCK, kvh, g, dh)

    def block(args):
        qb, b = args
        qpos = b * R.Q_BLOCK + jnp.arange(R.Q_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=R.HI) * dh ** -0.5
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
        s = jnp.where(ok[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = R.q8(p, -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=R.HI)

    out = lax.map(block, (qg, jnp.arange(t // R.Q_BLOCK)))
    return out.reshape(t, h * dh)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(h, lw, n_valid, dims, quant):
    dims = dict(dims)
    eps, nh, kvh, hd = (dims["norm_eps"], dims["n_heads"],
                        dims["n_kv_heads"], dims["head_dim"])
    t = h.shape[0]
    pos = jnp.arange(t)
    a, m = lw["attn"], lw["mlp"]
    x = R.rmsnorm(h, lw["ln1"], eps)
    q = R.mm(x, a["wq"], quant).reshape(t, nh, hd)
    k = R.mm(x, a["wk"], quant).reshape(t, kvh, hd)
    v = R.mm(x, a["wv"], quant).reshape(t, kvh, hd)
    if dims["qk_norm"]:
        q = R.rmsnorm(q, a["q_norm"], eps)
        k = R.rmsnorm(k, a["k_norm"], eps)
    q, k = rope(q, pos, dims["rope_theta"]), rope(k, pos, dims["rope_theta"])
    h = h + R.mm(attention(q, k, v, n_valid, quant), a["wo"], quant)
    x = R.rmsnorm(h, lw["ln2"], eps)
    mlp = jax.nn.silu(R.mm(x, m["w_gate"], quant)) * R.mm(x, m["w_up"], quant)
    return h + R.mm(mlp, m["w_down"], quant)


def reference_layer(h, lw, n_valid, layer, dims: dict, quant: bool):
    """One decoder layer over one padded sequence h [T, d] (every layer is
    alike, so ``layer`` is not needed)."""
    return _layer(h, lw, n_valid, R.frozen(dims), quant)


# ---------------------------------------------------------------------------
# operations and bytes (``chipbench/costs.py`` says how they are counted)
# ---------------------------------------------------------------------------

def layer_matmul_params(d: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    dm, h, kv, hd, f = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                        d["head_dim"], d["d_ff"])
    return dm * h * hd + 2 * dm * kv * hd + h * hd * dm + 3 * dm * f


def matmul_params_per_token(d: dict) -> int:
    """Weights one token multiplies through, all layers."""
    return d["n_layers"] * layer_matmul_params(d)


def prefill_attn_flops(d: dict, chunk: int, ctx: int) -> int:
    """Causal attention of ``chunk`` new tokens after ``ctx`` cached ones,
    all layers: QK^T and PV over the keys each query may see."""
    keys_seen = chunk * ctx + chunk * (chunk + 1) // 2
    return d["n_layers"] * 4 * d["n_heads"] * d["head_dim"] * keys_seen


def prefill_attn_bytes(d: dict, chunk: int, ctx: int, itemsize: int = 2) -> int:
    """K and V of the whole context read once per KV head, queries read
    and outputs written once, all layers."""
    kv = (ctx + chunk) * d["n_kv_heads"] * d["head_dim"] * 2
    q_out = 2 * chunk * d["n_heads"] * d["head_dim"]
    return d["n_layers"] * (kv + q_out) * itemsize


def decode_attn_flops(d: dict, ctx: int) -> int:
    """One query over ``ctx`` keys (its own included), all layers."""
    return d["n_layers"] * 4 * d["n_heads"] * d["head_dim"] * ctx


def decode_attn_bytes(d: dict, ctx: int, itemsize: int = 2) -> int:
    kv = ctx * d["n_kv_heads"] * d["head_dim"] * 2
    q_out = 2 * d["n_heads"] * d["head_dim"]
    return d["n_layers"] * (kv + q_out) * itemsize
