"""The plain reference: a decoder forward pass in float32 jax.numpy.

It follows the published Qwen2 / Qwen3 decoder (RMSNorm, rotary
embeddings on the two halves of each head, grouped-query causal
attention, per-head q/k RMSNorm for Qwen3, SwiGLU MLP, final norm and an
untied output head). It imports nothing from the program: it makes the
weights again from the seed (``chipbench/weights.py``), upcasts them from
the served bf16 to float32, and multiplies at ``HIGHEST`` precision. It
runs one layer at a time over every sampled sequence, so only one layer's
weights are ever held, and attention runs in blocks of queries.

``quant="fp8"`` makes the control: the same pass with every matrix
product's operands rounded to float8 e4m3 with a scale per row or column,
the precision below the configuration's bf16.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import weights as W

HI = lax.Precision.HIGHEST
Q_BLOCK = 512
E4M3_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = E4M3_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(x, w, quant):
    """x [..., k] @ w [k, n] in float32."""
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, delta, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + delta)


def _rope(x, pos, theta):
    """x [T, H, D]; rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [T, half]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, n_valid, quant):
    """Causal GQA over one sequence. q [T, H, D]; k, v [T, Kv, D]."""
    t, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    kpos = jnp.arange(t)
    qg = q.reshape(t // Q_BLOCK, Q_BLOCK, kvh, g, dh)

    def block(args):
        qb, b = args
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * dh ** -0.5
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
        s = jnp.where(ok[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = _q8(p, -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    out = lax.map(block, (qg, jnp.arange(t // Q_BLOCK)))
    return out.reshape(t, h * dh)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(h, lw, n_valid, dims, quant):
    """One decoder layer over one padded sequence h [T, d]."""
    dims = dict(dims)
    eps, nh, kvh, hd = (dims["norm_eps"], dims["n_heads"],
                        dims["n_kv_heads"], dims["head_dim"])
    t = h.shape[0]
    pos = jnp.arange(t)
    a, m = lw["attn"], lw["mlp"]
    x = _rmsnorm(h, lw["ln1"], eps)
    q = _mm(x, a["wq"], quant).reshape(t, nh, hd)
    k = _mm(x, a["wk"], quant).reshape(t, kvh, hd)
    v = _mm(x, a["wv"], quant).reshape(t, kvh, hd)
    if dims["qk_norm"]:
        q = _rmsnorm(q, a["q_norm"], eps)
        k = _rmsnorm(k, a["k_norm"], eps)
    q, k = _rope(q, pos, dims["rope_theta"]), _rope(k, pos, dims["rope_theta"])
    h = h + _mm(_attention(q, k, v, n_valid, quant), a["wo"], quant)
    x = _rmsnorm(h, lw["ln2"], eps)
    mlp = jax.nn.silu(_mm(x, m["w_gate"], quant)) * _mm(x, m["w_up"], quant)
    return h + _mm(mlp, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(h, rows, top, dims, quant):
    """Output logits at positions ``rows`` of one sequence h [T, d]."""
    dims = dict(dims)
    x = _rmsnorm(h[rows], top["final_norm"], dims["norm_eps"])
    head = top["embed"].T if dims["tie_embeddings"] else top["head"]
    return _mm(x, head, quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _key_dims(dims: dict):
    return tuple(sorted(dims.items()))


def served_logits(seed: int, dims: dict, seqs: Sequence[np.ndarray],
                  starts: Sequence[int], quant: Optional[str] = None,
                  device=None) -> List[np.ndarray]:
    """Logits at positions ``start - 1 ... len(seq) - 2`` of each
    sequence, i.e. for every served token ``seq[start:]``."""
    key = W._key(seed)
    kd = _key_dims(dims)
    q = quant == "fp8"
    with (jax.default_device(device) if device is not None
          else contextlib.nullcontext()):
        top = _f32(jax.jit(lambda: W.top_weights(key, dims))())
        hs = []
        for s in seqs:
            ids = np.zeros(_bucket(len(s), Q_BLOCK), np.int32)
            ids[:len(s)] = s
            hs.append(top["embed"][jnp.asarray(ids)])
        make_layer = jax.jit(functools.partial(W.layer_weights, dims=dims))
        for layer in range(dims["n_layers"]):
            lw = _f32(make_layer(key, layer))
            hs = [_layer(h, lw, len(s), kd, q) for h, s in zip(hs, seqs)]
            del lw
        out = []
        for h, s, st in zip(hs, seqs, starts):
            n = len(s) - st
            rows = np.full(_bucket(n, 16), len(s) - 2, np.int32)
            rows[:n] = np.arange(st - 1, len(s) - 1)
            out.append(np.asarray(_logits(h, rows, top, kd, q))[:n])
        return out


def _bucket(n: int, lo: int) -> int:
    """Power-of-two multiple of ``lo`` (few distinct compiled shapes)."""
    b = lo
    while b < n:
        b *= 2
    return b


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def compare(seed: int, dims: dict, samples: Sequence[Dict],
            control: bool = False, device=None) -> Dict:
    """Widest gap by which the tokens under test lie below the float32
    reference's best logit.

    ``samples``: dicts with ``prompt`` and ``served`` token arrays. The
    tokens under test are the served ones; with ``control=True`` they are
    the ones that the fp8 control puts first at the same positions (the
    control in the program's place), and ``served_max_gap`` still gives
    the served tokens' reading."""
    seqs = [np.concatenate([s["prompt"], s["served"]]).astype(np.int32)
            for s in samples]
    starts = [len(s["prompt"]) for s in samples]
    ref = served_logits(seed, dims, seqs, starts, device=device)
    served = np.concatenate([gaps(r, s["served"]) for r, s in
                             zip(ref, samples)])
    tested = served
    if control:
        low = served_logits(seed, dims, seqs, starts, quant="fp8",
                            device=device)
        tested = np.concatenate([gaps(r, c.argmax(-1)) for r, c in
                                 zip(ref, low)])
    out = {"max_gap": float(tested.max()), "n_tokens": int(len(tested)),
           "n_requests": len(samples),
           "mean_gap": float(tested.mean()),
           "share_exact": float(np.mean(tested == 0.0))}
    if control:
        out["served_max_gap"] = float(served.max())
    return out
