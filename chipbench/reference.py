"""The plain reference: a decoder forward pass in float32 jax.numpy.

The embedding, the loop over layers, the final norm and the output head
are here; each layer is the architecture family's
(``reference_layer`` of ``chipbench/families/<family>.py``, which follows
the published description of its model). It imports nothing from the
program: it makes the weights again from the seed through the family,
upcasts them from the served bf16 to float32, and multiplies at
``HIGHEST`` precision. It runs one layer at a time over every sampled
sequence, so only one layer's weights are ever held, and attention runs
in blocks of ``Q_BLOCK`` queries.

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

from chipbench import families
from chipbench import weights as W

HI = lax.Precision.HIGHEST
Q_BLOCK = 512
E4M3_MAX = 448.0


def q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = E4M3_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(x, w, quant):
    """x [..., k] @ w [k, n] in float32."""
    if quant:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, delta, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + delta)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(h, rows, top, dims, quant):
    """Output logits at positions ``rows`` of one sequence h [T, d]."""
    dims = dict(dims)
    x = rmsnorm(h[rows], top["final_norm"], dims["norm_eps"])
    head = top["embed"].T if dims["tie_embeddings"] else top["head"]
    return mm(x, head, quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def frozen(dims: dict) -> tuple:
    """``dims`` as a static argument of a jitted function (``dict()`` of it
    gives the dict back)."""
    return tuple(sorted(dims.items()))


def served_logits(seed: int, dims: dict, seqs: Sequence[np.ndarray],
                  starts: Sequence[int], quant: Optional[str] = None,
                  device=None) -> List[np.ndarray]:
    """Logits at positions ``start - 1 ... len(seq) - 2`` of each
    sequence, i.e. for every served token ``seq[start:]``."""
    fam = families.of(dims)
    key = W.seed_key(seed)
    kd = frozen(dims)
    q = quant == "fp8"
    with (jax.default_device(device) if device is not None
          else contextlib.nullcontext()):
        top = _f32(jax.jit(lambda: fam.top_weights(key, dims))())
        hs = []
        for s in seqs:
            ids = np.zeros(_bucket(len(s), Q_BLOCK), np.int32)
            ids[:len(s)] = s
            hs.append(top["embed"][jnp.asarray(ids)])
        make_layer = jax.jit(functools.partial(fam.layer_weights, dims=dims))
        for layer in range(dims["n_layers"]):
            lw = _f32(make_layer(key, layer))
            hs = [fam.reference_layer(h, lw, len(s), layer, dims, q)
                  for h, s in zip(hs, seqs)]
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
