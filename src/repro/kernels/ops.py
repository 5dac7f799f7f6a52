"""jit'd public wrappers for the Pallas kernels: padding, alignment, fallback.

``chunked_prefill_attention`` pads C/S to block multiples and D to a
multiple of 128 (MXU lane alignment), calls the Pallas kernel, and unpads.
``use_pallas=False`` routes to the pure-jnp oracle (the XLA path used on
CPU and in the dry-run). The kernels run compiled; ``interpret=True`` is
for CPU tests that exercise them in the Pallas interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.chunked_prefill_attention import chunked_prefill_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas


def _pad_to(x, mult: int, axis: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_pos(x, mult: int):
    """Pad a positions array with -1 (invalid) instead of zeros."""
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=-1)


@functools.partial(jax.jit, static_argnames=("window", "use_pallas",
                                             "block_q", "block_k", "interpret"))
def chunked_prefill_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                              use_pallas: bool = False, block_q: int = 128,
                              block_k: int = 128, interpret: bool = False):
    if not use_pallas:
        return ref.chunked_prefill_attention_ref(q, k, v, q_pos, kv_pos, window)
    b, c, h, d = q.shape
    bq = min(block_q, max(8, c))
    bk = min(block_k, max(8, k.shape[1]))
    qp = _pad_to(q, bq, 1)
    kp_ = _pad_to(k, bk, 1)
    vp = _pad_to(v, bk, 1)
    d_pad = max(128, d + (-d) % 128) if d > 8 else d
    if d_pad != d:
        qp = _pad_to(qp, d_pad, 3)
        kp_ = _pad_to(kp_, d_pad, 3)
        vp = _pad_to(vp, d_pad, 3)
    qpos = _pad_pos(q_pos, bq)
    kvpos = _pad_pos(kv_pos, bk)
    # padded D lanes contribute zeros to q.k — but the softmax scale must use
    # the ORIGINAL head dim, so pass it explicitly.
    out = chunked_prefill_attention_pallas(
        qp, kp_, vp, qpos, kvpos, window=window, block_q=bq, block_k=bk,
        scale=d ** -0.5, interpret=interpret)
    return out[:, :c, :, :d]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                           use_pallas: bool = False, interpret: bool = False):
    if not use_pallas:
        return ref.paged_decode_attention_ref(q, k_pages, v_pages,
                                              block_tables, context_lens)
    return paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                         context_lens, interpret=interpret)
