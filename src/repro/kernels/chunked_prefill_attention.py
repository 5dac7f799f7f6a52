"""Pallas TPU kernel: flash attention for a chunked-prefill step.

The CPI's hot loop (paper §4.4) is a batch mixing one prefill *chunk* with
decode tokens; the prefill chunk's attention against (cached context +
itself) dominates compute. This kernel computes that: a query chunk
``[C, H, D]`` attends to the KV cache ``[S, Kv, D]`` with causal masking by
*absolute position* (the chunk's offset into the request rides in
``q_pos``), an optional sliding window, and GQA head grouping.

TPU mapping: grid = (B, Kv, C/bq, S/bk) with the KV axis innermost
(sequential on TPU), running flash statistics (m, l, acc) in fp32 VMEM
scratch, output written on the final KV step. Query tiles fold the GQA
group dim into rows (row = c*G + g, rows = bq*G) outside the kernel, so
each KV tile is read once per KV head; D is padded to a multiple of 128 in
ops.py so the MXU matmuls are hardware-aligned. Positions arrive as VMEM
tiles: a ``(rows, 1)`` column of query positions and a ``(1, bk)`` row of
KV positions, so the mask is one broadcast compare (scalar memory only
serves scalar loads on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# contract the last dim of both operands: q [R, D] . k [bk, D] -> [R, bk]
_NT = (((1,), (1,)), ((), ()))


def _kernel(q_pos_ref, kv_pos_ref,           # [1, rows, 1] / [1, 1, bk] int32
            q_ref, k_ref, v_ref,             # VMEM tiles
            o_ref,                           # output tile
            m_ref, l_ref, acc_ref,           # fp32 scratch
            *, scale: float, window: int):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)        # [rows, D]
    k = k_ref[0, 0].astype(jnp.float32)        # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)        # [bk, D]
    s = jax.lax.dot_general(q, k, _NT) * scale  # [rows, bk]

    qp = q_pos_ref[0]                          # [rows, 1]
    kp = kv_pos_ref[0]                         # [1, bk]
    valid = (kp >= 0) & (kp <= qp)
    if window > 0:
        valid &= kp > qp - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1]                      # [rows, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _emit():
        l_fin = l_ref[:, :1]
        safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def chunked_prefill_attention_pallas(q, k, v, q_pos, kv_pos, *,
                                     window: int = 0,
                                     block_q: int = 128, block_k: int = 128,
                                     scale: float | None = None,
                                     interpret: bool = False):
    """q [B,C,H,D]; k,v [B,S,Kv,D]; q_pos [B,C]; kv_pos [B,S] -> [B,C,H,D].

    Requires C % block_q == 0 and S % block_k == 0 after clamping
    (ops.py pads inputs and unpads the result). ``interpret=True`` runs
    the kernel in the Pallas interpreter (CPU tests only).
    """
    b, c, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    # [B,Kv,C*G,D], row = c*G + g
    qg = (q.reshape(b, c, kvh, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, kvh, c * g, d))
    kt = k.transpose(0, 2, 1, 3)                              # [B,Kv,S,D]
    vt = v.transpose(0, 2, 1, 3)
    qpos = jnp.repeat(q_pos.astype(jnp.int32), g, axis=1)[:, :, None]
    kvpos = kv_pos.astype(jnp.int32)[:, None, :]

    bq = min(block_q, c)
    bk = min(block_k, s)
    assert c % bq == 0 and s % bk == 0, (c, bq, s, bk)
    grid = (b, kvh, c // bq, s // bk)
    rows = bq * g

    kernel = functools.partial(_kernel, scale=scale or d ** -0.5,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rows, 1), lambda bi, kvi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda bi, kvi, qi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, kvi, qi, ki: (bi, kvi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, kvi, qi, ki: (bi, kvi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, kvi, qi, ki: (bi, kvi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bi, kvi, qi, ki: (bi, kvi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, kvh, c * g, d), q.dtype),
        interpret=interpret,
    )(qpos, kvpos, qg, kt, vt)
    return (out.reshape(b, kvh, c, g, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, c, h, d))
