"""Pallas TPU kernel: decode attention over a paged KV cache.

vLLM's PagedAttention is a CUDA gather kernel; the TPU-native rethink uses
*scalar prefetch*: block tables are prefetched to SMEM, and the BlockSpec
index_map dereferences them so the DMA engine streams exactly the pages a
request owns from HBM into VMEM, ahead of compute. Grid = (B, Kv, pages)
with pages innermost (sequential), flash statistics accumulated in VMEM
scratch, output emitted on the final page.

The pool is laid out head-major, ``[P, Kv, page, D]``: one KV head of one
page is then a contiguous ``(page, D)`` tile whose last two dims are the
array's own, which is what Mosaic's block-shape rule asks for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# contract the last dim of both operands: q [G, D] . k [page, D] -> [G, page]
_NT = (((1,), (1,)), ((), ()))


def _kernel(block_tables_ref, context_lens_ref,   # scalar prefetch
            q_ref, k_ref, v_ref,                  # VMEM tiles
            o_ref,
            m_ref, l_ref, acc_ref,
            *, scale: float, page: int):
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = context_lens_ref[bi]
    # skip pages entirely beyond the context
    @pl.when(pi * page < ctx)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, _NT) * scale   # [G, page]
        pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < ctx
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]  # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(pi == np_ - 1)
    def _emit():
        l_fin = l_ref[:, :1]
        safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                  context_lens, *, interpret: bool = False):
    """q [B,H,D]; k/v_pages [P,Kv,page,D]; block_tables [B,max_pages];
    context_lens [B] -> [B,H,D]. ``interpret=True`` runs the kernel in the
    Pallas interpreter (CPU tests only)."""
    b, h, d = q.shape
    p_total, kvh, page, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)

    grid = (b, kvh, max_pages)
    kernel = functools.partial(_kernel, scale=d ** -0.5, page=page)

    def kv_index(bi, kvi, pi, bt_ref, cl_ref):
        return (bt_ref[bi, pi], kvi, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda bi, kvi, pi, *_: (bi, kvi, 0, 0)),
                pl.BlockSpec((1, 1, page, d), kv_index),
                pl.BlockSpec((1, 1, page, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, kvi, pi, *_: (bi, kvi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 128), jnp.float32),
                pltpu.VMEM((g, 128), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, qg, k_pages, v_pages)
    return out.reshape(b, h, d)
