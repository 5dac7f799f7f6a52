"""Pallas TPU kernel: decode attention over a paged KV cache.

vLLM's PagedAttention is a CUDA gather kernel; the TPU-native rethink keeps
the pool in HBM and has the kernel issue its own DMAs. Block tables and
context lengths are scalar-prefetched to SMEM. The grid is one step per
batch row, and each step serves all KV heads of that row: it walks the
row's *live* pages only (``ceil(ctx / page)`` of them), in compute blocks
of ``ppb`` pages, copying block ``i + 1`` HBM -> VMEM while it computes
block ``i`` (double-buffered, one DMA per page and pool). Table entries at
or past the live pages are never copied, and a padded lane (``ctx == 0``)
copies nothing and writes zeros. Flash statistics are kept in float32 VMEM
scratch; K/V stay in the pool's dtype.

``ppb`` follows from the shapes alone: a block holds ``BLOCK_TOKENS``
tokens (64 pages at page 16, 256 at page 4), capped at the table width.

The pool is laid out head-major, ``[P, Kv, page, D]``: one page of all KV
heads is then one contiguous ``Kv * page * D`` run, copied by one DMA into
its slot of a ``[ppb, Kv, page, D]`` VMEM block; a head's keys of the block
are read from there as ``(ppb * page, D)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 1024     # tokens per compute block
# contract the last dim of both operands: q [G, D] . k [T, D] -> [G, T]
_NT = (((1,), (1,)), ((), ()))


def _kernel(block_tables_ref, context_lens_ref,   # scalar prefetch (SMEM)
            q_ref, k_hbm, v_hbm,                  # q tile; pools in HBM
            o_ref,
            k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
            *, scale: float, page: int, ppb: int, max_pages: int):
    b = pl.program_id(0)
    kvh = k_buf.shape[2]
    tokens = ppb * page
    ctx = context_lens_ref[b]
    n_pages = jnp.minimum((ctx + page - 1) // page, max_pages)
    n_blocks = (n_pages + ppb - 1) // ppb

    def page_copies(blk, slot, j):
        """The K and V DMAs of page ``j`` of compute block ``blk``."""
        pid = block_tables_ref[b * max_pages + blk * ppb + j]
        return (pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, j],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[slot, j],
                                      sems.at[1, slot]))

    def for_live_pages(blk, slot, fn):
        live = jnp.minimum(n_pages - blk * ppb, ppb)

        def body(j, carry):
            for c in page_copies(blk, slot, j):
                fn(c)
            return carry

        jax.lax.fori_loop(0, live, body, 0)

    def start(blk, slot):
        for_live_pages(blk, slot, lambda c: c.start())

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _prime():
        start(0, 0)

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start(i + 1, 1 - slot)

        for_live_pages(i, slot, lambda c: c.wait())
        base = i * tokens
        valid = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, tokens), 1) < ctx                # [1, T]
        valid_rows = base + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, 1), 0) < ctx                # [T, 1]
        for h in range(kvh):
            q = q_ref[0, h]                                 # [G, D]
            k = k_buf[slot, :, h].reshape(tokens, -1)       # [T, D]
            # slots past ctx may hold stale or never-written data (NaN):
            # select them away, never multiply them by zero
            v = jnp.where(valid_rows, v_buf[slot, :, h].reshape(tokens, -1)
                          .astype(jnp.float32), 0.0)
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)        # [G, T]
            m_prev, l_prev = m_ref[h, :, :1], l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    l_fin = l_ref[:, :, :1]
    safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
    o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                  context_lens, *, interpret: bool = False):
    """q [B,H,D]; k/v_pages [P,Kv,page,D]; block_tables [B,max_pages];
    context_lens [B] -> [B,H,D]. ``interpret=True`` runs the kernel in the
    Pallas interpreter (CPU tests only)."""
    b, h, d = q.shape
    _, kvh, page, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    g = h // kvh
    ppb = max(1, min(BLOCK_TOKENS // page, max_pages))
    qg = q.reshape(b, kvh, g, d)

    kernel = functools.partial(_kernel, scale=d ** -0.5, page=page, ppb=ppb,
                               max_pages=max_pages)
    row = pl.BlockSpec((1, kvh, g, d), lambda bi, *_: (bi, 0, 0, 0))
    buf = pltpu.VMEM((2, ppb, kvh, page, d), k_pages.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                row,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row,
            scratch_shapes=[
                buf, buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, g, 128), jnp.float32),
                pltpu.VMEM((kvh, g, 128), jnp.float32),
                pltpu.VMEM((kvh, g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(block_tables.reshape(-1), context_lens, qg, k_pages, v_pages)
    return out.reshape(b, h, d)
