"""Pure-jnp oracles for the Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def chunked_prefill_attention_ref(q, k, v, q_pos, kv_pos, window: int = 0):
    """Flash-attention oracle for a prefill chunk against a (partial) cache.

    q:      [B, C, H, D]   chunk queries
    k, v:   [B, S, Kv, D]  KV cache contents (chunk already written)
    q_pos:  [B, C] int32   absolute positions of chunk tokens
    kv_pos: [B, S] int32   absolute positions of cache slots (-1 = empty)
    window: sliding window (0 = full causal)
    -> [B, C, H, D]
    """
    b, c, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, c, kvh, g, d).astype(jnp.float32)
    scores = jnp.einsum("bckgd,bskd->bckgs", qg, k.astype(jnp.float32)) * d ** -0.5
    valid = (kv_pos[:, None, :] >= 0) & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window > 0:
        valid &= kv_pos[:, None, :] > q_pos[:, :, None] - window
    scores = jnp.where(valid[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bckgs,bskd->bckgd", probs, v.astype(jnp.float32))
    return out.reshape(b, c, h, d).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, context_lens):
    """Decode attention over a paged KV cache.

    q:            [B, H, D]
    k/v_pages:    [P, Kv, page, D] (head-major, the Pallas kernel's layout)
    block_tables: [B, max_pages] int32 (page ids; padding entries arbitrary)
    context_lens: [B] int32
    -> [B, H, D]
    """
    b, h, d = q.shape
    p, kvh, page, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    g = h // kvh

    def gather(pages):
        """Per-request KV in token order: [B, max_pages*page, Kv, D]."""
        return (pages[block_tables].transpose(0, 1, 3, 2, 4)
                .reshape(b, max_pages * page, kvh, d))

    kk, vv = gather(k_pages), gather(v_pages)
    pos = jnp.arange(max_pages * page)[None, :]
    valid = pos < context_lens[:, None]
    qg = q.reshape(b, kvh, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, kk.astype(jnp.float32)) * d ** -0.5
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vv.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)
