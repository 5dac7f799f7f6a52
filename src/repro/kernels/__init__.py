"""Attention kernels for the real execution paths.

Public API (what :class:`repro.core.executor.PagedRealExecutor` and the
model stacks call):

``ops.chunked_prefill_attention(q, k, v, q_pos, kv_pos, *, window=0,
use_pallas=False)``
    Chunked-prefill attention over a gathered KV window. ``q`` is
    ``[B, C, H, D]`` (the current chunk), ``k``/``v`` are ``[B, S, Kv, D]``
    where ``S`` covers every token written so far (for the paged executor:
    the request's block table gathered flat, so ``S = n_pages *
    page_size``). ``q_pos``/``kv_pos`` are absolute positions with ``-1``
    marking padding; a kv token attends iff ``0 <= kv_pos <= q_pos``
    (causal), windowed variants additionally require ``q_pos - kv_pos <
    window``. ``use_pallas=False`` dispatches the pure-jnp reference
    (CPU/CI); ``True`` the compiled Pallas TPU kernel (``interpret=True``
    runs it in the Pallas interpreter, for CPU tests only).

``ops.paged_decode_attention(q, k_pages, v_pages, block_tables,
context_lens, *, use_pallas=False)``
    One decode step over block-pooled KV. ``q`` is ``[B, H, D]``,
    ``k_pages``/``v_pages`` are the physical pool ``[num_pages, Kv,
    page_size, D]`` (head-major, so one head of one page is a contiguous
    ``(page_size, D)`` tile), ``block_tables`` is ``[B, max_pages]`` of pool
    page ids, and ``context_lens[b]`` counts the valid tokens of row
    ``b``: position ``p`` of its table is attended iff ``p <
    context_lens[b]``, so a partial last page is handled by length alone.
    The Pallas kernel runs one grid step per row for all KV heads and
    copies only the row's live pages, ``ceil(context_lens[b] /
    page_size)`` of them, HBM -> VMEM in double-buffered blocks of ``ppb``
    pages (1024 tokens' worth, derived from the page size, capped at
    ``max_pages``): table entries past the live pages are never read, so
    rows may be padded with any in-range page id, and a row of length 0
    yields zeros. Slots past the context in the last page may hold
    anything, NaN included. No sliding-window support.

``paged_decode_attention_pallas`` / ``chunked_prefill_attention_pallas``
    The raw Pallas kernels behind ``use_pallas=True`` — fixed tile-size
    contracts, no padding convenience; prefer the ``ops`` wrappers.

``chunked_prefill_attention_ref`` / ``paged_decode_attention_ref``
    Pure-jnp references the property tests check the kernels against.

This layer exists because the paper's serving results ride on paged
attention: the engine's :class:`~repro.kvcache.allocator.BlockAllocator`
block tables are the *same* tables these kernels consume, which is what
makes prefix-cache hits and Cronus PPI→CPI handoffs free at the compute
level (block-id remaps, no KV copies).
"""
from repro.kernels import ops
from repro.kernels.chunked_prefill_attention import \
    chunked_prefill_attention_pallas
from repro.kernels.ops import chunked_prefill_attention, paged_decode_attention
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.ref import (chunked_prefill_attention_ref,
                               paged_decode_attention_ref)

__all__ = [
    "ops",
    "chunked_prefill_attention",
    "paged_decode_attention",
    "chunked_prefill_attention_pallas",
    "paged_decode_attention_pallas",
    "chunked_prefill_attention_ref",
    "paged_decode_attention_ref",
]
