"""The executor-facing half of a family served by the program's paged
executor (``PagedRealExecutor``): the program shapes a cell can reach, the
calls that compile each one, and the executor's device state.

This is no family of its own; a family whose engines run on that executor
returns these from its ``warm_phases`` and ``device_state``
(``chipbench/families/__init__.py``). It is the only file of the benchmark
that names the executor's private jitted steps and pools: the program has
no public warm-up entry yet, and its public calls need a request resident
in a slot.
"""
from __future__ import annotations

import math
from typing import List

import jax.numpy as jnp
import numpy as np

from chipbench import traffic

# extract_kv gathers the handoff's pages with eager ops, a handful of tiny
# programs per block count: about 1.1 s per block count to compile cold on
# a v5e, 0.12 s to load from the cache, and 1-10 s of stall when one first
# compiles inside the window. Handoffs of up to this many blocks (4096
# tokens at page 16) are warmed; warming all 512 would take the cold first
# run past its time limit. A longer one compiles when first seen
# (``compiles_in_window``)
EXTRACT_WARM_BLOCKS = 256


def _pow2(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def reachable_shapes(mix: dict, page: int, max_tokens: int,
                     max_slots: int) -> dict:
    """Bucket shapes the traffic mix's length bounds can reach, as the
    executor buckets them: prefill (chunk, pages), decode (batch, pages),
    handoff payload blocks and inject rows."""
    max_in, max_out = traffic.length_bounds(mix)
    prefill = set()
    for c in range(1, min(max_tokens, max_in) + 1):
        cb = _pow2(c, 16)
        lo = _pow2(math.ceil(c / page), 4)
        for pb in _buckets(lo, _pow2(math.ceil(max_in / page), 4)):
            prefill.add((cb, pb))
    pb_dec = _buckets(4, _pow2(math.ceil((max_in + max_out) / page), 4))
    decode = [(bb, pb) for bb in _buckets(4, _pow2(max_slots, 4))
              for pb in pb_dec]
    return {"prefill": sorted(prefill), "decode": decode,
            "extract_blocks": list(range(1, min(math.ceil(max_in / page),
                                                EXTRACT_WARM_BLOCKS) + 1)),
            "inject_rows": _buckets(page, _pow2(max_in, page))}


def warm_phases(ex, ecfg, mix: dict) -> dict:
    """Phase -> (shapes, call) for one engine's executor. Each call runs
    one shape on throwaway inputs that write only the trash page, exactly
    as the executor calls it, reads one logits row back the way the
    executor does, and drops its outputs, so the pools are left as they
    were. ``extract_kv`` repeats the executor's eager gather."""
    from repro.core.executor import robust_greedy
    page, trash = ex.page, ex._trash
    shapes = reachable_shapes(mix, page, ecfg.max_batched_tokens,
                              ecfg.max_slots)

    def prefill(cb, pb):
        pos = np.full((1, cb), -1, np.int32)
        pos[0, 0] = 0
        logits, _, _ = ex._prefill_fn(
            ex.params, ex.k_pool, ex.v_pool, np.zeros((1, cb), np.int32),
            pos, np.full((cb,), trash * page, np.int32),
            np.full((pb,), trash, np.int32), np.int32(1))
        robust_greedy(logits[0, 0])

    def decode(bb, pb):
        z = np.zeros((bb,), np.int32)
        logits, _, _ = ex._decode_fn(
            ex.params, ex.k_pool, ex.v_pool, z, z,
            np.full((bb,), trash * page, np.int32),
            np.full((bb, pb), trash, np.int32), z)
        robust_greedy(logits[0])

    def extract(n):
        idx = jnp.zeros((n,), jnp.int32)
        ex.k_pool[:, idx].transpose(0, 1, 3, 2, 4).block_until_ready()

    def inject(nb):
        l_dim, _, kvh, _, hd = ex.k_pool.shape
        rows = np.zeros((l_dim, nb, kvh, hd), jnp.bfloat16)
        k, _ = ex._inject_fn(ex.k_pool, ex.v_pool, rows, rows,
                             np.full((nb,), trash * page, np.int32))
        k.block_until_ready()

    return {"prefill": (shapes["prefill"], prefill),
            "decode": (shapes["decode"], decode),
            "extract_kv": (shapes["extract_blocks"], extract),
            "inject_kv": (shapes["inject_rows"], inject)}


def device_state(ex) -> tuple:
    """The executor's device arrays besides the weights: its K and V
    pools."""
    return ex.k_pool, ex.v_pool
