"""Pallas kernel validation (interpret mode) against pure-jnp oracles:
shape/dtype sweeps for the chunked-prefill flash kernel and the paged
decode kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.chunked_prefill_attention import chunked_prefill_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.ops import chunked_prefill_attention

KEY = jax.random.PRNGKey(0)


def _mk(b, c, h, kv, d, s, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, c, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    ctx = jnp.arange(b) * 5 + 3
    q_pos = ctx[:, None] + jnp.arange(c)[None, :]
    kv_pos = jnp.where(jnp.arange(s)[None, :] < (ctx + c)[:, None],
                       jnp.arange(s)[None, :], -1)
    return q, k, v, q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32)


@pytest.mark.parametrize("b,c,h,kv,d,s", [
    (1, 8, 4, 4, 32, 32),     # MHA
    (2, 16, 8, 2, 64, 64),    # GQA 4:1
    (2, 8, 8, 1, 64, 64),     # MQA
    (1, 32, 4, 4, 128, 32),   # d=128 MXU tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 8])
def test_chunked_prefill_kernel(b, c, h, kv, d, s, dtype, window):
    q, k, v, q_pos, kv_pos = _mk(b, c, h, kv, d, s, dtype)
    want = ref.chunked_prefill_attention_ref(q, k, v, q_pos, kv_pos, window)
    got = chunked_prefill_attention_pallas(
        q, k, v, q_pos, kv_pos, window=window, block_q=8, block_k=16,
        interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_chunked_prefill_ops_padding():
    """ops.py wrapper: unaligned C/S/D padded transparently."""
    q, k, v, q_pos, kv_pos = _mk(2, 13, 4, 2, 48, 50, jnp.float32)
    want = ref.chunked_prefill_attention_ref(q, k, v, q_pos, kv_pos, 0)
    got = chunked_prefill_attention(q, k, v, q_pos, kv_pos,
                                    use_pallas=True, block_q=8, block_k=16,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("b,h,kv,d,pages,page,maxp,lens", [
    pytest.param(2, 8, 2, 64, 16, 8, 4, None, id="2-8-2-64-16-8-4"),
    pytest.param(3, 4, 4, 32, 8, 16, 3, None, id="3-4-4-32-8-16-3"),
    pytest.param(1, 8, 1, 128, 32, 8, 8, None, id="1-8-1-128-32-8-8"),
    # qwen2 widths, page 16: 3 compute blocks of 64 pages, the last
    # partial; an empty padded lane; a row of one token; a block of one
    pytest.param(4, 28, 4, 128, 48, 16, 160, (2500, 1, 0, 1025),
                 id="qwen2-page16-blocks"),
    # qwen3's GQA group 8
    pytest.param(2, 64, 8, 128, 24, 16, 20, (300, 17), id="qwen3-group8"),
    # page 4: 256 pages per compute block
    pytest.param(4, 8, 2, 128, 96, 4, 320, (1280, 1025, 0, 1),
                 id="page4-blocks"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel(b, h, kv, d, pages, page, maxp, lens, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, d), dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, d), dtype)
    bt = jax.random.randint(ks[3], (b, maxp), 0, pages)
    cl = (jnp.arange(b) * 7 % (maxp * page - 1) + 1 if lens is None
          else jnp.array(lens)).astype(jnp.int32)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, cl)
    # an empty row attends to nothing: the kernel writes zeros there
    want = jnp.where(cl[:, None, None] == 0, 0.0, want.astype(jnp.float32))
    got = paged_decode_attention_pallas(q, kp, vp, bt, cl, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("page,maxp,lens", [
    (16, 160, (2100, 1, 0, 257)),
    (4, 320, (1100, 2, 0, 64)),
])
def test_paged_decode_reads_live_pages_only(page, maxp, lens):
    """Every pool slot that no row reads as live holds NaN: whole pages past
    each row's ``ceil(ctx / page)``, the tail of each partial last page,
    and the trash page (the last id) that pads the tables. The kernel must
    copy and attend to the live slots alone, so its output stays finite
    and matches the reference on the unpoisoned pool."""
    b, h, kv, d = len(lens), 8, 2, 128
    n_live = [-(-n // page) for n in lens]
    pages = sum(n_live) + 8 + 1            # live, spare dead pages, trash
    trash = pages - 1
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (pages, kv, page, d), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (pages, kv, page, d), jnp.bfloat16)

    poison = np.ones((pages, page), bool)   # per pool slot
    bt = np.full((b, maxp), trash, np.int32)
    dead = list(range(sum(n_live), trash))
    nxt = 0
    for i, (n, live) in enumerate(zip(lens, n_live)):
        bt[i, :live] = np.arange(nxt, nxt + live)
        for t in range(n):
            poison[nxt + t // page, t % page] = False
        nxt += live
        # the rest of the row points at poisoned pages
        bt[i, live:] = [dead[j % len(dead)] if j % 2 else trash
                        for j in range(maxp - live)]
    mask = jnp.asarray(poison)[:, None, :, None]
    cl = jnp.array(lens, jnp.int32)
    got = paged_decode_attention_pallas(
        q, jnp.where(mask, jnp.nan, kp), jnp.where(mask, jnp.nan, vp),
        jnp.asarray(bt), cl, interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    want = ref.paged_decode_attention_ref(q, kp, vp, jnp.asarray(bt), cl)
    want = np.where(np.asarray(cl)[:, None, None] == 0, 0.0,
                    np.asarray(want, np.float32))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_kernel_matches_model_attention():
    """The Pallas chunked-prefill kernel computes the same attention the
    model's jnp path uses in the engine (GQA + position masking)."""
    from repro.models.attention import gqa_attend, make_mask
    q, k, v, q_pos, kv_pos = _mk(2, 8, 8, 2, 64, 64, jnp.float32)
    mask = make_mask(q_pos, kv_pos, jnp.int32(0))
    want = gqa_attend(q, k, v, mask, 64 ** -0.5)
    got = chunked_prefill_attention_pallas(q, k, v, q_pos, kv_pos,
                                           block_q=8, block_k=16,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,sq,h,kv,d,skv,window", [
    (2, 64, 8, 2, 32, 128, 0),
    (1, 100, 4, 4, 64, 100, 0),     # unaligned block boundaries
    (2, 64, 8, 2, 32, 128, 24),     # sliding window
    (1, 7, 2, 2, 16, 40, 0),        # chunk smaller than a block
])
def test_blocked_attention_matches_exact(b, sq, h, kv, d, skv, window):
    """Flash-style blocked attention (pure XLA, §Perf HC-prefill) must match
    the exact masked-softmax path bit-for-bit up to fp32 accumulation."""
    from repro.models.attention import (blocked_gqa_attend, gqa_attend,
                                        make_mask)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, skv, kv, d))
    v = jax.random.normal(ks[2], (b, skv, kv, d))
    ctx = jnp.arange(b) * 3 + 5
    q_pos = (ctx[:, None] + jnp.arange(sq)[None, :]).astype(jnp.int32)
    kv_pos = jnp.where(jnp.arange(skv)[None, :] < (ctx + sq)[:, None],
                       jnp.arange(skv)[None, :], -1).astype(jnp.int32)
    want = gqa_attend(q, k, v, make_mask(q_pos, kv_pos, jnp.int32(window)),
                      d ** -0.5)
    got = blocked_gqa_attend(q, k, v, q_pos, kv_pos, jnp.int32(window),
                             d ** -0.5, block_q=16, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
