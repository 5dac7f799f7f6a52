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


@pytest.mark.parametrize("b,h,kv,d,pages,page,maxp", [
    (2, 8, 2, 64, 16, 8, 4),
    (3, 4, 4, 32, 8, 16, 3),
    (1, 8, 1, 128, 32, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel(b, h, kv, d, pages, page, maxp, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, d), dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, d), dtype)
    bt = jax.random.randint(ks[3], (b, maxp), 0, pages)
    cl = jnp.arange(b) * 7 % (maxp * page - 1) + 1
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, cl.astype(jnp.int32))
    got = paged_decode_attention_pallas(q, kp, vp, bt, cl.astype(jnp.int32),
                                        interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


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
