"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference wall times on
CPU are NOT performance numbers (TPU is the target); this bench validates
numerics at larger shapes and reports the ref path's CPU throughput as a
regression canary."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.chunked_prefill_attention import chunked_prefill_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas


def run():
    print("name,us_per_call,derived")
    key = jax.random.PRNGKey(0)

    b, c, h, kv, d, s = 2, 128, 8, 2, 128, 1024
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, c, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    ctx = jnp.array([512, 700])
    q_pos = (ctx[:, None] + jnp.arange(c)[None, :]).astype(jnp.int32)
    kv_pos = jnp.where(jnp.arange(s)[None, :] < (ctx + c)[:, None],
                       jnp.arange(s)[None, :], -1).astype(jnp.int32)

    fn = jax.jit(lambda *a: ref.chunked_prefill_attention_ref(*a, 0))
    fn(q, k, v, q_pos, kv_pos).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        out_ref = fn(q, k, v, q_pos, kv_pos).block_until_ready()
    t_ref = (time.time() - t0) / 5
    print(f"kernels/chunked_prefill_ref_xla,{t_ref*1e6:.1f},"
          f"shape=b{b}c{c}h{h}d{d}s{s}")

    out_pl = chunked_prefill_attention_pallas(q, k, v, q_pos, kv_pos,
                                              block_q=128, block_k=128,
                                              interpret=True)
    err = float(jnp.max(jnp.abs(out_pl - out_ref)))
    print(f"kernels/chunked_prefill_pallas_interp,0,max_err={err:.2e}")

    p_tot, page, maxp = 64, 16, 16
    q2 = jax.random.normal(ks[0], (8, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (p_tot, kv, page, d), jnp.float32)
    vp = jax.random.normal(ks[2], (p_tot, kv, page, d), jnp.float32)
    bt = jax.random.randint(key, (8, maxp), 0, p_tot)
    cl = (jnp.arange(8) * 29 % (maxp * page - 1) + 1).astype(jnp.int32)
    fn2 = jax.jit(ref.paged_decode_attention_ref)
    fn2(q2, kp, vp, bt, cl).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        out2_ref = fn2(q2, kp, vp, bt, cl).block_until_ready()
    t2 = (time.time() - t0) / 5
    print(f"kernels/paged_decode_ref_xla,{t2*1e6:.1f},pages={p_tot}x{page}")
    out2 = paged_decode_attention_pallas(q2, kp, vp, bt, cl, interpret=True)
    err2 = float(jnp.max(jnp.abs(out2 - out2_ref)))
    print(f"kernels/paged_decode_pallas_interp,0,max_err={err2:.2e}")


if __name__ == "__main__":
    run()
