"""Run the paged Cronus serving path once on a TPU chip and check it.

Usage, from the checkout root on a machine with a TPU:

    python chip_smoke.py               # one chip: kernels, then a served pair
    python chip_smoke.py --four-chips  # four chips: one engine per chip,
                                       # compared with all four on one chip

The model is qwen2-7b at its published widths (d_model 3584, 28 query
and 4 KV heads, head_dim 128, d_ff 18944, vocab 152064, bf16), cut in
depth to fit one 16 GB chip beside its KV pools. Weights and requests
are made from seeds; nothing is read from outside the checkout. The
script runs in one process and catches nothing: any failed check ends it
with a traceback. Without a TPU it exits non-zero before printing a
result. Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster.pair import CronusPairEndpoint  # noqa: E402
from repro.cluster.topology import build_cluster  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.executor import PagedRealExecutor  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.api import ServeSpec  # noqa: E402
from repro.serving.trace import make_trace  # noqa: E402

# 20 layers x 0.466 GB + 2.18 GB of embedding and head = 11.5 GB in bf16
N_LAYERS = 20
BLOCK = 16                  # KV page size in tokens
NUM_KV_BLOCKS = 512         # per engine: 8192 tokens, 0.34 GB of K+V
N_REQUESTS = 8
SEED = 0
KERNEL_TOL = 2e-2           # the bf16 tolerance of tests/test_kernels.py
# a Cronus pair (PPI + CPI) and two workers: four engines
FOUR_ENGINES = "cronus:A100+A10,2xworker:A100"


def require(ok: bool, what: str) -> None:
    """Fail the run (uncaught) unless ``ok``."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Sums the seconds JAX's backend spends compiling."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def qwen2_7b_cut(n_layers: int = N_LAYERS):
    """qwen2-7b at its published widths, cut to ``n_layers``."""
    return dataclasses.replace(get_config("qwen2-7b"), n_layers=n_layers)


def azure_requests(vocab_size: int, n: int = N_REQUESTS, seed: int = SEED):
    """Azure-conversation-shaped requests (log-normal lengths), cut to a
    few hundred prompt tokens and (mostly exactly) 32 output tokens, all
    at t=0."""
    return make_trace(n, seed=seed, mean_in=300, mean_out=64, max_in=512,
                      max_out=32, vocab_size=vocab_size)


def kernel_errors(cfg, seed: int = SEED) -> dict:
    """Compiled Pallas kernels against the jnp references at the model's
    attention widths, bf16: max abs error of paged decode (8 ragged
    requests over a 64-page pool) and of one 512-token prefill chunk
    after 512 tokens of context."""
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    b, pages, maxp = 8, 64, 8
    q = jax.random.normal(ks[0], (b, h, d), bf)
    kp = jax.random.normal(ks[1], (pages, kvh, BLOCK, d), bf)
    vp = jax.random.normal(ks[2], (pages, kvh, BLOCK, d), bf)
    tables = jax.random.permutation(ks[3], pages).reshape(b, maxp)
    ctx = jnp.asarray(np.random.default_rng(seed).integers(
        1, maxp * BLOCK + 1, b), jnp.int32)
    dec = [ops.paged_decode_attention(q, kp, vp, tables, ctx, use_pallas=p)
           for p in (True, False)]

    c, s = 512, 1024
    q = jax.random.normal(ks[4], (1, c, h, d), bf)
    k = jax.random.normal(ks[5], (1, s, kvh, d), bf)
    v = jax.random.normal(ks[6], (1, s, kvh, d), bf)
    q_pos = (s - c + jnp.arange(c, dtype=jnp.int32))[None]
    kv_pos = jnp.arange(s, dtype=jnp.int32)[None]
    pre = [ops.chunked_prefill_attention(q, k, v, q_pos, kv_pos,
                                         use_pallas=p) for p in (True, False)]

    def err(pair):
        got, want = (np.asarray(x, np.float32) for x in pair)
        return float(np.max(np.abs(got - want)))

    return {"decode_max_err": err(dec), "prefill_max_err": err(pre)}


def steps_call_kernels(ex: PagedRealExecutor) -> dict:
    """Whether the executor's lowered decode and prefill step programs
    contain the Pallas kernels (``tpu_custom_call``)."""
    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    params = jax.tree.map(like, ex.params)
    pool = like(ex.k_pool)
    decode = ex._decode_fn.lower(params, pool, pool, i32(4), i32(4), i32(4),
                                 i32(4, 4), i32(4))
    prefill = ex._prefill_fn.lower(params, pool, pool, i32(1, 16),
                                   i32(1, 16), i32(16), i32(4), i32())
    return {name: "tpu_custom_call" in low.as_text()
            for name, low in (("decode", decode), ("prefill", prefill))}


def check_served(reqs, metrics: dict, vocab_size: int) -> None:
    """Every request completed with its full output, in vocabulary."""
    require(metrics["completed"] == len(reqs),
            f"completed {metrics['completed']} of {len(reqs)}")
    for r in reqs:
        require(len(r.generated) == r.output_len,
                f"{r.req_id}: {len(r.generated)} of {r.output_len} tokens")
        require(all(0 <= t < vocab_size for t in r.generated),
                f"{r.req_id}: token outside the vocabulary")


def serve_pair(model, params, n: int = N_REQUESTS, seed: int = SEED):
    """Serve seeded requests through a paged Cronus pair, built the way
    a user builds one (``ServeSpec`` -> ``InferenceService``)."""
    spec = ServeSpec(arch="qwen2-7b", approach="cronus", executor="paged",
                     block_size=BLOCK, num_kv_blocks=NUM_KV_BLOCKS,
                     max_slots=16)
    svc = spec.build(model=model, params=params)
    reqs = azure_requests(model.cfg.vocab_size, n, seed)
    metrics = svc.run(reqs)
    check_served(reqs, metrics, model.cfg.vocab_size)
    require(any(r.partial_len > 0 for r in reqs),
            "no request was handed from the PPI to the CPI")
    return svc, reqs, metrics


def four_engine_streams(model, params, n: int = N_REQUESTS,
                        seed: int = SEED) -> dict:
    """Serve one seeded trace twice through a Cronus pair plus two
    workers: once built by ``ServeSpec`` (one engine per local device),
    once with all four engines on the first device sharing ``params``.
    Returns each run's per-request token streams and engine devices, and
    for the first run the pair's (PPI, CPI) devices and how many requests
    the PPI handed to the CPI."""
    spec = ServeSpec(arch="qwen2-7b", cluster=FOUR_ENGINES,
                     router="round_robin", executor="paged",
                     block_size=BLOCK, num_kv_blocks=NUM_KV_BLOCKS,
                     max_slots=16)
    vocab = model.cfg.vocab_size
    svc = spec.build(model=model, params=params)
    reqs = azure_requests(vocab, n, seed)
    check_served(reqs, svc.run(reqs), vocab)
    (pair,) = [ep for ep in svc.endpoints
               if isinstance(ep, CronusPairEndpoint)]
    spread = {"devices": [_pool_device(e) for e in svc.engines],
              "streams": {r.req_id: list(r.generated) for r in reqs},
              "pair_devices": [_pool_device(e) for e in pair.engines],
              "handed": sum(r.partial_len > 0 for r in reqs)}
    del svc, pair           # free its pools before the reference run

    system = build_cluster(
        model.cfg, spec.cluster, router=spec.router,
        executor_factory=lambda role: PagedRealExecutor(model, params),
        max_slots=spec.max_slots, block_size=spec.block_size,
        max_batched_tokens=spec.max_batched_tokens,
        num_kv_blocks=spec.num_kv_blocks, executor="paged")
    reqs = azure_requests(vocab, n, seed)
    check_served(reqs, system.run(reqs), vocab)
    one = {"devices": [_pool_device(e) for e in system.engines],
           "streams": {r.req_id: list(r.generated) for r in reqs}}
    return {"spread": spread, "one_device": one}


def _pool_device(engine) -> int:
    """Id of the device that holds an engine's KV pool."""
    (dev,) = engine.executor.k_pool.devices()
    return dev.id


def _one_chip(model, params) -> None:
    errs = kernel_errors(model.cfg)
    print(f"kernels (compiled Pallas vs jnp reference, bf16): {errs}")
    require(max(errs.values()) <= KERNEL_TOL,
            f"kernel error above {KERNEL_TOL}")

    svc, reqs, metrics = serve_pair(model, params)
    calls = steps_call_kernels(svc.engines[0].executor)
    print(f"tpu_custom_call in lowered steps: {calls}")
    require(all(calls.values()), "a step program lacks the Pallas kernel")
    handed = sum(r.partial_len > 0 for r in reqs)
    print(f"served {metrics['completed']}/{len(reqs)} requests, "
          f"{sum(len(r.generated) for r in reqs)} tokens; "
          f"{handed} handed PPI->CPI (partial_len "
          f"{[r.partial_len for r in reqs]})")
    for e in svc.engines:
        print(f"compile_stats[{e.name}]: {e.executor.compile_stats()}")


def _four_chips(model, params) -> None:
    require(len(jax.local_devices()) >= 4, "--four-chips needs four chips")
    runs = four_engine_streams(model, params)
    for name, run in runs.items():
        print(f"{name}: engine devices {run['devices']}")
    require(len(set(runs["spread"]["devices"])) == 4,
            "engines are not on four distinct chips")
    require(set(runs["one_device"]["devices"]) == {0},
            "reference engines are not all on chip 0")
    ppi_dev, cpi_dev = runs["spread"]["pair_devices"]
    print(f"spread: {runs['spread']['handed']} requests handed PPI (chip "
          f"{ppi_dev}) -> CPI (chip {cpi_dev})")
    require(ppi_dev != cpi_dev, "the pair's PPI and CPI share a chip")
    require(runs["spread"]["handed"] > 0,
            "no request was handed across chips from the PPI to the CPI")
    same = runs["spread"]["streams"] == runs["one_device"]["streams"]
    print(f"token streams identical, four chips vs one: {same} "
          f"({len(runs['spread']['streams'])} requests)")
    require(same, "token streams differ between four chips and one")


def main() -> int:
    """Run the one-chip or the four-chip check; 0 when every check held."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the one-engine-per-chip check on four "
                         "chips and the one-chip run it is compared with")
    args = ap.parse_args()

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1
    print(f"compile cache: {configure_compile_cache()}")
    clock = CompileClock()

    cfg = qwen2_7b_cut()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(SEED), model.dtype,
                               device=dev)
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} cut to {cfg.n_layers} of 28 layers; "
          f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
          f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}; weights {weight_bytes} bytes")

    if args.four_chips:
        _four_chips(model, params)
    else:
        _one_chip(model, params)

    for d in jax.local_devices():
        print(f"peak_bytes_in_use[{d.id}]: "
              f"{d.memory_stats()['peak_bytes_in_use']}")
    print(f"compiling: {clock.seconds:.1f} s in {clock.count} backend "
          f"compiles")
    print(f"wall time (set-up plus run, not a speed metric): "
          f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
