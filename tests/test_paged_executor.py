"""PagedRealExecutor: block-pool KV driven by the engine's block tables.

Three layers of evidence that paging is a pure layout change:

  * kernel properties — the paged decode kernel matches the jnp reference
    under ragged context lengths, partial last pages and both page sizes
    the executors use, and is invariant to block-table padding ids;
  * token equivalence — every approach x arrival pattern produces the
    same token streams whether KV lives in dense per-slot buffers
    (RealExecutor) or in the shared block pool (PagedRealExecutor);
  * the features the slot layout cannot do — prefix-cache hits and CoW
    divergence on real compute — leave tokens identical to cold runs.

Compile hygiene rides along: a full trace replay compiles a fixed,
asserted number of (bucket, batch) shapes, and a second identical wave
compiles nothing new.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.executor import PagedRealExecutor, RealExecutor
from repro.core.request import Request
from repro.models import build_model
from repro.serving.api import ServeSpec
from repro.serving.hardware import A100, A30
from repro.serving.simulator import APPROACHES, build_system

S_KV, SLOTS, CHUNK, BLOCK = 128, 4, 16, 4
# identical KV pool for slot and paged runs: the Balancer and admission
# gate on allocator.num_free, so token equivalence needs both runs to
# see the same block budget
NBLK = SLOTS * (S_KV // BLOCK)
LENS = [(17, 5), (33, 8), (9, 4), (41, 6), (25, 3)]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama3-8b", smoke=True)
    model = build_model(cfg, exact_moe=True)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in LENS]
    return cfg, model, params, prompts


def _reqs(prompts, staggered=False):
    reqs = [Request(req_id=f"r{i}", prompt=prompts[i].copy(),
                    output_len=LENS[i][1], arrival=0.0)
            for i in range(len(LENS))]
    if staggered:
        for i, r in enumerate(reqs):
            r.arrival = i * 0.5
            r.metrics.arrival = r.arrival
    return reqs


def _run(kind, cfg, model, params, prompts, approach, staggered):
    if kind == "real":
        def factory(role):
            return RealExecutor(model, params, max_slots=SLOTS, s_kv=S_KV,
                                chunk_pad=CHUNK)
    else:
        def factory(role):
            return PagedRealExecutor(model, params)
    system = build_system(approach, cfg, A100, A30,
                          executor_factory=factory, max_slots=SLOTS,
                          block_size=BLOCK, max_batched_tokens=CHUNK,
                          num_kv_blocks=NBLK, executor=kind)
    res = system.run(_reqs(prompts, staggered))
    assert res["completed"] == len(LENS)
    if hasattr(system, "engines"):               # DPSystem
        engines = system.engines
    elif hasattr(system, "engine"):              # PPSystem
        engines = [system.engine]
    else:                                        # CronusSystem
        engines = [system.ppi, system.cpi]
    toks, parts = {}, {}
    for e in engines:
        for r in e.finished:
            toks.setdefault(r.req_id, list(r.generated))
            parts.setdefault(r.req_id, r.partial_len)
    assert len(toks) == len(LENS)
    return toks, parts


# ---------------------------------------------------------------------------
# kernel properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page", [4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_decode_kernel_ragged(page, seed):
    """Pallas paged decode == jnp reference under ragged context lengths
    with partial last pages (len % page != 0 for most rows)."""
    from repro.kernels import (paged_decode_attention_pallas,
                               paged_decode_attention_ref)
    rng = np.random.default_rng(seed)
    b, h, kv, d, pages, maxp = 4, 4, 2, 32, 24, 6
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kp = jax.random.normal(ks[1], (pages, kv, page, d))
    vp = jax.random.normal(ks[2], (pages, kv, page, d))
    bt = np.asarray(rng.integers(0, pages, (b, maxp)), np.int32)
    # ragged: at least one full-page row, the rest partial last pages
    cl = np.asarray([maxp * page]
                    + list(rng.integers(1, maxp * page, b - 1)), np.int32)
    want = paged_decode_attention_ref(q, kp, vp, bt, cl)
    got = paged_decode_attention_pallas(q, kp, vp, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_padding_id_invariance():
    """Table entries past ceil(context_len / page) are dead: any in-range
    page id there (the executor pads with the trash page) must not change
    the output — masking is by context length, never by id."""
    from repro.kernels import paged_decode_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, h, kv, d, pages, page, maxp = 2, 4, 2, 32, 16, 4, 4
    q = jax.random.normal(ks[0], (b, h, d))
    kp = jax.random.normal(ks[1], (pages, kv, page, d))
    vp = jax.random.normal(ks[2], (pages, kv, page, d))
    cl = np.asarray([5, 9], np.int32)           # 2 and 3 live pages
    bt = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    base = np.asarray(paged_decode_attention_ref(q, kp, vp, bt, cl))
    for junk in (0, pages - 1):
        bt2 = bt.copy()
        bt2[0, 2:] = junk                        # dead tail of row 0
        bt2[1, 3:] = junk                        # dead tail of row 1
        got = np.asarray(paged_decode_attention_ref(q, kp, vp, bt2, cl))
        np.testing.assert_array_equal(got, base)


# ---------------------------------------------------------------------------
# token equivalence: paged == slot on every approach x arrival pattern
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("staggered", [False, True],
                         ids=["maxtput", "staggered"])
@pytest.mark.parametrize("approach", APPROACHES)
def test_paged_matches_slot_tokens(setup, approach, staggered):
    """Token equivalence matrix. Exact token equality is asserted for
    every cell whose chunk boundaries are arrival-independent (all five
    approaches at maxtput; dp/pp/disagg staggered — FixedBalancer pins
    the split to the input length). cronus+staggered chunk boundaries
    depend on arrival-time CPI stats, which test_system.py documents as
    compile-cache-sensitive on CPU: near-flat smoke-model logits make a
    1-token chunk's robust-greedy pick borderline, so there we assert
    the structure (same balancer splits, same stream lengths) and leave
    exact-token checks to the arrival-independent cells."""
    cfg, model, params, prompts = setup
    slot, s_parts = _run("real", cfg, model, params, prompts, approach,
                         staggered)
    paged, p_parts = _run("paged", cfg, model, params, prompts, approach,
                          staggered)
    assert p_parts == s_parts                  # identical balancer splits
    if approach == "cronus" and staggered:
        assert {k: len(v) for k, v in paged.items()} == \
               {k: len(v) for k, v in slot.items()}
    else:
        assert paged == slot


# ---------------------------------------------------------------------------
# what only the paged layout can do on real compute
# ---------------------------------------------------------------------------

def _cache_reqs(vocab):
    rng = np.random.default_rng(7)
    # 26 % BLOCK != 0 so the cache hit shares a partial block -> CoW copy
    shared = rng.integers(0, vocab, 26).astype(np.int32)
    tails = [rng.integers(0, vocab, n).astype(np.int32) for n in (9, 13, 5)]
    return [Request(req_id=f"c{i}", prompt=np.concatenate([shared, t]),
                    output_len=6, arrival=float(i))
            for i, t in enumerate(tails)]


def test_paged_prefix_cache_cow_divergence(setup):
    """Prefix-cache hits + CoW divergence on REAL compute: the cached run
    skips prefill work (cached_prefix_tokens > 0) yet decodes the exact
    tokens of the cold run — including past the shared prefix, where each
    request's KV diverges in its own CoW copy of the partial block."""
    cfg, *_ = setup

    def run(cluster):
        spec = ServeSpec(cluster=cluster, smoke=True, executor="paged",
                         s_kv=64, max_slots=SLOTS, block_size=BLOCK,
                         max_batched_tokens=CHUNK)
        svc = spec.build()
        svc.run(_cache_reqs(cfg.vocab_size))
        eng = svc.engines[0]
        toks = {r.req_id: list(r.generated) for r in eng.finished}
        reused = sum(r.metrics.cached_prefix_tokens for r in eng.finished)
        return toks, reused

    cold, reused_cold = run("worker:A100")
    warm, reused_warm = run("worker:A100@cache")
    assert reused_cold == 0
    assert reused_warm > 0
    assert warm == cold


def test_real_refuses_prefix_cache_paged_lifts_it():
    with pytest.raises(ValueError, match="paged"):
        ServeSpec(smoke=True, executor="real", s_kv=64, prefix_cache=True)
    spec = ServeSpec(smoke=True, executor="paged", s_kv=64,
                     prefix_cache=True)          # no raise
    assert spec.effective_num_kv_blocks() == spec.max_slots * (64 // 16)
    # and the new fields survive the JSON round-trip
    spec = ServeSpec(smoke=True, executor="paged", s_kv=64,
                     num_kv_blocks=80)
    assert ServeSpec.from_dict(spec.to_dict()) == spec
    with pytest.raises(ValueError, match="num_kv_blocks"):
        ServeSpec(smoke=True, executor="null", num_kv_blocks=80)


# ---------------------------------------------------------------------------
# compile hygiene
# ---------------------------------------------------------------------------

def test_paged_compile_budget(setup):
    """A full trace costs a bounded number of compiled (bucket, batch)
    shapes, and an identical second wave compiles NOTHING new — every
    dispatch hits the pow2-bucket cache."""
    cfg, model, params, prompts = setup
    spec = ServeSpec(smoke=True, approach="cronus", hi="A100", lo="A30",
                     executor="paged", s_kv=S_KV, max_slots=SLOTS,
                     block_size=BLOCK, max_batched_tokens=CHUNK,
                     num_kv_blocks=NBLK)
    svc = spec.build(model=model, params=params)
    svc.run(_reqs(prompts))
    stats = {e.name: e.executor.compile_stats() for e in svc.engines}
    for name, st in stats.items():
        assert st["total_shapes"] <= 12, (name, st)
    wave2 = [Request(req_id=f"w{i}", prompt=prompts[i].copy(),
                     output_len=LENS[i][1], arrival=0.0)
             for i in range(len(LENS))]
    svc.run(wave2)
    after = {e.name: e.executor.compile_stats() for e in svc.engines}
    for name in stats:
        assert after[name]["total_shapes"] == stats[name]["total_shapes"], (
            name, stats[name], after[name])
        assert after[name]["dispatches"] > stats[name]["dispatches"]


# ---------------------------------------------------------------------------
# KV transfer payloads (Cronus PPI -> CPI)
# ---------------------------------------------------------------------------

class _StubEngine:
    """The three attributes attach_engine / the executor call sites read."""

    def __init__(self):
        from repro.core.engine import EngineConfig
        from repro.kvcache.allocator import BlockAllocator
        self.ecfg = EngineConfig(max_batched_tokens=CHUNK, max_slots=SLOTS,
                                 block_size=BLOCK, num_kv_blocks=NBLK,
                                 executor="paged")
        self.allocator = BlockAllocator(NBLK, BLOCK)
        self.slots = [None] * SLOTS

    def place(self, slot, req_id, n_tokens):
        import types
        self.allocator.allocate(req_id, n_tokens)
        self.slots[slot] = types.SimpleNamespace(req_id=req_id)


def test_extract_kv_payload_bounded(setup):
    """Regression: extract_kv must copy only `upto` tokens — the Cronus
    transfer payload is sized by actual context, not capacity. The slot
    executor used to ship the full padded S_KV width; the paged payload
    is block-granular (ceil(upto / page) pages)."""
    cfg, model, params, prompts = setup
    upto = 17
    ex = RealExecutor(model, params, max_slots=SLOTS, s_kv=S_KV,
                      chunk_pad=CHUNK)
    ex.prefill_chunk(0, prompts[0][:upto], 0, False)
    payload = ex.extract_kv(0, upto)
    seq_keys = [k for k in payload["stack"] if k in ("k", "v", "ckv", "kpe")]
    assert seq_keys
    for key in seq_keys:
        assert payload["stack"][key].shape[1] == upto, (
            key, payload["stack"][key].shape, "payload must be `upto`-"
            "bounded, not the padded slot width S_KV")

    px = PagedRealExecutor(model, params)
    eng = _StubEngine()
    px.attach_engine(eng)
    eng.place(0, "p0", upto)
    for lo in range(0, upto, CHUNK):
        hi = min(lo + CHUNK, upto)
        px.prefill_chunk(0, prompts[0][lo:hi], lo, False)
    pp = px.extract_kv(0, upto)
    n_pages = -(-upto // BLOCK)
    assert pp["_upto"] == upto and pp["_page"] == BLOCK
    assert pp["k_pages"].shape == (model.n_stack, n_pages, BLOCK,
                                   cfg.n_kv_heads, cfg.head_dim)
    assert pp["v_pages"].shape == pp["k_pages"].shape


def test_paged_extract_inject_roundtrip(setup):
    """extract_kv -> inject_kv across two paged executors (the PPI->CPI
    handoff) lands the source KV rows exactly in the destination pool
    positions the destination's own block table assigns."""
    cfg, model, params, prompts = setup
    upto = 9
    src, dst = PagedRealExecutor(model, params), PagedRealExecutor(model,
                                                                   params)
    se, de = _StubEngine(), _StubEngine()
    src.attach_engine(se)
    dst.attach_engine(de)
    se.place(0, "s0", upto)
    de.place(2, "d0", upto)                  # different slot, own table
    src.prefill_chunk(0, prompts[0][:upto], 0, False)
    dst.inject_kv(2, src.extract_kv(0, upto), upto)

    st = se.allocator.block_table("s0")
    dt = de.allocator.block_table("d0")
    def token_rows(pool):
        """[L, P+1, Kv, page, D] pool -> [L, (P+1)*page, Kv, D] flat slots."""
        return np.asarray(pool).transpose(0, 1, 3, 2, 4).reshape(
            model.n_stack, -1, cfg.n_kv_heads, cfg.head_dim)

    sk, dk = token_rows(src.k_pool), token_rows(dst.k_pool)
    for p in range(upto):
        s_idx = st[p // BLOCK] * BLOCK + p % BLOCK
        d_idx = dt[p // BLOCK] * BLOCK + p % BLOCK
        np.testing.assert_array_equal(dk[:, d_idx], sk[:, s_idx])


# ---------------------------------------------------------------------------
# one engine per device
# ---------------------------------------------------------------------------

def test_one_engine_per_device_subprocess():
    """Four devices: the paged factory puts a Cronus pair plus two
    workers on four distinct devices, and every token stream equals the
    run with all four engines on one device (the CPU rehearsal of
    ``chip_smoke.py --four-chips``; the test session's jax sees one
    device, so this runs in its own process). At smoke widths it takes
    about 20 s on a CPU host, so unlike the multi-minute subprocess
    oracles it stays in the fast tier."""
    import os
    import subprocess
    import sys
    script = os.path.join(os.path.dirname(__file__), "helpers",
                          "check_engine_per_device.py")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "engine devices [0, 1, 2, 3]" in proc.stdout
