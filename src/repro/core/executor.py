"""Executors: the compute backends of the serving engines.

``RealExecutor`` runs actual JAX forwards on a slot-based cache (functional
correctness at reduced scale — the engine's tokens must match a monolithic
run bit-for-bit). ``PagedRealExecutor`` runs the same math over a block-pool
KV layout driven by the engine's live :class:`~repro.kvcache.BlockAllocator`
tables — attention reads exactly the blocks a request owns (paged-attention
kernels via :mod:`repro.kernels.ops`), so prefix-cache hits, copy-on-write
shares and Cronus PPI→CPI handoffs work on real compute. ``NullExecutor``
skips compute entirely (scheduling + timing studies at paper scale —
Tables 2-3, Fig. 4). All sit behind the same interface, so the
scheduler/balancer code under test is identical.

Slot-garbage invariant (why batched forwards are safe): forwards always run
over ALL slots; rows of slots not participating this iteration write
garbage K/V at indices beyond their valid region. Validity is defined
exclusively by host-managed ``kv_positions``, which only ever advance for
participating slots, and any later advance overwrites those indices with
real K/V first. Freed slots reset their position row to -1.

The paged pool has the same invariant per block row: padded/inactive lanes
write into a dedicated trash page that no block table references, and
attention masks by ``context_lens`` / kv positions, never by content.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def _pow2_bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class BucketCache:
    """Single home for power-of-two shape bucketing + compilation accounting.

    ``jax.jit`` caches one executable per distinct argument-shape tuple, so
    every *new* bucketed shape an executor dispatches is exactly one XLA
    compilation. Executors funnel all shape choices through one instance;
    ``compile_stats()`` then lets tests assert a fixed compilation budget
    over a full trace instead of hoping recompilation stays bounded.
    """

    def __init__(self):
        self._shapes: Dict[str, Dict[Tuple[int, ...], int]] = {}

    def bucket(self, n: int, lo: int = 16) -> int:
        return _pow2_bucket(n, lo)

    def record(self, kind: str, *shape: int) -> bool:
        """Note one dispatch of ``kind`` at a bucketed ``shape``. Returns
        True when the shape is new (i.e. this dispatch compiles)."""
        seen = self._shapes.setdefault(kind, {})
        new = shape not in seen
        seen[shape] = seen.get(shape, 0) + 1
        return new

    def compile_stats(self) -> Dict[str, int]:
        """Per-kind distinct compiled shapes, plus totals."""
        out = {kind: len(seen) for kind, seen in self._shapes.items()}
        out["total_shapes"] = sum(len(s) for s in self._shapes.values())
        out["dispatches"] = sum(c for s in self._shapes.values()
                                for c in s.values())
        return out


# Margin for deterministic greedy tie-breaking. XLA CPU results carry small
# environment-dependent jitter (heap alignment changes SIMD reduction tails,
# ~1e-4 with fp32); plain argmax then flips near-ties and the token stream
# cascades. Reproducible serving instead picks the LOWEST token id among all
# logits within this margin of the max — stable under jitter << margin.
GREEDY_TIE_MARGIN = 0.05


def robust_greedy(logits_row) -> int:
    row = np.asarray(logits_row, np.float32)
    top = row.max()
    return int(np.nonzero(row >= top - GREEDY_TIE_MARGIN)[0][0])


class NullExecutor:
    """No compute; emits deterministic dummy tokens."""

    def __init__(self):
        self._counter = 0

    def prefill_chunk(self, slot, tokens, ctx_len, completes, enc_emb=None):
        if completes:
            self._counter += 1
            return self._counter
        return None

    def decode(self, slot_tokens: Dict[int, int], slot_lens: Dict[int, int]):
        out = {}
        for s in slot_tokens:
            self._counter += 1
            out[s] = self._counter
        return out

    def extract_kv(self, slot, upto):
        return {"_null": upto}

    def inject_kv(self, slot, payload, upto):
        pass

    def reset_slot(self, slot):
        pass


class RealExecutor:
    """JAX execution over a slot-based unified cache with host-managed
    positions. Chunk lengths are padded to power-of-two buckets to bound
    recompilation."""

    def __init__(self, model, params, *, max_slots: int, s_kv: int,
                 chunk_pad: Optional[int] = None, greedy: bool = True):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.max_slots = max_slots
        self.s_kv = s_kv
        # Fixed chunk width: padding every prefill chunk to one width keeps
        # all forwards shape-identical, so XLA reductions are bit-identical
        # across schedules (token streams then match any same-width oracle).
        self.chunk_pad = chunk_pad
        self.cache = model.init_cache(max_slots, s_kv)
        self.pos = np.full((max_slots, s_kv), -1, np.int32)   # host positions
        self.lens = np.zeros((max_slots,), np.int32)          # host lengths
        self._fwd = jax.jit(
            lambda p, inp, cache, cl, pos, kvp, dec: model.forward(
                p, inp, cache, cl, positions=pos, kv_positions=kvp,
                decode=dec),
            static_argnames=("dec",))
        self._enc_dec = self.cfg.enc_dec
        self.buckets = BucketCache()

    def compile_stats(self) -> Dict[str, int]:
        return self.buckets.compile_stats()

    # ------------------------------------------------------------------
    def _run(self, inputs, positions, decode: bool, active_mask=None,
             enc_out=None):
        # copies: on CPU jnp.asarray may alias an aligned numpy buffer, and
        # the host rewrites pos/lens in place while this asynchronously
        # dispatched step can still be reading them
        kvp = jnp.asarray(self.pos.copy())
        cl = jnp.asarray(self.lens.copy())
        if self._enc_dec:
            logits, new_cache, _ = self.model.forward(
                self.params, jnp.asarray(inputs), self.cache, cl,
                positions=jnp.asarray(positions), kv_positions=kvp,
                enc_out=enc_out, decode=decode)
        else:
            logits, new_cache, _ = self._fwd(
                self.params, jnp.asarray(inputs), self.cache, cl,
                jnp.asarray(positions), kvp, decode)
        # Attention-cache garbage written to inactive slots is masked by
        # positions, but recurrent SSM state is not — restore it for slots
        # that did not participate in this forward.
        if active_mask is not None and "h" in new_cache.get("stack", {}):
            m = jnp.asarray(active_mask)
            old, new = self.cache["stack"], dict(new_cache["stack"])
            for key in ("h", "conv"):
                sel = m.reshape((1, -1) + (1,) * (old[key].ndim - 2))
                new[key] = jnp.where(sel, new[key], old[key])
            new_cache = dict(new_cache)
            new_cache["stack"] = new
        self.cache = new_cache
        return logits

    def prefill_chunk(self, slot: int, tokens: np.ndarray, ctx_len: int,
                      completes: bool, enc_emb=None) -> Optional[int]:
        """Run one prefill chunk for `slot`. Returns first token if the
        prompt completes with this chunk."""
        c = len(tokens)
        if self.chunk_pad and c <= self.chunk_pad:
            cb = self.chunk_pad
        else:
            cb = self.buckets.bucket(c)
        self.buckets.record("prefill", self.max_slots, cb)
        inputs = np.zeros((self.max_slots, cb), np.int32)
        positions = np.full((self.max_slots, cb), -1, np.int32)
        inputs[slot, :c] = tokens
        positions[slot, :c] = ctx_len + np.arange(c)
        # mark new positions valid for this slot (host-side)
        idx = (ctx_len + np.arange(c)) % self.s_kv
        self.pos[slot, idx] = ctx_len + np.arange(c)
        if self._enc_dec and enc_emb is not None:
            # run the encoder for this request only and install its
            # cross-KV into the slot (never clobbering other slots)
            assert enc_emb.shape[0] == self.cache["cross_k"].shape[2], (
                "encoder input length must match the cross-KV cache "
                f"({enc_emb.shape[0]} vs {self.cache['cross_k'].shape[2]}); "
                "pad/crop the frontend-stub embeddings to enc_seq_len")
            enc_out = self.model.encode(self.params,
                                        jnp.asarray(enc_emb)[None])
            ck, cv = self.model.compute_cross_kv(self.params, enc_out)
            cache = dict(self.cache)
            cache["cross_k"] = cache["cross_k"].at[:, slot].set(ck[:, 0])
            cache["cross_v"] = cache["cross_v"].at[:, slot].set(cv[:, 0])
            self.cache = cache
        mask = np.zeros((self.max_slots,), bool)
        mask[slot] = True
        logits = self._run(inputs, positions, decode=False, active_mask=mask)
        self.lens[slot] = ctx_len + c
        if completes:
            return robust_greedy(logits[slot, c - 1])
        return None

    def decode(self, slot_tokens: Dict[int, int],
               slot_lens: Dict[int, int]) -> Dict[int, int]:
        """One decode step for the given slots. Returns slot -> next token."""
        self.buckets.record("decode", self.max_slots, 1)
        inputs = np.zeros((self.max_slots, 1), np.int32)
        positions = np.full((self.max_slots, 1), -1, np.int32)
        mask = np.zeros((self.max_slots,), bool)
        for s, tok in slot_tokens.items():
            inputs[s, 0] = tok
            positions[s, 0] = slot_lens[s]
            self.pos[s, slot_lens[s] % self.s_kv] = slot_lens[s]
            mask[s] = True
        logits = self._run(inputs, positions, decode=True, active_mask=mask)
        out = {}
        for s in slot_tokens:
            out[s] = robust_greedy(logits[s, 0])
            self.lens[s] = slot_lens[s] + 1
        return out

    # ------------------------------------------------------------------
    # KV handoff. Attention caches (k/v, MLA ckv/kpe) carry a sequence
    # axis at dim 2 of each [L, slot, S_kv, ...] leaf; only the first
    # ``upto`` positions are valid at extract time, so only they travel —
    # the PPI->CPI payload is sized by the partial prefill, not by the
    # padded slot width. Recurrent state (SSM h/conv — conv's pseudo-seq
    # axis is kernel taps, not positions) and cross-KV move whole.
    _SEQ_KEYS = frozenset(("k", "v", "ckv", "kpe"))

    def extract_kv(self, slot: int, upto: int):
        """Pull one slot's cache slices (the PPI->CPI payload)."""
        def take(key, a):
            return a[:, slot, :upto] if key in self._SEQ_KEYS else a[:, slot]

        payload = {"stack": {k: take(k, a)
                             for k, a in self.cache["stack"].items()}}
        if "dense" in self.cache:
            payload["dense"] = {k: take(k, a)
                                for k, a in self.cache["dense"].items()}
        for k in ("cross_k", "cross_v"):
            if k in self.cache:
                payload[k] = self.cache[k][:, slot]
        payload["_upto"] = upto
        return payload

    def inject_kv(self, slot: int, payload, upto: int):
        """Install a transferred payload into `slot` and mark [0, upto) valid."""
        def put(key, dst, src):
            if key in self._SEQ_KEYS:
                return dst.at[:, slot, :src.shape[1]].set(src)
            return dst.at[:, slot].set(src)

        cache = dict(self.cache)
        cache["stack"] = {k: put(k, a, payload["stack"][k])
                          for k, a in self.cache["stack"].items()}
        if "dense" in payload:
            cache["dense"] = {k: put(k, a, payload["dense"][k])
                              for k, a in self.cache["dense"].items()}
        for k in ("cross_k", "cross_v"):
            if k in payload:
                cache[k] = cache[k].at[:, slot].set(payload[k])
        self.cache = cache
        self.pos[slot, :] = -1
        self.pos[slot, :upto] = np.arange(upto)
        self.lens[slot] = upto

    def reset_slot(self, slot: int):
        self.pos[slot, :] = -1
        self.lens[slot] = 0
        # Attention-cache garbage is masked out by positions, but recurrent
        # state (SSM/hybrid) has no positional validity — zero it explicitly.
        stack = self.cache["stack"]
        if "h" in stack:
            cache = dict(self.cache)
            new_stack = dict(stack)
            for key in ("h", "conv"):
                new_stack[key] = stack[key].at[:, slot].set(0)
            cache["stack"] = new_stack
            self.cache = cache


class PagedRealExecutor:
    """JAX execution over a block-pool KV cache driven by the engine's live
    block tables.

    Layout: per layer, K and V pools of shape ``[num_blocks + 1,
    n_kv_heads, block_size, head_dim]`` (stacked to ``[L, P+1, Kv, bs, D]``
    for the layer scan) — head-major, the paged decode kernel's layout.
    Pool row ``i`` *is* allocator block ``i`` — the engine's
    :class:`~repro.kvcache.BlockAllocator` decides placement and this
    executor just reads/writes through the tables, so:

      * prefix-cache hits skip real prefill compute (retained blocks keep
        their K/V rows; ``share_blocks`` only bumps refcounts),
      * copy-on-write divergence clones one block row (the allocator's
        ``on_cow`` hook, registered at :meth:`attach_engine`),
      * Cronus PPI→CPI ``extract_kv``/``inject_kv`` move only the blocks
        covering the partial prefill — and skip positions the target's
        cache already shares (a block-id remap, not a slot-cache rewrite).

    The extra pool row (index ``num_blocks``) is a trash page: padded batch
    lanes and padded chunk tokens write their garbage K/V there. No block
    table ever references it, and attention masks strictly by positions /
    ``context_lens``, so garbage is never read (same invariant as the slot
    executor's position masking).

    Decode runs :func:`repro.kernels.ops.paged_decode_attention` over the
    pool + gathered block tables; prefill chunks run
    :func:`repro.kernels.ops.chunked_prefill_attention` over the request's
    gathered pages. ``use_pallas=None`` auto-selects the compiled Pallas
    kernels on TPU backends and the jnp reference path elsewhere (CPU CI).
    The pools are made on the device that holds ``params``.

    Supported model families: dense-attention stacks ("mlp" kind, e.g. the
    llama3 smoke arch) without sliding windows. MoE/SSM/hybrid/MLA/enc-dec
    and windowed layers stay on :class:`RealExecutor`.
    """

    # Pool rows are materialized for real — refuse the simulated device
    # HBM budgets (tens of thousands of blocks) that the builders default
    # to, and demand an explicit ``num_kv_blocks`` override instead.
    MAX_POOL_BLOCKS = 8192

    def __init__(self, model, params, *, use_pallas: Optional[bool] = None,
                 greedy: bool = True):
        self.check_model(model)
        self.model = model
        self.params = params
        self.cfg = model.cfg
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.greedy = greedy
        self.buckets = BucketCache()
        self._engine = None
        self._allocator = None
        self.page: Optional[int] = None
        self.k_pool = None              # [L, P+1, Kv, page, D]
        self.v_pool = None
        self._trash: Optional[int] = None
        self._host_store: Dict[bytes, tuple] = {}   # chain hash -> (K, V)

    @staticmethod
    def check_model(model) -> None:
        """Raise NotImplementedError unless the paged path can run
        ``model`` (a dense-attention stack without sliding windows)."""
        cfg = model.cfg
        kind = model._stack_kind()
        if kind != "mlp" or model.is_mla or model.n_dense:
            raise NotImplementedError(
                f"PagedRealExecutor supports dense-attention stacks only "
                f"(got stack kind {kind!r}); use executor='real'")
        if cfg.enc_dec or cfg.embeddings_input:
            raise NotImplementedError(
                "PagedRealExecutor does not support encoder/decoder or "
                "embedding-input models; use executor='real'")
        if any(cfg.layer_window(i) for i in range(cfg.n_layers)):
            raise NotImplementedError(
                "PagedRealExecutor does not support sliding-window layers "
                "(paged decode attends the whole table); use executor='real'")

    def compile_stats(self) -> Dict[str, int]:
        return self.buckets.compile_stats()

    # ------------------------------------------------------------------
    # engine attachment: pool sizing + allocator hooks
    # ------------------------------------------------------------------
    def attach_engine(self, engine) -> None:
        """Bind to the engine whose allocator drives this pool (called by
        ``Engine.__init__``). Sizes the physical pool from the engine's
        ``num_kv_blocks`` and registers the copy-on-write clone hook."""
        ecfg = engine.ecfg
        if ecfg.num_kv_blocks > self.MAX_POOL_BLOCKS:
            raise ValueError(
                f"paged executor would materialize {ecfg.num_kv_blocks} real "
                f"KV blocks (> {self.MAX_POOL_BLOCKS}); that default comes "
                "from the simulated device HBM budget — pass an explicit "
                "num_kv_blocks override (builders / ServeSpec "
                "--num-kv-blocks) sized for the real run")
        cfg = self.cfg
        self.page = ecfg.block_size
        self._trash = ecfg.num_kv_blocks
        shape = (self.model.n_stack, ecfg.num_kv_blocks + 1, cfg.n_kv_heads,
                 self.page, cfg.head_dim)
        # the pools live beside the weights, on the params' device
        (dev,) = jax.tree.leaves(self.params)[0].devices()
        self.k_pool = jnp.zeros(shape, self.model.dtype, device=dev)
        self.v_pool = jnp.zeros(shape, self.model.dtype, device=dev)
        self._engine = engine
        self._hook_allocator(engine.allocator)
        self._build_fns()

    def _hook_allocator(self, alloc) -> None:
        if alloc is self._allocator:
            return
        assert alloc.block_size == self.page, \
            "allocator block size changed under the paged pool"
        assert alloc.num_blocks <= self._trash, \
            "allocator grew past the physical pool"
        alloc.on_cow = self._clone_block
        # host-memory tier hooks: demotions copy the pool row out to host
        # DRAM before the allocator recycles it, promotions write it back
        alloc.on_demote = self._save_block
        alloc.on_promote = self._restore_block
        alloc.on_host_evict = self._drop_host
        self._allocator = alloc
        self._host_store: Dict[bytes, tuple] = {}

    def _save_block(self, blk: int, key: bytes) -> None:
        """Allocator demotion hook: the GPU row is about to be recycled —
        copy its K/V out to the modeled host store (fires while the row
        is still intact, before the block returns to the free list)."""
        self.buckets.record("host_demote", 1)
        self._host_store[key] = (np.asarray(self.k_pool[:, blk]),
                                 np.asarray(self.v_pool[:, blk]))

    def _restore_block(self, blk: int, key: bytes) -> None:
        """Allocator promotion hook: a host-resident chain got a prefix
        hit — write its K/V back into the newly assigned pool row."""
        k, v = self._host_store.pop(key)
        self.buckets.record("host_promote", 1)
        self.k_pool = self.k_pool.at[:, blk].set(jnp.asarray(k))
        self.v_pool = self.v_pool.at[:, blk].set(jnp.asarray(v))

    def _drop_host(self, key: bytes) -> None:
        """Allocator host-eviction hook (capacity pressure, or the GPU
        re-registered the same chain): forget the stored row."""
        self._host_store.pop(key, None)

    def _alloc(self):
        """The engine's CURRENT allocator (tests swap allocators to model
        migration; the pool follows, re-registering the CoW hook)."""
        self._hook_allocator(self._engine.allocator)
        return self._allocator

    def _req_id(self, slot: int) -> str:
        req = self._engine.slots[slot]
        assert req is not None, f"executor touched empty slot {slot}"
        return req.req_id

    def _clone_block(self, dst: int, src: int, n_tokens: int) -> None:
        """Allocator CoW hook: physically copy the first ``n_tokens`` rows
        of block ``src`` into ``dst`` (one [L, Kv, n, D] copy — the rest of
        ``dst`` is garbage until prefill/decode writes it)."""
        self.buckets.record("cow", 1)
        self.k_pool, self.v_pool = self._cow_fn(
            self.k_pool, self.v_pool, np.int32(dst), np.int32(src),
            np.int32(n_tokens))

    # ------------------------------------------------------------------
    # jitted forwards (built once per attach; XLA caches per bucket shape)
    # ------------------------------------------------------------------
    def _build_fns(self):
        from repro.kernels import ops
        from repro.models.layers import rmsnorm, swiglu
        from repro.models.rope import position_encode

        cfg = self.cfg
        model = self.model
        page = self.page
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        eps = cfg.norm_eps
        use_pallas = self.use_pallas

        def qkv(lp, x, positions):
            b, sq, _ = x.shape
            ap = lp["attn"]
            q = (x @ ap["wq"].astype(x.dtype)).reshape(b, sq, h, hd)
            k = (x @ ap["wk"].astype(x.dtype)).reshape(b, sq, kvh, hd)
            v = (x @ ap["wv"].astype(x.dtype)).reshape(b, sq, kvh, hd)
            if cfg.qk_norm:
                q = rmsnorm(q, ap["q_norm"], eps)
                k = rmsnorm(k, ap["k_norm"], eps)
            q = position_encode(q, positions, cfg)
            k = position_encode(k, positions, cfg)
            return q, k, v

        def write(pool, rows, write_idx):
            """Scatter token K/V rows into their pages.
            pool [P+1, Kv, page, D]; rows [n, Kv, D]; write_idx [n] flat
            slots (block_id * page + offset; trash for padded lanes)."""
            blk, off = write_idx // page, write_idx % page
            return pool.at[blk, :, off].set(rows.astype(pool.dtype))

        def gather(pool, table):
            """A table's pages in token order: [1, s, Kv, D]."""
            return pool[table].transpose(0, 2, 1, 3).reshape(1, -1, kvh, hd)

        def finish(x, params):
            x = rmsnorm(x, params["final_norm"], eps)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["head"])
            return x @ head.astype(x.dtype)

        def mlp(lp, x):
            h2 = rmsnorm(x, lp["ln2"], eps)
            return x + swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                              lp["mlp"]["w_down"])

        def prefill_fwd(params, k_pool, v_pool, tokens, positions,
                        write_idx, table, total):
            """tokens/positions [1, Cb] (-1-padded); write_idx [Cb] flat
            pool slots; table [Pb] page ids (trash-padded); total: scalar
            valid context length after this chunk."""
            x = params["embed"].astype(model.dtype)[tokens]
            s = table.shape[0] * page
            iota = jnp.arange(s, dtype=jnp.int32)
            kv_pos = jnp.where(iota < total, iota, -1)[None]

            def body(xc, xs):
                lp, kp, vp = xs
                hx = rmsnorm(xc, lp["ln1"], eps)
                q, k, v = qkv(lp, hx, positions)
                kp = write(kp, k[0], write_idx)
                vp = write(vp, v[0], write_idx)
                out = ops.chunked_prefill_attention(
                    q, gather(kp, table), gather(vp, table), positions,
                    kv_pos, window=0,
                    use_pallas=use_pallas)
                xc = xc + (out.reshape(out.shape[:2] + (h * hd,))
                           @ lp["attn"]["wo"].astype(xc.dtype))
                return mlp(lp, xc), (kp, vp)

            x, (k_new, v_new) = jax.lax.scan(
                body, x, (params["layers"], k_pool, v_pool))
            return finish(x, params), k_new, v_new

        def decode_fwd(params, k_pool, v_pool, tokens, positions,
                       write_idx, tables, ctx_lens):
            """tokens/positions/write_idx [Bb]; tables [Bb, Pb] (trash-
            padded); ctx_lens [Bb] (0 for padded lanes)."""
            x = params["embed"].astype(model.dtype)[tokens][:, None]
            pos2 = positions[:, None]

            def body(xc, xs):
                lp, kp, vp = xs
                hx = rmsnorm(xc, lp["ln1"], eps)
                q, k, v = qkv(lp, hx, pos2)
                kp = write(kp, k[:, 0], write_idx)
                vp = write(vp, v[:, 0], write_idx)
                out = ops.paged_decode_attention(
                    q[:, 0], kp, vp, tables, ctx_lens,
                    use_pallas=use_pallas)
                xc = xc + (out.reshape(out.shape[0], 1, h * hd)
                           @ lp["attn"]["wo"].astype(xc.dtype))
                return mlp(lp, xc), (kp, vp)

            x, (k_new, v_new) = jax.lax.scan(
                body, x, (params["layers"], k_pool, v_pool))
            return finish(x, params)[:, 0], k_new, v_new

        def cow_fwd(k_pool, v_pool, dst, src, n):
            keep = jnp.arange(page) < n

            def clone(pool):
                sel = keep.reshape(1, 1, page, 1)
                merged = jnp.where(sel, pool[:, src], pool[:, dst])
                return pool.at[:, dst].set(merged)

            return clone(k_pool), clone(v_pool)

        def inject_fwd(k_pool, v_pool, k_rows, v_rows, dst_idx):
            """k/v_rows [L, n, Kv, D] payload tokens; dst_idx [n] flat
            pool slots (trash-padded)."""
            blk, off = dst_idx // page, dst_idx % page

            def put(pool, rows):
                # advanced indices split by a slice lead the result: [n, L, Kv, D]
                return pool.at[:, blk, :, off].set(
                    rows.transpose(1, 0, 2, 3).astype(pool.dtype))

            return put(k_pool, k_rows), put(v_pool, v_rows)

        self._prefill_fn = jax.jit(prefill_fwd)
        self._decode_fn = jax.jit(decode_fwd)
        self._cow_fn = jax.jit(cow_fwd)
        self._inject_fn = jax.jit(inject_fwd)

    # ------------------------------------------------------------------
    # executor interface
    # ------------------------------------------------------------------
    def _flat_idx(self, table, pos: int) -> int:
        return table[pos // self.page] * self.page + pos % self.page

    def prefill_chunk(self, slot: int, tokens: np.ndarray, ctx_len: int,
                      completes: bool, enc_emb=None) -> Optional[int]:
        """Run one prefill chunk for ``slot`` through the request's block
        table. Returns the first output token if the prompt completes."""
        table = self._alloc().block_table(self._req_id(slot))
        page = self.page
        c = len(tokens)
        total = ctx_len + c
        cb = self.buckets.bucket(c, lo=16)
        pb = self.buckets.bucket(math.ceil(total / page), lo=4)
        assert len(table) * page >= total, "block table behind context"

        tok = np.zeros((1, cb), np.int32)
        tok[0, :c] = tokens
        pos = np.full((1, cb), -1, np.int32)
        pos[0, :c] = ctx_len + np.arange(c)
        widx = np.full((cb,), self._trash * page, np.int32)
        for j in range(c):
            widx[j] = self._flat_idx(table, ctx_len + j)
        tbl = np.full((pb,), self._trash, np.int32)
        take = min(len(table), pb)
        tbl[:take] = table[:take]

        self.buckets.record("prefill", cb, pb)
        logits, self.k_pool, self.v_pool = self._prefill_fn(
            self.params, self.k_pool, self.v_pool, tok, pos, widx, tbl,
            np.int32(total))
        if completes:
            return robust_greedy(logits[0, c - 1])
        return None

    def decode(self, slot_tokens: Dict[int, int],
               slot_lens: Dict[int, int]) -> Dict[int, int]:
        """One decode step over the active slots' block tables."""
        alloc = self._alloc()
        page = self.page
        slots = sorted(slot_tokens)
        tables = [alloc.block_table(self._req_id(s)) for s in slots]
        n = len(slots)
        bb = self.buckets.bucket(n, lo=4)
        pb = self.buckets.bucket(
            max(math.ceil((slot_lens[s] + 1) / page) for s in slots), lo=4)

        tok = np.zeros((bb,), np.int32)
        pos = np.zeros((bb,), np.int32)
        widx = np.full((bb,), self._trash * page, np.int32)
        tbl = np.full((bb, pb), self._trash, np.int32)
        ctx = np.zeros((bb,), np.int32)
        for i, s in enumerate(slots):
            p = slot_lens[s]
            tok[i] = slot_tokens[s]
            pos[i] = p
            widx[i] = self._flat_idx(tables[i], p)
            take = min(len(tables[i]), pb)
            tbl[i, :take] = tables[i][:take]
            ctx[i] = p + 1

        self.buckets.record("decode", bb, pb)
        logits, self.k_pool, self.v_pool = self._decode_fn(
            self.params, self.k_pool, self.v_pool, tok, pos, widx, tbl, ctx)
        tracer = self._engine.tracer
        if tracer is None:
            return {s: robust_greedy(logits[i]) for i, s in enumerate(slots)}
        # traced: wait for the step on its own, so that the row reads
        # after it time only the host round trips
        track = self._engine.trace_track
        # the pages the decode kernel copies, against the padded table
        live = sum(math.ceil(c / page) for c in ctx[:n].tolist())
        with tracer.span(track, "decode.wait", live_pages=live,
                         table_pages=bb * pb):
            logits.block_until_ready()
        with tracer.span(track, "readback", rows=n):
            return {s: robust_greedy(logits[i]) for i, s in enumerate(slots)}

    # ------------------------------------------------------------------
    # KV handoff: block-granular, sized by the partial prefill
    # ------------------------------------------------------------------
    def extract_kv(self, slot: int, upto: int):
        """PPI->CPI payload: only the ``ceil(upto / page)`` blocks covering
        the partial prefill travel (honest transfer accounting — the slot
        executor used to ship the full padded slot width)."""
        table = self._alloc().block_table(self._req_id(slot))
        nblk = math.ceil(upto / self.page)
        idx = jnp.asarray(table[:nblk], jnp.int32)
        # token-major pages [L, nblk, page, Kv, D]: the payload layout does
        # not depend on how the pool orders heads and tokens
        return {"k_pages": self.k_pool[:, idx].transpose(0, 1, 3, 2, 4),
                "v_pages": self.v_pool[:, idx].transpose(0, 1, 3, 2, 4),
                "_upto": upto, "_page": self.page}

    def inject_kv(self, slot: int, payload, upto: int):
        """Scatter a transferred payload into the blocks this engine's
        allocator assigned. Positions the local prefix cache already
        covers (``allocator.shared_tokens``) are skipped: shared blocks
        are immutable, and their content is already resident."""
        alloc = self._alloc()
        assert payload["_page"] == self.page, \
            "page-size mismatch across a paged handoff"
        req_id = self._req_id(slot)
        table = alloc.block_table(req_id)
        shared = (alloc.shared_tokens(req_id)
                  if hasattr(alloc, "shared_tokens") else 0)
        p_upto = int(payload["_upto"])
        start = min(shared, p_upto)
        n = p_upto - start
        if n <= 0:
            return
        nb = self.buckets.bucket(n, lo=self.page)
        l_dim, _, kvh, _, hd = self.k_pool.shape
        k_rows = np.zeros((l_dim, nb, kvh, hd), np.asarray(
            payload["k_pages"]).dtype)
        v_rows = np.zeros_like(k_rows)
        src_k = np.asarray(payload["k_pages"]).reshape(l_dim, -1, kvh, hd)
        src_v = np.asarray(payload["v_pages"]).reshape(l_dim, -1, kvh, hd)
        k_rows[:, :n] = src_k[:, start:p_upto]
        v_rows[:, :n] = src_v[:, start:p_upto]
        dst = np.full((nb,), self._trash * self.page, np.int32)
        for j in range(n):
            dst[j] = self._flat_idx(table, start + j)
        self.buckets.record("inject", nb)
        self.k_pool, self.v_pool = self._inject_fn(
            self.k_pool, self.v_pool, k_rows, v_rows, dst)

    def reset_slot(self, slot: int):
        """Nothing to scrub: validity lives in the allocator's tables and
        per-request context lengths, not in pool contents."""
