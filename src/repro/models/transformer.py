"""Decoder-only model covering dense / MoE / SSM / hybrid / VLM families.

Layers are *stacked* (leading L dim on every param leaf) and executed with
``lax.scan`` so that the HLO (and compile time) is O(1) in depth — essential
for the 60+-layer full configs in the multi-pod dry-run. Heterogeneous
layers (MoE models' leading dense layers) live in a second, separately
stacked scan. Per-layer attention-window sizes ride along the scan as an
int32 array, so gemma3's 5:1 local:global pattern costs nothing extra.

One forward serves four modes:
  * train (no cache; attention over in-sequence k,v only),
  * full/partial prefill (writes into the cache),
  * chunked prefill continuation (queries attend to cache context + chunk),
  * decode (S=1; SSM uses the recurrent step).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (dense_init, init_mlp, init_rmsnorm, rmsnorm,
                                 swiglu)
from repro.models.sharding import maybe_shard


class DecoderModel:
    """Functional model; all state passes through explicitly."""

    def __init__(self, cfg, *, exact_moe: bool = False,
                 window_override: Optional[int] = None, remat: bool = True,
                 scan_unroll: bool = False, decode_write: str = "select"):
        self.cfg = cfg
        self.exact_moe = exact_moe
        self.remat = remat
        self.scan_unroll = scan_unroll  # unroll layer scans (cost calibration)
        # decode-step cache write strategy: "scatter" pairs with head-dim-
        # sharded decode caches (O(1) write bytes); "select" tolerates
        # sequence-sharded caches (see attention.scatter_tokens)
        self.decode_write = decode_write
        self.n_dense = cfg.moe_dense_layers if cfg.is_moe else 0
        self.n_stack = cfg.n_layers - self.n_dense
        if window_override is not None:
            widths = [window_override] * cfg.n_layers
        else:
            widths = [cfg.layer_window(i) for i in range(cfg.n_layers)]
        self.widths_dense = jnp.array(widths[: self.n_dense], jnp.int32)
        self.widths_stack = jnp.array(widths[self.n_dense:], jnp.int32)
        self.is_mla = cfg.mla_kv_lora_rank > 0
        self.attn_keys = ("ckv", "kpe") if self.is_mla else ("k", "v")
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def _stack_kind(self) -> str:
        cfg = self.cfg
        if cfg.arch_type == "ssm":
            return "ssm"
        if cfg.hybrid:
            return "hybrid"
        if cfg.is_moe:
            return "moe"
        return "mlp"

    def _init_layer(self, key, kind: str):
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p = {"ln1": init_rmsnorm(cfg.d_model)}
        if kind == "ssm":
            p["ssm"] = ssm_mod.init_ssm(ks[0], cfg)
            return p
        p["attn"] = attn.init_attention(ks[0], cfg)
        if kind == "hybrid":
            p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
            p["ln_attn_out"] = init_rmsnorm(cfg.d_model)
            p["ln_ssm_out"] = init_rmsnorm(cfg.d_model)
        p["ln2"] = init_rmsnorm(cfg.d_model)
        if kind == "moe":
            p["moe"] = moe_mod.init_moe(ks[2], cfg)
        elif kind == "dense_mlp":
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.moe_dense_d_ff or cfg.d_ff)
        else:
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff)
        return p

    def _init_top(self, ks):
        cfg = self.cfg
        params = {
            "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), scale=0.02),
            "final_norm": init_rmsnorm(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size))
        return params

    def init_params(self, key, dtype=jnp.float32, device=None):
        """Weights with every leaf in ``dtype``: fp32 master weights by
        default (training, CPU functional tests); a server passes the
        model's dtype (``self.dtype``).

        One jitted program makes the stacked layers one at a time
        (``lax.map``) and casts each tensor as it is made, so the peak
        holds neither an fp32 copy of a bf16 model nor a second stacked
        copy. ``device`` places the result (default: JAX's default
        device)."""
        cfg = self.cfg
        kind = self._stack_kind()

        def cast(tree):
            return jax.tree.map(lambda a: a.astype(dtype), tree)

        def build(key):
            ks = jax.random.split(key, 2 + cfg.n_layers)
            params = cast(self._init_top(ks))
            if self.n_dense:
                params["dense_layers"] = jax.lax.map(
                    lambda k: cast(self._init_layer(k, "dense_mlp")),
                    ks[2:2 + self.n_dense])
            params["layers"] = jax.lax.map(
                lambda k: cast(self._init_layer(k, kind)),
                ks[2 + self.n_dense:])
            return params

        out = None if device is None else SingleDeviceSharding(device)
        return jax.jit(build, out_shardings=out)(key)

    # ------------------------------------------------------------------
    # cache
    # ------------------------------------------------------------------
    def _attn_layer_cache(self, n: int, batch: int, s_kv: int):
        cfg = self.cfg
        if self.is_mla:
            return {
                "ckv": jnp.zeros((n, batch, s_kv, cfg.mla_kv_lora_rank), self.dtype),
                "kpe": jnp.zeros((n, batch, s_kv, cfg.mla_rope_head_dim), self.dtype),
            }
        return {
            "k": jnp.zeros((n, batch, s_kv, cfg.n_kv_heads, cfg.head_dim), self.dtype),
            "v": jnp.zeros((n, batch, s_kv, cfg.n_kv_heads, cfg.head_dim), self.dtype),
        }

    def _ssm_layer_cache(self, n: int, batch: int):
        d_inner, h, p, nst, conv_dim = ssm_mod.ssm_dims(self.cfg)
        return {
            "h": jnp.zeros((n, batch, h, p, nst), jnp.float32),
            "conv": jnp.zeros((n, batch, self.cfg.ssm_conv_width - 1, conv_dim),
                              self.dtype),
        }

    def init_cache(self, batch: int, s_kv: int):
        kind = self._stack_kind()
        cache = {"pos": jnp.full((batch, max(s_kv, 1)), -1, jnp.int32)}
        stack = {}
        if kind in ("mlp", "moe", "hybrid"):
            stack.update(self._attn_layer_cache(self.n_stack, batch, s_kv))
        if kind in ("ssm", "hybrid"):
            stack.update(self._ssm_layer_cache(self.n_stack, batch))
        cache["stack"] = stack
        if self.n_dense:
            cache["dense"] = self._attn_layer_cache(self.n_dense, batch, s_kv)
        return cache

    def _dummy_cache(self, kind: str, n: int, batch: int):
        """Per-layer state for the cache-free training path."""
        if kind in ("ssm", "hybrid"):
            return self._ssm_layer_cache(n, batch)
        return {"_none": jnp.zeros((n,), jnp.float32)}

    # ------------------------------------------------------------------
    # one layer
    # ------------------------------------------------------------------
    def _layer(self, kind, lp, x, positions, kv_pos, idx, lc, width, decode, aux):
        cfg = self.cfg
        cache_free = idx is None
        token_mask = None if cache_free else positions >= 0
        if kind == "ssm":
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
            out, new_ssm = ssm_mod.ssm_block(
                lp["ssm"], cfg, h, {"h": lc["h"], "conv": lc["conv"]},
                decode=decode, token_mask=token_mask)
            return x + out, new_ssm, aux

        fn = attn.mla_attention_block if self.is_mla else attn.attention_block
        attn_lc = None if cache_free else {k: lc[k] for k in self.attn_keys}
        wmode = self.decode_write if decode else None
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if kind == "hybrid":
            a_out, new_kv = fn(lp["attn"], cfg, h, positions, kv_pos, idx,
                               attn_lc, width, write_mode=wmode)
            s_out, new_ssm = ssm_mod.ssm_block(
                lp["ssm"], cfg, h, {"h": lc["h"], "conv": lc["conv"]},
                decode=decode, token_mask=token_mask)
            mixed = 0.5 * (rmsnorm(a_out, lp["ln_attn_out"], cfg.norm_eps)
                           + rmsnorm(s_out, lp["ln_ssm_out"], cfg.norm_eps))
            x = x + mixed
            new_lc = {**(new_kv or {}), **new_ssm}
        else:
            a_out, new_kv = fn(lp["attn"], cfg, h, positions, kv_pos, idx,
                               attn_lc, width, write_mode=wmode)
            x = x + a_out
            new_lc = new_kv or {}

        h2 = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if kind == "moe":
            m_out, a = moe_mod.moe_block(lp["moe"], cfg, h2, exact=self.exact_moe)
            aux = aux + a
        else:
            m_out = swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"])
        return x + m_out, new_lc, aux

    # ------------------------------------------------------------------
    # stacked-scan runner
    # ------------------------------------------------------------------
    def _run_stack(self, kind, stacked, widths, x, positions, kv_pos, idx,
                   stack_cache, decode, aux, train):
        def body(carry, xs):
            xc, auxc = carry
            lp, lc, width = xs
            xn, new_lc, auxn = self._layer(kind, lp, xc, positions, kv_pos,
                                           idx, lc, width, decode, auxc)
            return (xn, auxn), (0.0 if train else new_lc)

        if train and self.remat:
            body = jax.checkpoint(body)
        (x, aux), new_cache = jax.lax.scan(
            body, (x, aux), (stacked, stack_cache, widths),
            unroll=True if self.scan_unroll else 1)
        return x, (None if train else new_cache), aux

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def embed_inputs(self, params, inputs):
        if inputs.ndim == 3:  # precomputed embeddings (audio / vlm frontend stub)
            return inputs.astype(self.dtype)
        return params["embed"].astype(self.dtype)[inputs]

    def forward(self, params, inputs, cache, cache_len, *, positions=None,
                kv_positions=None, decode: bool = False, train: bool = False):
        """inputs: tokens [B,S] int32 or embeddings [B,S,d].
        ``kv_positions`` [B,S_kv]: host-managed post-write cache positions
        (serving engines); if None the cache's own position buffer is used.
        Returns (logits [B,S,V], new_cache, aux)."""
        cfg = self.cfg
        kind = self._stack_kind()
        x = self.embed_inputs(params, inputs)
        x = maybe_shard(x, "batch", "seq", None)
        b, s, _ = x.shape
        if positions is None:
            positions = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

        aux = jnp.zeros((), jnp.float32)
        if train:
            kv_pos, idx = positions, None
            dense_cache = self._dummy_cache("mlp", self.n_dense, b)
            stack_cache = self._dummy_cache(kind, self.n_stack, b)
        else:
            s_kv = cache["pos"].shape[1]
            idx = attn.write_indices(cache_len, s, s_kv)
            if kv_positions is None:
                kv_pos = attn.scatter_tokens(cache["pos"], positions, idx)
            else:
                kv_pos = kv_positions
            stack_cache = cache["stack"]
            dense_cache = cache.get("dense")

        new_cache = None if train else {"pos": kv_pos}
        if self.n_dense:
            x, new_dense, aux = self._run_stack(
                "dense_mlp", params["dense_layers"], self.widths_dense, x,
                positions, kv_pos, idx, dense_cache, decode, aux, train)
            if not train:
                new_cache["dense"] = new_dense
        x, new_stack, aux = self._run_stack(
            kind, params["layers"], self.widths_stack, x, positions, kv_pos,
            idx, stack_cache, decode, aux, train)
        if not train:
            new_cache["stack"] = new_stack

        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = x @ head.astype(x.dtype)
        logits = maybe_shard(logits, "batch", "seq", "vocab")
        return logits, new_cache, aux

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def loss(self, params, batch):
        """batch: {'tokens': [B,S+1]} or {'embeddings': [B,S,d], 'labels': [B,S]}."""
        if "embeddings" in batch:
            inputs, labels = batch["embeddings"], batch["labels"]
        else:
            inputs, labels = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        b = inputs.shape[0]
        logits, _, aux = self.forward(params, inputs, None,
                                      jnp.zeros((b,), jnp.int32), train=True)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        loss = nll.mean()
        if self.cfg.is_moe:
            loss = loss + 0.01 * aux / max(self.n_stack, 1)
        return loss
