"""Mixture-of-Experts: top-k router + capacity-based sort/gather dispatch.

Design notes (TPU adaptation)
-----------------------------
Dispatch uses argsort + capacity gather into an ``[E, C, d]`` buffer followed
by batched expert matmuls ``ecd,edf->ecf``. This gives *active-FLOPs-exact*
cost accounting (matmul FLOPs = topk * T * cf * d * f * 6), unlike dense
one-hot dispatch (which would overcount by E/topk). With tokens sharded on
the 'data' axis and experts sharded on the 'model' axis, the gather/scatter
between the two layouts lowers to all-to-all-style collectives under GSPMD —
the expert-parallel pattern.

``exact`` mode sets capacity C = T (no token can be dropped since each token
routes to an expert at most once) — used by the functional serving engine
and smoke tests, where bit-exact routing matters more than peak efficiency.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, init_mlp, swiglu
from repro.models.sharding import maybe_shard


def init_moe(key, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 3 + cfg.n_shared_experts)
    p = {
        "router": dense_init(ks[0], (d, e), scale=d ** -0.5),
        "w_gate": dense_init(ks[1], (e, d, f)),
        "w_up": dense_init(ks[2], (e, d, f)),
        "w_down": dense_init(jax.random.fold_in(ks[2], 1), (e, f, d)),
    }
    for i in range(cfg.n_shared_experts):
        p[f"shared_{i}"] = init_mlp(ks[3 + i], d, f)
    return p


def _capacity(t: int, cfg, exact: bool) -> int:
    if exact:
        return t
    c = int(t * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(min(t, c), min(t, 8))


def moe_block(p, cfg, x, exact: bool = False):
    """x [B,S,d] -> (out [B,S,d], aux_loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, k)                         # [T,k]
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)    # renorm

    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(0)                                                  # [E]
    ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # §Perf HC1-2: under a mesh with expert parallelism, dispatch through
    # shard_map — every scatter/gather becomes device-LOCAL (GSPMD cannot
    # shard data-dependent scatters and all-gathers the [E,C,d] buffers:
    # measured 598 s collective on kimi-k2 prefill_32k).
    from repro.models.sharding import get_mesh, get_rules
    mesh, rules = get_mesh(), get_rules()
    use_shardmap = mesh is not None and rules and rules.get("experts") \
        and not exact
    if use_shardmap:
        b_ax = rules.get("batch")
        n_b = 1
        for a_ in (b_ax if isinstance(b_ax, (tuple, list)) else (b_ax,)):
            n_b *= mesh.shape[a_]
        # tokens must split evenly over the batch axes (single-token decode
        # steps, e.g. long_500k with batch 1, fall back to GSPMD dispatch)
        use_shardmap = t % n_b == 0 and t >= n_b
    if use_shardmap:
        out = _moe_dispatch_shardmap(p, cfg, xt, gate_w, gate_idx, mesh,
                                     rules)
        for i in range(cfg.n_shared_experts):
            sp = p[f"shared_{i}"]
            out = out + swiglu(xt, sp["w_gate"], sp["w_up"], sp["w_down"])
        return out.reshape(b, s, d), aux

    # ---- dispatch: sort (token,expert) pairs by expert ------------------
    cap = _capacity(t, cfg, exact)
    flat_e = gate_idx.reshape(-1)                                       # [T*k]
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)              # [T*k]
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # rank within expert group = position - start-of-group
    pos = jnp.arange(t * k, dtype=jnp.int32)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    group_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(counts)[:-1]])
    rank = pos - group_start[se]
    keep = rank < cap
    slot_e = jnp.where(keep, se, 0)
    slot_c = jnp.where(keep, rank, cap - 1)

    buf = jnp.zeros((e, cap, d), xt.dtype)
    buf = buf.at[slot_e, slot_c].add(jnp.where(keep[:, None], xt[st], 0))
    buf = maybe_shard(buf, "experts", None, None)

    # ---- expert compute (E sharded on 'model' under pjit) ---------------
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(xt.dtype)))
         * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(xt.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(xt.dtype))
    out_buf = maybe_shard(out_buf, "experts", None, None)

    # ---- combine ---------------------------------------------------------
    gathered = out_buf[slot_e, slot_c]                                  # [T*k, d]
    contrib = jnp.where(keep[:, None], gathered * sw[:, None].astype(xt.dtype), 0)
    out = jnp.zeros((t, d), xt.dtype).at[st].add(contrib)

    for i in range(cfg.n_shared_experts):
        sp = p[f"shared_{i}"]
        out = out + swiglu(xt, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out.reshape(b, s, d), aux


def _moe_dispatch_shardmap(p, cfg, xt, gate_w, gate_idx, mesh, rules):
    """Expert-parallel dispatch via shard_map (§Perf HC1-2).

    Layout: tokens are sharded over the batch axes and REPLICATED over the
    expert ('model') axis, so no token movement is needed at all: each
    (data i, model j) device routes data-block i's tokens to its LOCAL
    experts with device-local sort/scatter/gather, and the per-expert-shard
    partial outputs combine with one psum over the expert axis — the only
    collective this MoE layer needs (vs GSPMD all-gathering [E,C,d]
    dispatch buffers)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    t = xt.shape[0]
    b_ax = rules.get("batch")
    m_ax = rules.get("experts")
    n_exp_shards = mesh.shape[m_ax]
    e_loc = e // n_exp_shards
    n_b = 1
    for a in (b_ax if isinstance(b_ax, (tuple, list)) else (b_ax,)):
        n_b *= mesh.shape[a]
    t_loc = t // n_b
    cap = max(min(t_loc, int(t_loc * k * cfg.capacity_factor / e) + 1),
              min(t_loc, 8))

    def local(xl, gw, gi, wg, wu, wd):
        # xl [T_loc, d]; gw/gi [T_loc, k]; wg/wu [E_loc, d, f]; wd [E_loc, f, d]
        j = jax.lax.axis_index(m_ax)
        e0 = j * e_loc
        flat_e = gi.reshape(-1) - e0                       # [T_loc*k]
        flat_t = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
        flat_w = gw.reshape(-1)
        local_sel = (flat_e >= 0) & (flat_e < e_loc)
        le = jnp.where(local_sel, flat_e, e_loc)           # bucket E_loc = misc
        order = jnp.argsort(le, stable=True)
        se, st, sw = le[order], flat_t[order], flat_w[order]
        sel = se < e_loc
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[le].add(1)
        group_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                       jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(t_loc * k, dtype=jnp.int32) - group_start[se]
        keep = sel & (rank < cap)
        slot_e = jnp.where(keep, se, 0)
        slot_c = jnp.where(keep, rank, cap - 1)
        buf = jnp.zeros((e_loc, cap, d), xl.dtype)
        buf = buf.at[slot_e, slot_c].add(
            jnp.where(keep[:, None], xl[st], 0))
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xl.dtype)))
             * jnp.einsum("ecd,edf->ecf", buf, wu.astype(xl.dtype)))
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(xl.dtype))
        gathered = out_buf[slot_e, slot_c]
        contrib = jnp.where(keep[:, None],
                            gathered * sw[:, None].astype(xl.dtype), 0)
        out = jnp.zeros((t_loc, d), xl.dtype).at[st].add(contrib)
        return jax.lax.psum(out, m_ax)                     # combine shards

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(b_ax, None), P(b_ax, None), P(b_ax, None),
                  P(m_ax, None, None), P(m_ax, None, None),
                  P(m_ax, None, None)),
        out_specs=P(b_ax, None))
    return fn(xt, gate_w.astype(xt.dtype), gate_idx,
              p["w_gate"], p["w_up"], p["w_down"])


def moe_block_dense_ref(p, cfg, x):
    """Oracle: dense (all-experts) routing, exact combine. O(T*E*d*f)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)
    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)
    w = jnp.zeros_like(probs).at[jnp.arange(xt.shape[0])[:, None], gate_idx].set(gate_w)
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"].astype(xt.dtype)))
         * jnp.einsum("td,edf->tef", xt, p["w_up"].astype(xt.dtype)))
    y = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(xt.dtype))
    out = jnp.einsum("ted,te->td", y, w.astype(xt.dtype))
    for i in range(cfg.n_shared_experts):
        sp = p[f"shared_{i}"]
        out = out + swiglu(xt, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out.reshape(b, s, d)
