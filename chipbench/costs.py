"""Operations and bytes that the served work needs, from live shapes.

Counts follow the requests' own lengths, never the padded bucket shapes
the program compiles, so the same work counts the same whatever
implements it, and padding shows as a lower share of the roofline.
A multiply-add counts as two operations. Norms, rotary embeddings and
softmax are left out (well under 1% of a step at these widths).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(d: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    dm, h, kv, hd, f = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                        d["head_dim"], d["d_ff"])
    return dm * h * hd + 2 * dm * kv * hd + h * hd * dm + 3 * dm * f


def head_flops(d: dict) -> int:
    """Output projection of one token."""
    return 2 * d["d_model"] * d["vocab_size"]


def prefill_attn_flops(d: dict, chunk: int, ctx: int) -> int:
    """Causal attention of ``chunk`` new tokens after ``ctx`` cached ones,
    all layers: QK^T and PV over the keys each query may see."""
    keys_seen = chunk * ctx + chunk * (chunk + 1) // 2
    return d["n_layers"] * 4 * d["n_heads"] * d["head_dim"] * keys_seen


def prefill_attn_bytes(d: dict, chunk: int, ctx: int, itemsize: int = 2) -> int:
    """K and V of the whole context read once per KV head, queries read
    and outputs written once, all layers."""
    kv = (ctx + chunk) * d["n_kv_heads"] * d["head_dim"] * 2
    q_out = 2 * chunk * d["n_heads"] * d["head_dim"]
    return d["n_layers"] * (kv + q_out) * itemsize


def decode_attn_flops(d: dict, ctx: int) -> int:
    """One query over ``ctx`` keys (its own included), all layers."""
    return d["n_layers"] * 4 * d["n_heads"] * d["head_dim"] * ctx


def decode_attn_bytes(d: dict, ctx: int, itemsize: int = 2) -> int:
    kv = ctx * d["n_kv_heads"] * d["head_dim"] * 2
    q_out = 2 * d["n_heads"] * d["head_dim"]
    return d["n_layers"] * (kv + q_out) * itemsize


def prefill_step_flops(d: dict, chunk: int, ctx: int, completes: bool) -> int:
    """A prefill chunk: every layer for every new token, and the output
    head only for the one token that is served (when the prompt ends)."""
    return (2 * chunk * d["n_layers"] * layer_matmul_params(d)
            + prefill_attn_flops(d, chunk, ctx)
            + (head_flops(d) if completes else 0))


def decode_step_flops(d: dict, ctxs: Iterable[int]) -> int:
    """A decode step over live requests with context lengths ``ctxs``."""
    per_token = 2 * d["n_layers"] * layer_matmul_params(d) + head_flops(d)
    return sum(per_token + decode_attn_flops(d, c) for c in ctxs)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
