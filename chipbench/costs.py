"""Operations and bytes that the served work needs, from live shapes.

Counts follow the requests' own lengths, never the padded bucket shapes
the program compiles, so the same work counts the same whatever
implements it, and padding shows as a lower share of the roofline.
A multiply-add counts as two operations. Norms, rotary embeddings and
softmax are left out (well under 1% of a step at these widths).

The architecture's own counts (the weights a token multiplies through,
attention's operations and bytes) are its family's
(``chipbench/families/<family>.py``); the steps here are built on them.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

from chipbench import families

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def head_flops(d: dict) -> int:
    """Output projection of one token."""
    return 2 * d["d_model"] * d["vocab_size"]


def prefill_step_flops(d: dict, chunk: int, ctx: int, completes: bool) -> int:
    """A prefill chunk: every layer for every new token, and the output
    head only for the one token that is served (when the prompt ends)."""
    fam = families.of(d)
    return (2 * chunk * fam.matmul_params_per_token(d)
            + fam.prefill_attn_flops(d, chunk, ctx)
            + (head_flops(d) if completes else 0))


def decode_step_flops(d: dict, ctxs: Iterable[int]) -> int:
    """A decode step over live requests with context lengths ``ctxs``."""
    fam = families.of(d)
    per_token = 2 * fam.matmul_params_per_token(d) + head_flops(d)
    return sum(per_token + fam.decode_attn_flops(d, c) for c in ctxs)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
