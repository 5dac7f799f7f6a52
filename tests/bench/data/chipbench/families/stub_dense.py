"""A family for the tests, found only under ``tests/bench/data``: the dense
family, with a warm-up that records each call and one more array of
device state on each executor, as a family whose executor holds more than
its pools would have."""
import jax.numpy as jnp

from chipbench.families import dense
from chipbench.families.dense import (  # noqa: F401
    decode_attn_bytes, decode_attn_flops, dims, layer_weights, make_params,
    matmul_params_per_token, model_config, prefill_attn_bytes,
    prefill_attn_flops, reference_layer, top_weights)

WARMED = []     # (prefill_only, decode_only, phases) per call
EXTRA = []      # the extra arrays, one per executor warmed


def warm_phases(ex, ecfg, mix):
    phases = dense.warm_phases(ex, ecfg, mix)
    WARMED.append((ecfg.prefill_only, ecfg.decode_only, sorted(phases)))
    pool = dense.device_state(ex)[0]
    ex.stub_extra = jnp.ones((4,), jnp.float32, device=pool.sharding)
    EXTRA.append(ex.stub_extra)
    return phases


def device_state(ex):
    return dense.device_state(ex) + (ex.stub_extra,)
