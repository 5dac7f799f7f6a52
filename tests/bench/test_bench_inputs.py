"""Traffic and weights the benchmark makes from its seed."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic, weights
from chipbench.families import dense

MIX = json.loads((Path(__file__).resolve().parents[2] / "chipbench"
                  / "traffic" / "cronus.conv.json").read_text())
TINY = {"family": "dense", "d_model": 16, "n_layers": 3, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 4, "d_ff": 32, "vocab_size": 64,
        "qk_norm": True, "tie_embeddings": False}


def _plan(seed):
    return traffic.plan(MIX, rate=2.0, warm_s=10.0, window_s=30.0,
                        seed=seed, vocab_size=1000)


def _sizes(plan, in_window):
    return sorted((len(p.prompt), p.output_len) for p in plan
                  if p.in_window == in_window)


def test_every_seed_offers_the_same_work_in_another_order():
    a, b = _plan(1), _plan(2 ** 31 + 11)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    for part in (True, False):
        assert _sizes(a, part) == _sizes(b, part)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert all(len(p.prompt) <= MIX["input_len"]["max"] for p in a)


def test_same_seed_same_inputs():
    a, b = _plan(5), _plan(5)
    assert all(np.array_equal(x.prompt, y.prompt) and
               x.output_len == y.output_len for x, y in zip(a, b))


def test_reference_remakes_the_served_weights_bit_for_bit():
    params = dense.make_params(2 ** 31 + 3, TINY, jax.devices()[0])
    key = weights.seed_key(2 ** 31 + 3)
    for layer in range(TINY["n_layers"]):
        alone = jax.jit(lambda l: dense.layer_weights(key, l, TINY))(layer)
        stacked = jax.tree.map(lambda a: a[layer], params["layers"])
        assert jax.tree.all(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), alone, stacked))
    top = jax.jit(lambda: dense.top_weights(key, TINY))()
    assert bool(jnp.array_equal(top["head"], params["head"]))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
