"""FLOP and byte counters of the benchmark against hand-worked values:
the dense family's own counts, and the steps built on them."""
import pytest

from chipbench import costs
from chipbench.families import dense

# a small model whose numbers can be worked by hand
D = {"family": "dense", "d_model": 8, "n_layers": 2, "n_heads": 4,
     "n_kv_heads": 2, "head_dim": 2, "d_ff": 16, "vocab_size": 10}
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_layer_matmul_params():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 MLP matrices
    assert dense.layer_matmul_params(D) == 64 + 32 + 32 + 64 + 384
    assert dense.matmul_params_per_token(D) == 2 * 576


def test_prefill_attention():
    # chunk 3 after 2 cached tokens: queries see 3, 4 and 5 keys
    assert dense.prefill_attn_flops(D, 3, 2) == 2 * 4 * 4 * 2 * (3 + 4 + 5)
    # K and V of 5 tokens x 2 heads x dim 2, q and out of 3 x 4 x 2, bf16
    assert dense.prefill_attn_bytes(D, 3, 2) == 2 * (5 * 2 * 2 * 2 + 2 * 3 * 8) * 2


def test_decode_attention():
    assert dense.decode_attn_flops(D, 7) == 2 * 4 * 4 * 2 * 7
    assert dense.decode_attn_bytes(D, 7) == 2 * (7 * 2 * 2 * 2 + 2 * 8) * 2


def test_step_flops():
    per_token = 2 * 2 * 576
    head = 2 * 8 * 10
    assert costs.prefill_step_flops(D, 3, 2, False) == (
        3 * per_token + dense.prefill_attn_flops(D, 3, 2))
    assert costs.prefill_step_flops(D, 3, 2, True) == (
        3 * per_token + dense.prefill_attn_flops(D, 3, 2) + head)
    assert costs.decode_step_flops(D, [1, 4]) == (
        2 * (per_token + head) + dense.decode_attn_flops(D, 5))


@pytest.mark.parametrize("flops,nbytes,want", [
    (1000.0, 10.0, (10.0, "flops")),
    (10.0, 1000.0, (100.0, "bytes")),
])
def test_least_time_names_its_bound(flops, nbytes, want):
    assert costs.least_time(flops, nbytes, PEAK) == want


def test_peaks_table_knows_v5e_and_refuses_the_rest():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
