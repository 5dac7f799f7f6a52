"""The end-to-end and host-side readers on hand-made host-clock records."""
import types

import numpy as np
import pytest

from chipbench import harness, run
from chipbench.families import paged


def _run():
    rec = types.SimpleNamespace(
        due={"a": 10.0, "b": 12.0, "c": 5.0, "late": 18.0},
        sent={"a": 10.001, "b": 12.003, "c": 5.0, "late": 18.002},
        stamps={"a": [10.5, 10.6, 10.8], "b": [13.0], "c": [6.0, 10.2]},
        calls=[harness.Call("decode", "cpi", 11.0, 11.02, (5, 6)),
               harness.Call("decode", "cpi", 12.0, 12.04, (7,)),
               harness.Call("decode", "cpi", 9.0, 9.5, (7,))],
        kv_share=[(9.0, 0.9), (11.0, 0.25), (15.0, 0.5)],
        compiles=[(9.5, "compile"), (12.5, "cache_load")])
    reqs = [types.SimpleNamespace(req_id="a", output_len=3, partial_len=60,
                                  input_len=100),
            types.SimpleNamespace(req_id="b", output_len=4, partial_len=10,
                                  input_len=50),
            types.SimpleNamespace(req_id="c", output_len=2, partial_len=0,
                                  input_len=70),
            types.SimpleNamespace(req_id="late", output_len=2,
                                  partial_len=0, input_len=10)]
    data = run.RunData.__new__(run.RunData)
    data.rec, data.reqs = rec, {r.req_id: r for r in reqs}
    data.t_open, data.t_close, data.setup_s = 10.0, 20.0, 42.0
    data.window_ids = [r for r, t in rec.due.items() if 10.0 <= t < 20.0]
    data.has_pair, data.trace, data.hbm_peak_bytes = True, None, 13e9
    return data


def read(name):
    return run.read_metric(name, _run())


def test_ttft_counts_a_missing_first_token_at_its_wait():
    # a: 0.5, b: 1.0, late: no token by the close -> 2.0
    assert read("ttft_p90_s") == pytest.approx(
        np.percentile([0.5, 1.0, 2.0], 90))


def test_backlog_counts_requests_sent_without_a_first_token():
    rec = _run().rec
    assert [harness.backlog(rec, t) for t in (5.5, 10.2, 12.5, 19.0)] == [
        1, 1, 1, 1]
    assert harness.backlog(rec, 4.0) == 0
    assert harness.backlog(rec, 13.5) == 0


def test_tbt_takes_gaps_ending_in_the_window_and_open_gaps():
    # a: 0.1, 0.2; c: 4.2 (ends in the window); b open: 20 - 13 = 7.0
    gaps = [0.1, 0.2, 4.2, 7.0]
    assert read("tbt_p99_ms") == pytest.approx(1e3 * np.percentile(gaps, 99))


def test_output_tokens_in_the_window_over_its_length():
    assert read("output_tok_per_s") == pytest.approx(5 / 10.0)


def test_host_side_layer_readers():
    assert read("setup_s") == 42.0
    assert read("gen_lag_ms_p99") == pytest.approx(2.98, abs=0.01)
    assert read("compiles_in_window") == 1
    assert read("kv_peak_share") == pytest.approx(50.0)
    assert read("decode_step_ms") == pytest.approx(30.0)
    assert read("hbm_peak_gb") == pytest.approx(13.0)
    # requests of the window that reached the pair: a and b
    assert read("ppi_prefill_share") == pytest.approx(100 * 70 / 150)
    for name in ("step_mfu", "decode_attn_roofline", "prefill_attn_roofline",
                 "device_idle_share"):
        assert read(name) is None          # nothing traced


def test_reachable_shapes_follow_the_length_bounds():
    mix = {"input_len": {"max": 40}, "output_len": {"max": 10}}
    s = paged.reachable_shapes(mix, page=16, max_tokens=32, max_slots=5)
    # chunks of 1-16 and 17-32 tokens, contexts up to 40 tokens (3 pages)
    assert s["prefill"] == [(16, 4), (32, 4)]
    assert s["decode"] == [(4, 4), (8, 4)]
    assert s["extract_blocks"] == [1, 2, 3]
    assert s["inject_rows"] == [16, 32, 64]
