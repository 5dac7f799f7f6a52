"""The harness end to end on the CPU, at a tiny size.

The measurement entry refuses the CPU; these tests skip that look and
drive the rest of a run: build, warm-up, the open loop, the metrics and
the comparison with the float32 reference. Faults planted under the timed
path, and the lower-precision control, must turn ``correct`` false.

The metrics are the repo's own ``BENCHMARK.json``; only its configurations
and cells are replaced by the tiny ones under ``data/``.
"""
import json
from pathlib import Path

import jax
import pytest

from chipbench import harness, run
from repro.core.executor import PagedRealExecutor
from repro.kernels import ops

DATA = Path(__file__).resolve().parent / "data"
CRONUS, WORKER = "tiny-qwen3.tiny.cronus", "tiny-qwen3.tiny.worker"
SEED = 2 ** 31 + 17
LIMIT = json.loads((DATA / "chipbench" / "cells" / f"{CRONUS}.json")
                   .read_text())["check"]["max_logit_gap"]


def _deployment(root, traffic):
    return json.loads((root / "chipbench" / "traffic" / f"{traffic}.json")
                      .read_text())["deployment"]


def _tiny_bench():
    """The repo's BENCHMARK.json with the tiny configurations and cells; a
    metric kept to some cells is kept to the tiny cells of the same
    deployment."""
    bench = harness.read_bench()
    real = {w["name"]: _deployment(harness.REPO, w["traffic"])
            for w in bench["workloads"]}
    bench.update(json.loads((DATA / "workloads.json").read_text()))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            deps = [real[w] for w in m["workloads"]]
            m["workloads"] = [w["name"] for w in bench["workloads"]
                              if _deployment(DATA, w["traffic"]) in deps]
    return bench


BENCH = _tiny_bench()
E2E = {m["name"] for m in BENCH["end_to_end"]}


def _run(name=CRONUS, traced=False, seconds=2.0, control=False):
    res = run.run(name, SEED, seconds, traced, bench=BENCH, root=DATA,
                  device=jax.devices()[0], cache=False, control=control)
    return json.loads(json.dumps(res))          # as printed


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The executor's attention calls go to the Pallas kernels, run by the
    Pallas interpreter (on CPU the executor would pick the jnp path)."""
    for name in ("paged_decode_attention", "chunked_prefill_attention"):
        inner = getattr(ops, name)

        def call(*a, inner=inner, use_pallas=False, **k):
            return inner(*a, use_pallas=True, interpret=True, **k)
        monkeypatch.setattr(ops, name, call)


def test_cell_end_to_end_prints_a_result_line(interpret_kernels):
    res = _run()
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["check"]["max_logit_gap"]["value"] <= LIMIT


def test_traced_run_reports_only_per_layer_metrics():
    res = _run(WORKER, traced=True)
    names = {m["name"] for m in BENCH["per_layer"]}
    assert set(res["metrics"]) <= names
    # host-side readers find their numbers on any backend
    assert {"ttft_p90_s", "tbt_p99_ms", "gen_lag_ms_p99",
            "compiles_in_window", "kv_peak_share",
            "decode_step_ms"} <= set(res["metrics"])
    assert "ppi_prefill_share" not in res["metrics"]      # no pair here
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"] is True


def test_measurement_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", harness.read_bench()["workloads"][0]["name"],
              "--seed", "1",
                  "--seconds", "1"])
    assert exit_.value.code == 1
    assert capsys.readouterr().out == ""


def _token_altered(monkeypatch):
    inner = PagedRealExecutor.decode

    def decode(self, slot_tokens, slot_lens):
        out = inner(self, slot_tokens, slot_lens)
        return {s: (t + 1) % self.cfg.vocab_size for s, t in out.items()}
    monkeypatch.setattr(PagedRealExecutor, "decode", decode)


def _handoff_dropped(monkeypatch):
    monkeypatch.setattr(PagedRealExecutor, "inject_kv",
                        lambda self, slot, payload, upto: None)


def _decode_state_unchanged(monkeypatch):
    inner = PagedRealExecutor.decode

    def decode(self, slot_tokens, slot_lens):
        k, v = self.k_pool, self.v_pool
        out = inner(self, slot_tokens, slot_lens)
        self.k_pool, self.v_pool = k, v
        return out
    monkeypatch.setattr(PagedRealExecutor, "decode", decode)


@pytest.mark.parametrize("plant", [_token_altered, _handoff_dropped,
                                   _decode_state_unchanged],
                         ids=["token_altered", "handoff_dropped",
                              "decode_state_unchanged"])
def test_fault_under_the_timed_path_fails_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = _run()
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > LIMIT


def test_lower_precision_control_fails_correct():
    """With the fp8 control in the program's place, the run's own
    comparison reads not correct, while the served bf16 tokens of the same
    run keep the limit."""
    res = _run(control=True)
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > LIMIT
    assert res["control"]["served_max_gap"] <= LIMIT
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("name", [CRONUS, WORKER])
def test_warm_up_leaves_nothing_to_compile_in_the_window(name):
    """The warm-up runs every program the window runs, so a change in the
    program's shapes or programs shows here, not as a silent compile
    inside a measured window."""
    res = _run(name, traced=True)
    assert res["metrics"]["compiles_in_window"]["value"] == 0
