"""Architecture families: the dense family against what the same code gave
before it moved into ``chipbench/families/dense.py``, a family that exists
only under a run's checkout, and a family that brings its own warm-up and
device state (``data/chipbench/families/stub_dense.py``).

``data/dense_golden.json`` was recorded on the CPU from the code before
the move: a SHA-256 of every weight leaf of ``tiny-qwen3``, of the float32
reference's logits with and without the fp8 control at two fixed
sequences, and every cost count at three configurations and three shapes
each. XLA's CPU backend sums in another order on a host that gives the
process one core, so the logits carry a second digest recorded there.
"""
import hashlib
import json
import os
import shutil
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import costs, families, harness, reference, run
from chipbench.families import dense

DATA = Path(__file__).resolve().parent / "data"
GOLDEN = json.loads((DATA / "dense_golden.json").read_text())
TINY = json.loads((DATA / "configs" / "tiny-qwen3.json").read_text())
CRONUS = "tiny-qwen3.tiny.cronus"
SEED = 2 ** 31 + 29


def _config(name):
    if name == "tiny-qwen3":
        return TINY
    return json.loads((harness.REPO / "chipbench" / "configs"
                       / f"{name}.json").read_text())


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()
                          ).hexdigest()


# ---------------------------------------------------------------------------
# the dense family reproduces the code it was moved from
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(GOLDEN["costs"]))
def test_dense_dims_are_the_recorded_ones(name):
    assert dense.dims(_config(name)) == dict(GOLDEN["costs"][name]["dims"],
                                             family="dense")


def test_dense_weights_are_the_recorded_bytes():
    params = dense.make_params(GOLDEN["seed"], dense.dims(TINY),
                               jax.devices()[0])
    got = {jax.tree_util.keystr(p): {"sha256": _sha(x),
                                     "shape": list(x.shape),
                                     "dtype": str(x.dtype)}
           for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == GOLDEN["weights"]


@pytest.mark.parametrize("quant", [None, "fp8"], ids=["float32", "fp8"])
def test_reference_logits_are_the_recorded_bits(quant):
    seqs = [np.asarray(s, np.int32) for s in GOLDEN["sequences"]]
    out = reference.served_logits(GOLDEN["seed"], dense.dims(TINY), seqs,
                                  GOLDEN["starts"], quant=quant)
    one_core = len(os.sched_getaffinity(0)) == 1
    for got, want in zip(out, GOLDEN["logits"][quant or "float32"]):
        assert list(got.shape) == want["shape"]
        assert _sha(got.astype(np.float32)) == (
            want["sha256_one_core"] if one_core else want["sha256"])


@pytest.mark.parametrize("name", sorted(GOLDEN["costs"]))
def test_costs_are_the_recorded_integers(name):
    want = GOLDEN["costs"][name]
    d = dense.dims(_config(name))
    peak = costs.peaks(GOLDEN["peak_device_kind"])
    prefill, decode = GOLDEN["prefill_shapes"], GOLDEN["decode_ctxs"]
    assert dense.layer_matmul_params(d) == want["layer_matmul_params"]
    assert dense.matmul_params_per_token(d) == (
        d["n_layers"] * want["layer_matmul_params"])
    assert costs.head_flops(d) == want["head_flops"]
    assert [dense.prefill_attn_flops(d, c, x) for c, x in prefill] == \
        want["prefill_attn_flops"]
    assert [dense.prefill_attn_bytes(d, c, x) for c, x in prefill] == \
        want["prefill_attn_bytes"]
    assert [dense.decode_attn_flops(d, c[-1]) for c in decode] == \
        want["decode_attn_flops"]
    assert [dense.decode_attn_bytes(d, c[-1]) for c in decode] == \
        want["decode_attn_bytes"]
    assert [[costs.prefill_step_flops(d, c, x, done)
             for done in (False, True)] for c, x in prefill] == \
        want["prefill_step_flops"]
    assert [costs.decode_step_flops(d, c) for c in decode] == \
        want["decode_step_flops"]
    assert [list(costs.least_time(dense.prefill_attn_flops(d, c, x),
                                  dense.prefill_attn_bytes(d, c, x), peak))
            for c, x in prefill] == want["least_time_prefill"]
    assert [list(costs.least_time(dense.decode_attn_flops(d, c[-1]),
                                  dense.decode_attn_bytes(d, c[-1]), peak))
            for c in decode] == want["least_time_decode"]


# ---------------------------------------------------------------------------
# a family brought by new files alone
# ---------------------------------------------------------------------------

def _bench():
    bench = harness.read_bench()
    bench.update(json.loads((DATA / "workloads.json").read_text()))
    return bench


def _checkout(tmp_path, config: dict, family_file=None) -> Path:
    """The tiny cells' files under a new root, with ``config`` as their
    configuration and ``family_file`` as ``chipbench/families/<name>``."""
    root = tmp_path / "checkout"
    shutil.copytree(DATA / "chipbench", root / "chipbench")
    (root / "configs").mkdir()
    (root / "configs" / "tiny-qwen3.json").write_text(json.dumps(config))
    if family_file is not None:
        (root / "chipbench" / "families").mkdir(exist_ok=True)
        shutil.copy(dense.__file__, root / "chipbench" / "families"
                    / family_file)
    return root


def _run_at(root):
    return run.run(CRONUS, SEED, 1.0, False, bench=_bench(), root=root,
                   device=jax.devices()[0], cache=False)


def _drive_to_the_end(svc, reqs, due_at, rec, until, on_time=None):
    """Every request at once, served to its end: the same batches, and so
    the same served tokens, in every run, whatever the host's clock does."""
    for r, due in zip(reqs, due_at):
        svc.submit(r)
        rec.due[r.req_id] = due
        rec.sent[r.req_id] = time.perf_counter()
    svc.drain()


def test_a_family_planted_under_the_checkout_runs_the_tiny_cell(
        tmp_path, monkeypatch):
    """A copy of the dense family under another name, found only under the
    run's root, serves and checks the tiny Cronus cell as ``dense`` does."""
    monkeypatch.setattr(harness, "drive", _drive_to_the_end)
    root = _checkout(tmp_path, dict(TINY, family="dense_copy"),
                     "dense_copy.py")
    cell = harness.load_cell(CRONUS, _bench(), root)
    assert Path(cell.family.__file__) == (
        root / "chipbench" / "families" / "dense_copy.py")
    assert cell.dims["family"] == "dense_copy"
    results = [_run_at(r) for r in (DATA, root)]
    for res in results:
        assert res["correct"] is True
        assert res["check"]["tokens_compared"]["value"] > 0
    plain, planted = (res["check"] for res in results)
    for k in ("tokens_compared", "max_logit_gap"):
        assert planted[k]["value"] == plain[k]["value"]


def test_a_missing_family_names_the_files_it_looked_for(tmp_path):
    root = _checkout(tmp_path, dict(TINY, family="no_such_family"))
    with pytest.raises(FileNotFoundError) as err:
        _run_at(root)
    for path in (root / "chipbench" / "families" / "no_such_family.py",
                 families.HERE / "no_such_family.py"):
        assert str(path) in str(err.value)


def test_a_configuration_without_a_family_is_refused(tmp_path):
    root = _checkout(tmp_path, {k: v for k, v in TINY.items()
                                if k != "family"})
    with pytest.raises(ValueError) as err:
        _run_at(root)
    assert str(root / "configs" / "tiny-qwen3.json") in str(err.value)


# ---------------------------------------------------------------------------
# the warm-up and the device state go through the family
# ---------------------------------------------------------------------------

# the shapes warmed per engine and phase, as the harness warmed them before
# the warm calls moved into the family
PARENT_WARM_COUNTS = {
    CRONUS: {"ppi.prefill": 2, "ppi.extract_kv": 3, "cpi.prefill": 2,
             "cpi.decode": 2, "cpi.inject_kv": 3},
    "tiny-qwen3.tiny.worker": {"worker0.prefill": 2, "worker0.decode": 2},
}
PROGRAM_METRICS = {"ppi_wait_s_p90", "cpi_wait_s_p90", "handoff_ms_p90",
                   "step_host_ms", "readback_ms_per_step",
                   "compile_s_in_window", "migrated_overlap_share"}
PRIVATE = ("_prefill_fn", "_decode_fn", "_inject_fn", "k_pool", "v_pool",
           "_trash")


@pytest.mark.parametrize("name", sorted(PARENT_WARM_COUNTS))
def test_warm_counts_per_phase_are_the_parents(name):
    cell = harness.load_cell(name, _bench(), DATA)
    svc, _ = harness.build_service(cell, SEED, jax.devices()[0])
    assert harness.warm_shapes(svc, cell) == PARENT_WARM_COUNTS[name]


def test_a_family_brings_its_warm_up_and_device_state():
    """A traced run of the tiny Cronus cell under the stub family warms
    through the stub, reads the program's metrics, and frees the stub's
    extra device array with the pools."""
    stub = families.load("stub_dense", DATA)
    assert Path(stub.__file__) == (DATA / "chipbench" / "families"
                                   / "stub_dense.py")
    stub.WARMED.clear()
    stub.EXTRA.clear()
    bench = _bench()
    bench["configs"].append({"name": "tiny-qwen3-stub", "source": "test",
                             "file": "configs/tiny-qwen3-stub.json",
                             "reduced": [], "why": "test"})
    for w in bench["workloads"]:
        if w["name"] == CRONUS:
            w["config"] = "tiny-qwen3-stub"
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CRONUS]
    res = run.run(CRONUS, SEED, 2.0, True, bench=bench, root=DATA,
                  device=jax.devices()[0], cache=False)
    assert res["correct"] is True
    assert PROGRAM_METRICS <= set(res["metrics"])
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    # one call per engine: the PPI (prefill only) and the CPI
    assert sorted(w[:2] for w in stub.WARMED) == [(False, False),
                                                  (True, False)]
    assert all(w[2] == ["decode", "extract_kv", "inject_kv", "prefill"]
               for w in stub.WARMED)
    assert len(stub.EXTRA) == 2
    assert all(a.is_deleted() for a in stub.EXTRA)


@pytest.mark.parametrize("module", ["harness", "run", "reference", "costs",
                                    "knee_sweep"])
def test_generic_code_names_no_private_executor_attribute(module):
    text = (harness.BENCH_DIR / f"{module}.py").read_text()
    assert [p for p in PRIVATE if p in text] == []
