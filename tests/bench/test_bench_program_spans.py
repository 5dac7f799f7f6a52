"""The reduction of the program's own spans, and the readers of the seven
metrics it feeds, on a recorded trace and on traced runs of the tiny
cells.

``tests/bench/data/program_trace.json`` holds the flight recorder's
host-clock events of the tiny Cronus cell (five requests run to the end).
A traced run starts the service's recorder after the warm-up and hands
its events to the readers as ``run.program_events``; an untraced run
starts none.
"""
import importlib.util
import json
import math
import types
from pathlib import Path

import jax
import pytest

from chipbench import harness, program_spans, run, traffic

DATA = Path(__file__).resolve().parent / "data"
REPO = DATA.parents[2]
CRONUS, WORKER = "tiny-qwen3.tiny.cronus", "tiny-qwen3.tiny.worker"
SEED = 2 ** 31 + 29
FIXTURE = json.loads((DATA / "program_trace.json").read_text())
NAMES = ["ppi_wait_s_p90", "cpi_wait_s_p90", "handoff_ms_p90",
         "step_host_ms", "readback_ms_per_step", "compile_s_in_window",
         "migrated_overlap_share"]
ENTRIES = [m for m in harness.read_bench()["per_layer"] if m["name"] in NAMES]
PAIR_ONLY = {m["name"] for m in ENTRIES if "workloads" in m}

_tr = importlib.util.spec_from_file_location(
    "trace_report", REPO / "tools" / "trace_report.py")
trace_report = importlib.util.module_from_spec(_tr)
_tr.loader.exec_module(trace_report)


def _fixture_run():
    """A run record around the fixture: every request due in a window
    that holds the whole recording."""
    prog = program_spans.Program(FIXTURE["events"])
    return types.SimpleNamespace(
        program_events=FIXTURE["events"], window_ids=list(FIXTURE["requests"]),
        t_open=min(s.t0 for s in prog.spans) - 1.0,
        t_close=max(s.t1 for s in prog.spans) + 1.0)


def _read(name, data):
    return run.read_metric(name, data)


def test_fixture_reduces_to_spans_waits_and_roles():
    prog = program_spans.Program(FIXTURE["events"])
    assert prog.is_pair and len(prog.ppi_lanes) == 1
    reqs = FIXTURE["requests"]
    for rid in reqs:
        queues = prog.waits[rid]["queue"]
        # one wait on the PPI, one on the CPI, both ended by an admission
        assert [w.lane in prog.ppi_lanes for w in queues] == [True, False]
        assert all(w.t1 is not None and w.t1 >= w.t0 for w in queues)
        (flight,) = prog.waits[rid]["kv_in_flight"]
        (ex,) = prog.by_req[rid]["extract_kv"]
        # in flight from the end of the extract to before the CPI's queue
        assert flight.t0 >= ex.t1
        assert flight.t1 <= queues[1].t0
    # every span but a tick has a parent that was recorded, and lies in it
    for s in prog.spans:
        if s.name == "tick":
            assert s.parent is None
            continue
        p = prog.by_sid[s.parent]
        assert p.t0 <= s.t0 and s.t1 <= p.t1, (s.name, p.name)

    def parents(name):
        return {prog.by_sid[s.parent].name for s in prog.spans
                if s.name == name}
    assert parents("iter") == {"tick"}
    assert parents("dispatch") == parents("pump") == {"tick"}
    assert parents("schedule") == parents("decode") == {"iter"}
    assert parents("readback") == parents("decode.wait") == {"decode"}
    assert parents("extract_kv") == parents("inject_kv") == {"iter"}


def test_fixture_handoff_bytes_follow_the_pages_moved():
    prog = program_spans.Program(FIXTURE["events"])
    page, per_token = FIXTURE["page"], FIXTURE["block_bytes_per_token"]
    for rid, r in FIXTURE["requests"].items():
        secs, nbytes = prog.handoff(rid)
        assert nbytes == math.ceil(r["partial_len"] / page) * page * per_token
        (ex,) = prog.by_req[rid]["extract_kv"]
        (inj,) = prog.by_req[rid]["inject_kv"]
        assert secs == pytest.approx(ex.dur + inj.dur)
        assert ex.args["tokens"] == inj.args["tokens"] == r["partial_len"]


def test_fixture_ttft_parts_fit_inside_ttft():
    prog = program_spans.Program(FIXTURE["events"])
    for rid in FIXTURE["requests"]:
        p = prog.ttft_parts(rid)
        parts = sum(v for k, v in p.items() if k != "ttft")
        assert 0.0 < parts <= p["ttft"] + 1e-3, (rid, p)
        assert min(p.values()) >= 0.0


def test_fixture_readers_match_the_spans():
    data = _fixture_run()
    prog = program_spans.of(data)
    ticks = [s for s in prog.spans if s.name == "tick"]
    own = []
    for t in ticks:
        calls = [s for s in prog.spans
                 if s.name in program_spans.EXECUTOR_CALLS
                 and t.t0 <= s.t0 and s.t1 <= t.t1]
        own.append(t.dur - sum(s.dur for s in calls))
    assert _read("step_host_ms", data) == pytest.approx(
        1e3 * sum(own) / len(own))
    rb = [s.dur for s in prog.spans if s.name == "readback"]
    assert _read("readback_ms_per_step", data) == pytest.approx(
        1e3 * sum(rb) / len(rb))
    comp = [s.dur for s in prog.spans if s.name == "compile"]
    assert comp and _read("compile_s_in_window", data) == pytest.approx(
        sum(comp))
    its = [s for s in prog.spans if s.name == "iter"
           and s.args["migrated_prefill_tokens"] > 0]
    both = sum(s.dur for s in its if s.args["n_decode"] > 0)
    assert _read("migrated_overlap_share", data) == pytest.approx(
        100 * both / sum(s.dur for s in its))
    for name in ("ppi_wait_s_p90", "cpi_wait_s_p90", "handoff_ms_p90"):
        assert _read(name, data) > 0.0


def test_waits_open_at_the_close_count_their_wait_so_far():
    prog = program_spans.Program(FIXTURE["events"])
    rid = next(iter(FIXTURE["requests"]))
    t_sub = prog.submit[rid]
    admitted = prog.admission(rid, ppi=True).t1
    cut = (t_sub + admitted) / 2
    assert prog.ppi_wait(rid, cut) == pytest.approx(cut - t_sub)
    assert prog.ppi_wait(rid, admitted + 1.0) == pytest.approx(
        admitted - t_sub)
    assert prog.ppi_wait(rid, t_sub) is None        # not yet submitted
    (flight,) = prog.waits[rid]["kv_in_flight"]
    assert prog.cpi_wait(rid, flight.t0 + 1e-6) == pytest.approx(1e-6)


def test_readers_read_nothing_without_the_program_spans():
    empty = types.SimpleNamespace(window_ids=[], t_open=0.0, t_close=1.0)
    for name in NAMES:
        assert _read(name, empty) is None


# ---------------------------------------------------------------------------
# runs of the tiny cells, with the recorder that a traced run starts
# ---------------------------------------------------------------------------

def _bench():
    """The repo's BENCHMARK.json with the tiny cells; a metric kept to the
    Cronus cell is kept to the tiny one."""
    bench = harness.read_bench()
    bench.update(json.loads((DATA / "workloads.json").read_text()))
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CRONUS]
    return bench


def _run_seen(monkeypatch, name, traced):
    """One run of a tiny cell, and the record its readers were given."""
    seen = []
    read = run.read_metric

    def spy(metric, data):
        seen.append(data)
        return read(metric, data)
    monkeypatch.setattr(run, "read_metric", spy)
    res = run.run(name, SEED, 2.0, traced, bench=_bench(), root=DATA,
                  device=jax.devices()[0], cache=False)
    assert seen and all(d is seen[0] for d in seen)
    return res, seen[0]


def test_entries_on_the_result_line():
    assert [m["name"] for m in ENTRIES] == NAMES
    assert all(m["workloads"] == ["qwen2-7b-l20.cronus.conv-over"]
               for m in ENTRIES if m["name"] in PAIR_ONLY)
    assert PAIR_ONLY == {"ppi_wait_s_p90", "cpi_wait_s_p90",
                         "handoff_ms_p90", "migrated_overlap_share"}


@pytest.mark.parametrize("name", [CRONUS, WORKER])
def test_traced_tiny_cell_reports_the_program_metrics(monkeypatch, name):
    res, data = _run_seen(monkeypatch, name, True)
    got = res["metrics"]
    want = set(NAMES) if name == CRONUS else set(NAMES) - PAIR_ONLY
    assert want <= set(got)
    assert all(got[m]["value"] is not None for m in want)
    if name == WORKER:
        assert not PAIR_ONLY & set(got)
    # the warm-up leaves nothing to compile in the window
    assert got["compile_s_in_window"]["value"] == 0
    assert res["correct"] is True
    # on the host clock, from the open loop's start to the close
    prog = program_spans.Program(data.program_events)
    t_start = data.t_open - data.cell.setup["warm_seconds"]
    assert prog.spans
    assert t_start <= min(s.t0 for s in prog.spans)
    assert max(s.t0 for s in prog.spans) < data.t_close
    if name == CRONUS:
        split = [r for r in prog.submit if prog.ttft_parts(r) is not None]
        assert split
        for rid in split:
            p = prog.ttft_parts(rid)
            assert sum(v for k, v in p.items() if k != "ttft") \
                <= p["ttft"] + 1e-3


def test_untraced_run_starts_no_recorder(monkeypatch):
    res, data = _run_seen(monkeypatch, CRONUS, False)
    assert data.program_events is None
    assert not set(NAMES) & set(res["metrics"])


def test_trace_report_checks_a_host_clock_trace_of_the_tiny_cell(tmp_path):
    bench = _bench()
    cell = harness.load_cell(CRONUS, bench, DATA)
    svc, _ = harness.build_service(cell, SEED, jax.devices()[0])
    svc.start_trace()
    planned = traffic.plan(cell.mix, 6.0, 0.0, 1.0, SEED,
                           cell.dims["vocab_size"])
    for r in harness.make_requests(planned):
        svc.submit(r)
    svc.drain()
    path = tmp_path / "tiny.json"
    svc.export_trace(str(path))
    assert trace_report.main([str(path), "--check"]) == 0
    rep = trace_report.report(trace_report.load_events(str(path)))
    assert rep["ttft"]["n_finished"] == len(planned)
    assert rep["overlap"]["migrated_busy_s"] > 0.0
