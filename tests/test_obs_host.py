"""The flight recorder on the host clock: a service that runs real compute
records host-work spans with parents, per-request waits and compiles, and
each span lands in the profiler's trace as an annotation of the same
length; a simulated service records what it always did; tracing off
records and annotates nothing."""
import glob
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.request import Request
from repro.obs import Tracer
from repro.serving.api import ServeSpec
from repro.serving.trace import make_trace

_TR_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "tools", "trace_report.py")
_spec = importlib.util.spec_from_file_location("trace_report", _TR_PATH)
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)

PAGED = dict(smoke=True, approach="cronus", hi="A100", lo="A30",
             executor="paged", s_kv=64, max_slots=4, block_size=4,
             max_batched_tokens=16, num_kv_blocks=64)
LENS = [(21, 4), (9, 3), (30, 5), (14, 2)]


def _requests(vocab, tag="r"):
    rng = np.random.default_rng(7)
    return [Request(req_id=f"{tag}{i}",
                    prompt=rng.integers(0, vocab, n).astype(np.int32),
                    output_len=m, arrival=0.0)
            for i, (n, m) in enumerate(LENS)]


@pytest.fixture(scope="module")
def paged():
    """A paged Cronus pair on the smoke model, built once."""
    svc = ServeSpec(**PAGED).build()
    return svc


@pytest.fixture(scope="module")
def traced():
    """The paged pair's host-clock trace of four requests run to the
    end."""
    svc = ServeSpec(**PAGED).build()
    tracer = svc.start_trace()
    svc.run(_requests(svc.cfg.vocab_size))
    return svc, tracer


def _spans(tracer):
    return [e for e in tracer.events if e["ph"] == "X"]


def test_decode_wait_counts_live_pages(traced):
    """``decode.wait`` carries the pages the decode kernel copies (each
    row's ``ceil(ctx / page)``) beside the padded table's size."""
    _, tracer = traced
    spans = _spans(tracer)
    by_sid = {e["args"]["sid"]: e for e in spans}
    waits = [e for e in spans if e["name"] == "decode.wait"]
    assert waits
    longest = max(-(-(n + m) // PAGED["block_size"]) for n, m in LENS)
    for e in waits:
        rows = by_sid[e["args"]["parent"]]["args"]["n"]
        live, table = e["args"]["live_pages"], e["args"]["table_pages"]
        assert rows <= live <= min(table, rows * longest)
    assert any(e["args"]["live_pages"] < e["args"]["table_pages"]
               for e in waits)


def test_clock_follows_the_executor(traced):
    _, tracer = traced
    assert tracer.host_clock
    assert not ServeSpec(approach="cronus").build().start_trace().host_clock


def test_host_spans_nest_under_their_parents(traced):
    _, tracer = traced
    spans = _spans(tracer)
    by_sid = {e["args"]["sid"]: e for e in spans}
    assert len(by_sid) == len(spans)              # every span has an id
    want = {"dispatch": "tick", "pump": "tick", "iter": "tick",
            "schedule": "iter", "prefill_chunk": "iter", "decode": "iter",
            "extract_kv": "iter", "inject_kv": "iter",
            "decode.wait": "decode", "readback": "decode"}
    seen = set()
    for e in spans:
        parent = e["args"].get("parent")
        if e["name"] == "tick":
            assert parent is None
            continue
        p = by_sid[parent]
        assert p["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"]
        if e["name"] in want:
            assert p["name"] == want[e["name"]], e["name"]
        seen.add(e["name"])
    assert set(want) <= seen
    # an iteration keeps the simulated interval it stood for
    it = next(e for e in spans if e["name"] == "iter")
    assert it["args"]["sim_t1"] > it["args"]["sim_t0"] >= 0.0
    # spans tied to a request carry it
    assert all("req" in e["args"] for e in spans
               if e["name"] in ("prefill_chunk", "extract_kv", "inject_kv"))


def test_host_trace_passes_check_and_reports(traced, tmp_path):
    svc, tracer = traced
    assert trace_report.validate(tracer.to_chrome()) == []
    path = tmp_path / "host.json"
    svc.export_trace(str(path))
    assert trace_report.main([str(path), "--check"]) == 0
    rep = trace_report.report(tracer.to_chrome())
    assert rep["ttft"]["n_finished"] == len(LENS)
    assert set(rep["bubbles"]) == {"cronus/ppi", "cronus/cpi"}


def test_every_admission_ends_a_queue_wait(traced):
    _, tracer = traced
    waits = {}
    for e in tracer.events:
        if e["ph"] in ("b", "e") and e["cat"] in ("queue", "kv_in_flight"):
            waits.setdefault((e["cat"], e["id"]), []).append(e)
    assert all([x["ph"] for x in w] == ["b", "e"] for w in waits.values())
    queued = [w[0] for (cat, _), w in waits.items() if cat == "queue"]
    lanes = {(e["pid"], e["tid"]) for e in queued}
    # the PPI's admissions and, newly marked, the CPI's
    assert len(lanes) == 2
    assert len(queued) == 2 * len(LENS)
    flights = [w for (cat, _), w in waits.items() if cat == "kv_in_flight"]
    assert len(flights) == len(LENS)


def test_annotations_match_the_recorded_spans(paged, tmp_path):
    """Every host-work span shows in the profiler's trace, on the driving
    thread, as ``<lane>:<name>`` with its length within 0.1 ms."""
    from jax.profiler import ProfileData
    svc = ServeSpec(**PAGED).build()
    tracer = svc.start_trace()
    reqs = _requests(svc.cfg.vocab_size)
    svc.run([reqs[0]])                       # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    n0 = len(tracer.events)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        svc.run(reqs[1:])
    finally:
        jax.profiler.stop_trace()
    lanes = trace_report.track_names(tracer.to_chrome())
    mine = {}
    for e in tracer.events[n0:]:
        if e["ph"] == "X" and e["name"] != "compile":
            lane = lanes[(e["pid"], e["tid"])]
            mine.setdefault(f"{lane}:{e['name']}", []).append(e["dur"] / 1e3)
    assert mine
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    theirs = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in mine:
                    theirs.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns / 1e6))
    for name, durs in mine.items():
        got = [d for _, d in sorted(theirs.get(name, []))]
        assert len(got) == len(durs), name
        assert np.allclose(got, durs, rtol=0, atol=0.1), name


def test_compile_is_a_span_under_the_open_one():
    tracer = Tracer(host_clock=True)
    track = tracer.track("ep", "eng")
    x = jnp.arange(13.0)
    # fresh functions, so each call compiles
    with tracer.span(track, "outer"):
        jax.jit(lambda v: v * 3.5 + 1.0)(x).block_until_ready()
    jax.jit(lambda v: v * 2.5 - 1.0)(x).block_until_ready()  # not recorded
    outer, *compiles = [e for e in tracer.events if e["ph"] == "X"]
    assert outer["name"] == "outer" and compiles
    for c in compiles:
        assert c["name"] == "compile"
        assert c["args"]["parent"] == outer["args"]["sid"]
        assert c["args"]["fun"].startswith("jit(")
        assert outer["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= outer["ts"] + outer["dur"]


def test_simulated_trace_records_no_host_work():
    svc = ServeSpec(approach="cronus").build()
    tracer = svc.start_trace()
    svc.run(make_trace(20, seed=1, interval=1 / 8.0).fresh())
    names = {e["name"] for e in tracer.events}
    assert "iter" in names
    assert not names & {"tick", "dispatch", "pump", "schedule", "decode",
                        "readback", "queue", "kv_in_flight", "compile",
                        "busy_frac"}
    assert all("sid" not in e.get("args", {}) for e in tracer.events)


def test_tracing_off_engine_step_records_and_annotates_nothing(
        paged, traced, monkeypatch):
    """With tracing off, a step of the real path calls into the recorder
    only to enter and leave the shared no-op span (and, as it compiles,
    the process's compile listener, which finds no span open), records
    nothing anywhere and opens no profiler annotation."""
    other = traced[1]
    n_other = len(other.events)
    opened = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            opened.append(a)
            super().__init__(*a, **k)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    svc = paged
    assert svc.tracer is None
    for r in _requests(svc.cfg.vocab_size, tag="off"):
        svc.submit(r)
    tracer_py = os.path.join("repro", "obs", "tracer.py")
    calls = set()

    def watch(frame, event, arg):
        if event == "call" and frame.f_code.co_filename.endswith(tracer_py):
            calls.add(frame.f_code.co_qualname)
    sys.setprofile(watch)
    try:
        while svc.n_active:
            svc.step()
    finally:
        sys.setprofile(None)
    assert calls <= {"_NoSpan.__enter__", "_NoSpan.__exit__", "_on_duration"}
    assert "_NoSpan.__enter__" in calls
    assert opened == []
    assert len(other.events) == n_other
