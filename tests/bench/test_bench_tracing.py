"""The reduction from trace events to busy time, program and kernel time,
and named idle gaps."""
import pytest

from chipbench import tracing

DEV = "/device:TPU:0"


def mod(start, dur, name="jit_decode_fwd", plane=DEV):
    return {"plane": plane, "kind": "module", "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def op(start, dur, name="fusion.1", opcode="fusion", plane=DEV):
    return {"plane": plane, "kind": "op", "name": name, "opcode": opcode,
            "start_ns": float(start), "dur_ns": float(dur)}


def host(start, dur, name):
    return {"plane": tracing.HOST_PLANE, "kind": "host", "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def events():
    return tracing.annotate_ops([
        mod(0, 360), mod(400, 420, "jit_prefill_fwd"),
        op(0, 150, "while.5", "while"),                 # holds the next two
        op(0, 100), op(50, 100, "fusion.2"),            # overlap: 0..150
        op(300, 50, "paged_decode_attention.9", "custom-call"),
        op(400, 200, "fusion.7"),
        op(700, 100, "chunked_prefill_attention.3", "custom-call"),
        host(0, 1000, "service.step"), host(150, 150, "cpi.decode"),
        host(600, 100, "ppi.prefill_chunk"),
    ])


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0), ([(0, 10)], 10.0), ([(0, 10), (5, 20)], 20.0),
    ([(0, 10), (20, 30)], 20.0), ([(20, 30), (0, 10), (0, 5)], 20.0),
])
def test_union(intervals, want):
    assert tracing.union_ns(intervals) == want


@pytest.mark.parametrize("text,want", [
    ("%paged_decode_attention.9 = bf16[32,4,7,128]{3,2,1,0:T(8,128)(2,1)S(1)}"
     " custom-call(s32[32] %x)", "custom-call"),
    ("%while.5 = (s32[]{:T(128)}, bf16[32,1,3584]{2,0,1:T(8,128)}) "
     "while(%tuple), condition=%c", "while"),
    ("%fusion.110 = bf16[32,18944]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[20] %p),"
     " kind=kCustom", "fusion"),
])
def test_opcode_from_hlo_text(text, want):
    assert tracing.opcode(text) == want


def test_ops_get_their_program_kernel_and_leaf_flags():
    ops = {e["name"]: e for e in events() if e["kind"] == "op"}
    assert ops["paged_decode_attention.9"]["module"] == "jit_decode_fwd"
    assert ops["chunked_prefill_attention.3"]["module"] == "jit_prefill_fwd"
    assert ops["paged_decode_attention.9"]["kernel"]
    assert not ops["fusion.2"]["kernel"]
    assert not ops["while.5"]["leaf"] and ops["fusion.2"]["leaf"]


def test_busy_is_the_union_of_ops_over_devices():
    ev = events()
    assert tracing.busy_seconds(ev) == pytest.approx(500e-9)
    two = ev + [op(0, 1000, plane="/device:TPU:1")]
    assert tracing.busy_seconds(two) == pytest.approx((500e-9 + 1e-6) / 2)


def test_program_and_kernel_time():
    ev = events()
    assert tracing.program_seconds(ev, tracing.DECODE_PROGRAM) == \
        pytest.approx(360e-9)
    assert tracing.program_seconds(ev, tracing.PREFILL_PROGRAM) == \
        pytest.approx(420e-9)
    assert tracing.kernel_seconds(ev, tracing.DECODE_PROGRAM) == \
        pytest.approx(50e-9)
    assert tracing.kernel_seconds(ev, tracing.PREFILL_PROGRAM) == \
        pytest.approx(100e-9)


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = tracing.idle_gaps(events())
    assert gaps == [["cpi.decode", pytest.approx(150e-9)],
                    ["ppi.prefill_chunk", pytest.approx(100e-9)],
                    ["service.step", pytest.approx(50e-9)]]


def test_top_ops_count_leaves_only():
    top = tracing.top_ops(events(), n=2)
    assert top[0] == ["jit_prefill_fwd/fusion.7", pytest.approx(200e-9)]
    assert all("while" not in name for name, _ in tracing.top_ops(events()))


def test_recorded_v5e_trace():
    """60 ms of a traced run of the conversation cell on one v5e chip: a
    prefill step and the per-row logits reads of ``robust_greedy``."""
    import json
    from pathlib import Path
    raw = json.loads((Path(__file__).resolve().parent / "data"
                      / "trace_v5e_slice.json").read_text())
    for e in raw:                       # as loaded, before annotation
        e.pop("module", None), e.pop("kernel", None), e.pop("leaf", None)
    ev = tracing.annotate_ops(raw)
    mods = [e for e in ev if e["kind"] == "module"]
    ops = [e for e in ev if e["kind"] == "op"]
    # every op ran inside some program
    assert all(o["module"] for o in ops)
    # the prefill kernel, found independently: custom calls whose start
    # lies inside a jit_prefill_fwd run
    runs = [(m["start_ns"], m["start_ns"] + m["dur_ns"]) for m in mods
            if m["name"] == "jit_prefill_fwd"]
    want = sum(o["dur_ns"] for o in ops if o["opcode"] == "custom-call"
               and any(s <= o["start_ns"] <= e for s, e in runs)) / 1e9
    assert want > 0
    assert tracing.kernel_seconds(ev, tracing.PREFILL_PROGRAM) == \
        pytest.approx(want)
    assert tracing.program_seconds(ev, tracing.PREFILL_PROGRAM) == \
        pytest.approx(sum(e - s for s, e in runs) / 1e9)
    span = (max(o["start_ns"] + o["dur_ns"] for o in ops)
            - min(o["start_ns"] for o in ops)) / 1e9
    busy = tracing.busy_seconds(ev)
    assert 0 < busy <= span
    assert busy <= sum(o["dur_ns"] for o in ops) / 1e9
    gaps = tracing.idle_gaps(ev)
    host_names = {e["name"] for e in ev if e["kind"] == "host"}
    assert gaps and all(label in host_names | {"no host span"}
                        for label, _ in gaps)
    assert sum(g for _, g in gaps) <= span - busy + 1e-9
