"""From a profiler trace to busy time, program time, kernel time and gaps.

``load_events`` turns an ``.xplane.pb`` into plain event dicts; everything
else works on those dicts, so the reduction can be checked on a small
recorded trace kept with the tests. An event is ``{"plane", "kind",
"name", "start_ns", "dur_ns"}`` with ``kind`` one of ``module`` (an XLA
program's run on a device), ``op`` (one HLO op; ``module`` names the
program it ran in, ``kernel`` says whether it is a custom call, and
``leaf`` whether it holds no other op, as a ``while`` does) or ``host``
(a span on the host thread that drives the service).

Names the reduction relies on, as a TPU v5e trace has them:

* device planes ``/device:TPU:<n>``; programs on the line ``XLA Modules``
  (``jit_<function>(<hash>)``), ops on the line ``XLA Ops`` (HLO text);
* step programs: ``jit_prefill_fwd`` and ``jit_decode_fwd``, the jitted
  ``prefill_fwd`` and ``decode_fwd`` of ``PagedRealExecutor``;
* kernels: the ``custom-call`` ops inside them, the Pallas kernels
  ``chunked_prefill_attention`` and ``paged_decode_attention``;
* host: the thread line that holds the harness's ``service.step`` spans.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, List, Sequence

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_MARK = "service.step"
PREFILL_PROGRAM = "jit_prefill_fwd"
DECODE_PROGRAM = "jit_decode_fwd"
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def find_xplane(log_dir: str) -> str:
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return path


def opcode(hlo_text: str) -> str:
    """``custom-call`` for ``%x.1 = bf16[2]{0} custom-call(...)``."""
    rhs = hlo_text.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


def load_events(path: str) -> List[Dict]:
    """Programs and ops of every TPU plane, and the spans of the host
    thread that drives the service."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                kind = {MODULES_LINE: "module", OPS_LINE: "op"}.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    ev = {"plane": plane.name, "kind": kind,
                          "name": e.name.split("(")[0] if kind == "module"
                          else e.name.split(" = ")[0].lstrip("%"),
                          "start_ns": float(e.start_ns),
                          "dur_ns": float(e.duration_ns)}
                    if kind == "op":
                        ev["opcode"] = opcode(e.name)
                    out.append(ev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = list(line.events)
                if any(e.name == HOST_MARK for e in evs):
                    out += [{"plane": plane.name, "kind": "host",
                             "name": e.name, "start_ns": float(e.start_ns),
                             "dur_ns": float(e.duration_ns)} for e in evs]
    return annotate_ops(out)


def annotate_ops(events: List[Dict]) -> List[Dict]:
    """Give each op the program it ran in (the module event that holds
    its start), whether it is a kernel, and whether it is a leaf."""
    for plane, evs in device_events(events).items():
        mods = sorted((e for e in evs if e["kind"] == "module"),
                      key=lambda e: e["start_ns"])
        ops = sorted((e for e in evs if e["kind"] == "op"),
                     key=lambda e: e["start_ns"])
        i = 0
        for op in ops:
            while i < len(mods) and (mods[i]["start_ns"] + mods[i]["dur_ns"]
                                     < op["start_ns"]):
                i += 1
            inside = i < len(mods) and mods[i]["start_ns"] <= op["start_ns"]
            op["module"] = mods[i]["name"] if inside else ""
            op["kernel"] = op.get("opcode") == "custom-call"
            op["leaf"] = op.get("opcode") not in ("while", "conditional",
                                                  "call")
    return events


def device_events(events: Sequence[Dict]) -> Dict[str, List[Dict]]:
    per = defaultdict(list)
    for e in events:
        if e["plane"].startswith("/device:"):
            per[e["plane"]].append(e)
    return dict(per)


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _op_intervals(evs):
    return [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in evs
            if e["kind"] == "op"]


def busy_seconds(events: Sequence[Dict]) -> float:
    """Seconds in which some op ran, averaged over the device planes."""
    per = device_events(events)
    if not per:
        return 0.0
    return sum(union_ns(_op_intervals(evs)) for evs in per.values()) \
        / len(per) / 1e9


def program_seconds(events: Sequence[Dict], program: str) -> float:
    """Device time of the runs of ``program`` (an ``XLA Modules`` name)."""
    return sum(e["dur_ns"] for e in events
               if e["kind"] == "module" and e["name"] == program) / 1e9


def kernel_seconds(events: Sequence[Dict], program: str) -> float:
    """Device time of the custom-call kernels run inside ``program``."""
    return sum(e["dur_ns"] for e in events
               if e["kind"] == "op" and e.get("kernel")
               and e.get("module") == program) / 1e9


def top_ops(events: Sequence[Dict], n: int = 10) -> List[list]:
    """The leaf ops that took the most device time, ``[name, seconds]``,
    named ``<program>/<op>``."""
    tot = defaultdict(float)
    for e in events:
        if e["kind"] == "op" and e.get("leaf", True):
            tot[f"{e.get('module', '')}/{e['name']}"] += e["dur_ns"] / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Dict], n: int = 10) -> List[list]:
    """The longest stretches with no op on the first device, each named by
    the innermost host span running at its middle: ``[label, seconds]``."""
    per = device_events(events)
    if not per:
        return []
    iv = sorted(_op_intervals(per[sorted(per)[0]]))
    gaps, end = [], None
    for s, e in iv:
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    host = [e for e in events if e["kind"] == "host"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        inner = [h for h in host
                 if h["start_ns"] <= mid <= h["start_ns"] + h["dur_ns"]]
        label = (min(inner, key=lambda h: h["dur_ns"])["name"]
                 if inner else "no host span")
        out.append([label, (e - s) / 1e9])
    return out
