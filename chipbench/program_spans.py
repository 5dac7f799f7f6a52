"""The program's own spans, reduced to what the per-layer readers need.

A traced run (``--trace 1``) starts the service's recorder
(``InferenceService.start_trace()``, on the paged executor) after the
warm-up and holds the flight recorder's host-clock events: Chrome
``trace_event`` dicts (``repro/obs/tracer.py``, ``docs/OBSERVABILITY.md``)
stamped with ``time.perf_counter`` in microseconds, the clock the harness
stamps tokens with. The readers find them as ``run.program_events``; a run
without them (an untraced run, or a program that records no such spans)
reads None everywhere.

Names the reduction relies on, as the program records them:

* spans (``X``) with ``args.sid`` and ``args.parent``: ``tick`` (control
  lane) over ``dispatch``, ``pump`` and an engine's ``iter``; ``iter``
  over ``schedule`` and the executor calls ``prefill_chunk``, ``decode``,
  ``extract_kv``, ``inject_kv`` (the handoff ones carry ``req``,
  ``tokens``, ``bytes``); ``decode`` over ``decode.wait`` and
  ``readback``; ``compile`` under whatever span was open;
* per-request waits (``b``/``e`` with ``args.req`` on the ``b``):
  ``queue`` (add to an engine's queue -> slot admission) and
  ``kv_in_flight`` (end of ``extract_kv`` -> delivery to the CPI);
* instants: ``submit`` (control lane) and ``first_token``; the lanes'
  roles from ``track_meta`` (``prefill_only``: a Cronus PPI).
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

EXECUTOR_CALLS = ("prefill_chunk", "decode", "extract_kv", "inject_kv")

Lane = Tuple[int, int]


@dataclasses.dataclass
class Span:
    name: str
    lane: Lane
    t0: float              # seconds
    t1: float
    sid: int
    parent: Optional[int]
    args: dict

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Wait:
    lane: Lane
    t0: float
    t1: Optional[float]     # None: still waiting when the record ends


class Program:
    """Spans by name and id, waits by request, lane roles."""

    def __init__(self, events: List[dict]):
        self.ppi_lanes = {(e["pid"], e["tid"]) for e in events
                          if e.get("ph") == "i" and e["name"] == "track_meta"
                          and e["args"].get("prefill_only")}
        self.spans: List[Span] = []
        self.submit: Dict[str, float] = {}
        self.first_token: Dict[str, float] = {}
        self.waits: Dict[str, Dict[str, List[Wait]]] = defaultdict(
            lambda: defaultdict(list))        # req -> wait name -> in order
        open_: Dict[tuple, Wait] = {}
        for e in sorted(events, key=lambda e: e["ts"]):
            ph, t = e.get("ph"), e["ts"] / 1e6
            if ph == "X" and "sid" in e.get("args", {}):
                a = e["args"]
                self.spans.append(Span(e["name"], (e["pid"], e["tid"]), t,
                                       t + e["dur"] / 1e6, a["sid"],
                                       a.get("parent"), a))
            elif ph == "b" and e["cat"] in ("queue", "kv_in_flight"):
                w = Wait((e["pid"], e["tid"]), t, None)
                self.waits[e["args"]["req"]][e["cat"]].append(w)
                open_[(e["cat"], e["id"])] = w
            elif ph == "e" and (e["cat"], e["id"]) in open_:
                open_.pop((e["cat"], e["id"])).t1 = t
            elif ph == "i" and e["name"] == "submit":
                self.submit.setdefault(e["args"]["req"], t)
            elif ph == "i" and e["name"] == "first_token":
                # the last one wins: a CPI's supersedes its PPI view's
                self.first_token[e["args"]["req"]] = t
        self.by_sid = {s.sid: s for s in self.spans}
        self.children: Dict[int, List[Span]] = defaultdict(list)
        for s in self.spans:
            if s.parent is not None:
                self.children[s.parent].append(s)
        self.by_req: Dict[str, Dict[str, List[Span]]] = defaultdict(
            lambda: defaultdict(list))
        for s in self.spans:
            if "req" in s.args:
                self.by_req[s.args["req"]][s.name].append(s)

    @property
    def is_pair(self) -> bool:
        return bool(self.ppi_lanes)

    def named(self, name: str, t_open: float, t_close: float) -> List[Span]:
        """Spans ``name`` that started in [t_open, t_close)."""
        return [s for s in self.spans
                if s.name == name and t_open <= s.t0 < t_close]

    def admission(self, req: str, ppi: bool) -> Optional[Wait]:
        """``req``'s first queue wait on a PPI lane (``ppi``) or on any
        other lane."""
        for w in self.waits[req]["queue"]:
            if (w.lane in self.ppi_lanes) == ppi:
                return w
        return None

    def _admitted(self, req: str, ppi: bool, t_close: float) -> float:
        """When ``req`` was admitted there, or ``t_close`` if not yet."""
        w = self.admission(req, ppi)
        return t_close if w is None or w.t1 is None else min(w.t1, t_close)

    def executor_time(self, span: Span) -> float:
        """Time of the executor calls under ``span`` (outermost ones)."""
        total = 0.0
        for c in self.children[span.sid]:
            total += (c.dur if c.name in EXECUTOR_CALLS
                      else self.executor_time(c))
        return total

    # ---- per request ------------------------------------------------
    def ppi_wait(self, req: str, t_close: float) -> Optional[float]:
        """From ``submit`` to the PPI's slot admission: the wait to be
        routed (the pair takes at most two prompts) and the PPI queue."""
        t_sub = self.submit.get(req)
        if t_sub is None or t_sub >= t_close:
            return None
        return self._admitted(req, True, t_close) - t_sub

    def cpi_wait(self, req: str, t_close: float) -> Optional[float]:
        """From the end of ``req``'s ``extract_kv`` to the CPI's slot
        admission: ``kv_in_flight`` and the CPI queue."""
        flights = self.waits[req]["kv_in_flight"]
        if not flights or flights[0].t0 >= t_close:
            return None
        return self._admitted(req, False, t_close) - flights[0].t0

    def handoff(self, req: str) -> Optional[Tuple[float, int]]:
        """Seconds of ``req``'s ``extract_kv`` and ``inject_kv``, and the
        payload bytes; None until both ran."""
        spans = self.by_req.get(req, {})
        ex, inj = spans.get("extract_kv"), spans.get("inject_kv")
        if not ex or not inj:
            return None
        return ex[0].dur + inj[0].dur, int(ex[0].args.get("bytes", 0))

    def ttft_parts(self, req: str) -> Optional[Dict[str, float]]:
        """A split request's time to first token in disjoint parts: PPI
        wait, PPI prefill chunks, handoff, CPI wait, CPI prefill chunks,
        and the whole (``submit`` -> last ``first_token``)."""
        spans = self.by_req.get(req, {})
        if (req not in self.submit or req not in self.first_token
                or self.handoff(req) is None):
            return None
        inf = float("inf")
        chunks = spans.get("prefill_chunk", [])
        return {
            "ppi_wait": self.ppi_wait(req, inf),
            "ppi_prefill": sum(s.dur for s in chunks
                               if s.lane in self.ppi_lanes),
            "handoff": self.handoff(req)[0],
            "cpi_wait": self.cpi_wait(req, inf),
            "cpi_prefill": sum(s.dur for s in chunks
                               if s.lane not in self.ppi_lanes),
            "ttft": self.first_token[req] - self.submit[req],
        }


def of(run) -> Optional[Program]:
    """The run's program spans, reduced once; None without them."""
    events = getattr(run, "program_events", None)
    if not events:
        return None
    prog = getattr(run, "_program", None)
    if prog is None:
        prog = run._program = Program(events)
    return prog
