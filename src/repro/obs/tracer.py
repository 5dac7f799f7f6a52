"""Structured tracing — the cluster's flight recorder.

One :class:`Tracer` records the full life of every request as typed
events: spans (``complete``), instants, gauge counters, flow arrows tying
a KV transfer's send to its receive, and async request lifelines
spanning submit → finish/cancel. Events are stored as Chrome
``trace_event`` dicts (the format Perfetto and ``chrome://tracing`` load
directly), in **emission order**.

Two clocks, one per tracer, chosen by ``InferenceService.start_trace``
from the service's executor:

* **simulated** (``null`` executor): every event is stamped with the
  simulated time its emission site passes in — the engines' clocks.
  Same spec + seed ⇒ the same ``events`` list, so traces are
  CI-diffable; the host-work spans below are not recorded.
* **host** (``real`` / ``paged`` executors, which run real compute):
  every event is stamped with ``time.perf_counter()`` at the moment it
  is recorded, and the simulated time passed in is dropped (each
  ``iter`` span keeps its engine's simulated interval as ``sim_t0`` /
  ``sim_t1``). On this clock the tracer also records host-work spans
  with parents (:meth:`span`), per-request waits (:meth:`async_open`)
  and one ``compile`` span per JAX backend compile, and every host-work
  span opens a ``jax.profiler.TraceAnnotation`` named
  ``<lane>:<span>``, so a profiler trace holds the program's spans on
  the same time base as the device's ops.

Track model (how the timeline renders):

* one Chrome *process* per endpoint (``pid``), one *thread* per engine
  (``tid``) — a Cronus pair shows its PPI and CPI as two lanes under one
  endpoint group, a worker shows a single ``main`` lane;
* process 0 is the synthetic ``cluster`` process whose ``control`` lane
  carries cluster-scope instants (submit, route decisions, balancer
  splits, autoscale actions, attach/detach), the cumulative transfer
  counters and, on the host clock, the ``tick`` spans.

Track handles are small ints from :meth:`track`; the string form
``"endpoint/engine"`` (the :class:`~repro.kvcache.transfer
.TransferEngine`'s pool names) resolves through :meth:`track_for`, so
flow arrows land on the same lanes the iteration spans live on.

The hot-path contract, matching the repo's other opt-in surfaces: the
tracer is only ever reached behind ``if tracer is not None`` guards, so
with tracing off no event dict — not one — is allocated, no annotation
is opened, and every aggregate metric dict stays byte-identical to an
untraced run.

Timestamps are float microseconds (seconds * 1e6 on either clock), the
unit Chrome expects; the µs↔s round-trip error is ~1e-16 relative, far
inside the 1e-6 tolerance ``tools/trace_report.py`` cross-checks
against ``aggregate()``.
"""
from __future__ import annotations

import json
import time
import weakref
from typing import Dict, List, Optional, Tuple

# JAX monitoring events behind the ``compile`` spans. In JAX 0.9 the
# persistent-cache read is timed inside the backend-compile event, so it
# annotates that event's span instead of making a second one.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class _NoSpan:
    """What a span site gets when tracing is off, or on the simulated
    clock: a context that records nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        """Nothing to annotate."""


NO_SPAN = _NoSpan()


class _Span:
    """One open host-clock span: an ``X`` event appended when it opens
    (so a parent precedes its children at equal timestamps) and closed
    with its duration, inside a ``TraceAnnotation`` of the same name."""

    __slots__ = ("_tracer", "ev", "_note")

    def __init__(self, tracer: "Tracer", ev: dict, note):
        self._tracer, self.ev, self._note = tracer, ev, note

    def __enter__(self):
        self._note.__enter__()
        self.ev["ts"] = time.perf_counter() * 1e6
        tr = self._tracer
        if tr._stack:
            self.ev["args"]["parent"] = tr._stack[-1].ev["args"]["sid"]
        tr._stack.append(self)
        tr.events.append(self.ev)
        return self

    def __exit__(self, *exc):
        ev = self.ev
        ev["dur"] = time.perf_counter() * 1e6 - ev["ts"]
        self._tracer._stack.pop()
        self._note.__exit__(*exc)
        return False

    def set(self, **args) -> None:
        """Add args known only once the work is done (bytes moved, ...)."""
        self.ev["args"].update(args)


class Tracer:
    """Event recorder for one cluster run. Obtain via
    :meth:`repro.serving.api.InferenceService.start_trace`.
    ``host_clock=True`` stamps the host clock (see the module doc)."""

    def __init__(self, host_clock: bool = False):
        self.host_clock = host_clock
        # emission-order event list: THE determinism artifact (tests
        # compare two runs' lists for equality)
        self.events: List[dict] = []
        self._meta: List[dict] = []                 # chrome "M" events
        self._procs: Dict[str, int] = {}            # process name -> pid
        self._next_tid: Dict[int, int] = {}         # pid -> next tid
        self._by_key: Dict[Tuple[str, str], int] = {}
        self._tracks: List[Tuple[int, int]] = []    # handle -> (pid, tid)
        self._flow_seq = 0
        self._labels: List[str] = []                # handle -> lane label
        # host clock only: open spans, innermost last (one thread drives
        # the service, so a span's parent may sit on another lane: an
        # engine's iter under the control lane's tick), and the ids of
        # open async waits by (name, track, key)
        self._stack: List[_Span] = []
        self._n_spans = 0
        self._n_waits = 0
        self._waits: Dict[Tuple[str, int, str], str] = {}
        # process 0 / thread 0: cluster-scope control lane
        self.control = self.track("cluster", "control")
        if host_clock:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
            _watch_compiles(self)

    # ------------------------------------------------------------------
    # tracks
    # ------------------------------------------------------------------
    def track(self, process: str, thread: str = "main") -> int:
        """Handle for the (process, thread) lane, creating it (and its
        Perfetto naming metadata) on first use."""
        key = (process, thread)
        handle = self._by_key.get(key)
        if handle is not None:
            return handle
        pid = self._procs.get(process)
        if pid is None:
            pid = len(self._procs)
            self._procs[process] = pid
            self._meta.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": process}})
        tid = self._next_tid.get(pid, 0)
        self._next_tid[pid] = tid + 1
        self._meta.append({"ph": "M", "name": "thread_name",
                           "pid": pid, "tid": tid,
                           "args": {"name": thread}})
        handle = len(self._tracks)
        self._tracks.append((pid, tid))
        self._labels.append(process if thread == "main"
                            else f"{process}/{thread}")
        self._by_key[key] = handle
        return handle

    def track_for(self, name: str) -> int:
        """Resolve a transfer-engine pool name (``"endpoint"`` or
        ``"endpoint/engine"``) to a track, creating it lazily — a
        migration's source may be an endpoint that was never registered
        as an engine lane (or already detached)."""
        process, sep, thread = name.partition("/")
        return self.track(process, thread if sep else "main")

    # ------------------------------------------------------------------
    # emitters: t in simulated seconds, replaced by the host clock at the
    # call on a host-clock tracer
    # ------------------------------------------------------------------
    def _ts(self, t: float) -> float:
        return (time.perf_counter() if self.host_clock else t) * 1e6

    def complete(self, track: int, name: str, t0: float, t1: float,
                 args: Optional[dict] = None, cat: str = "span") -> None:
        """A span [t0, t1] of simulated time on ``track`` (chrome ``X``);
        on the host clock spans come from :meth:`span`."""
        pid, tid = self._tracks[track]
        ev = {"ph": "X", "name": name, "cat": cat, "pid": pid, "tid": tid,
              "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6}
        if args is not None:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, track: int, name: str, t: float,
                args: Optional[dict] = None, cat: str = "event") -> None:
        """A point event at ``t`` (chrome ``i``, thread-scoped)."""
        pid, tid = self._tracks[track]
        ev = {"ph": "i", "name": name, "cat": cat, "pid": pid, "tid": tid,
              "ts": self._ts(t), "s": "t"}
        if args is not None:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, track: int, name: str, t: float,
                values: Dict[str, float]) -> None:
        """Gauge sample(s) at ``t`` (chrome ``C``); each key renders as
        one series under the counter ``name``."""
        pid, tid = self._tracks[track]
        self.events.append({"ph": "C", "name": name, "cat": "counter",
                            "pid": pid, "tid": tid, "ts": self._ts(t),
                            "args": values})

    def new_flow_id(self) -> int:
        """Fresh id tying one flow's start to its end."""
        self._flow_seq += 1
        return self._flow_seq

    def flow_start(self, track: int, name: str, t: float, flow_id: int,
                   args: Optional[dict] = None) -> None:
        """Tail of a flow arrow (chrome ``s``) — e.g. a KV send."""
        pid, tid = self._tracks[track]
        ev = {"ph": "s", "name": name, "cat": "flow", "id": flow_id,
              "pid": pid, "tid": tid, "ts": self._ts(t)}
        if args is not None:
            ev["args"] = args
        self.events.append(ev)

    def flow_end(self, track: int, name: str, t: float, flow_id: int,
                 args: Optional[dict] = None) -> None:
        """Head of a flow arrow (chrome ``f``, binding-point enclosing)
        — e.g. the matching KV receive."""
        pid, tid = self._tracks[track]
        ev = {"ph": "f", "name": name, "cat": "flow", "id": flow_id,
              "bp": "e", "pid": pid, "tid": tid, "ts": self._ts(t)}
        if args is not None:
            ev["args"] = args
        self.events.append(ev)

    def async_begin(self, track: int, name: str, t: float, ident: str,
                    args: Optional[dict] = None,
                    cat: str = "request") -> None:
        """Open an async lifeline (chrome ``b``) keyed by (cat, id) —
        one per request, submit → finish/cancel."""
        pid, tid = self._tracks[track]
        ev = {"ph": "b", "name": name, "cat": cat, "id": ident,
              "pid": pid, "tid": tid, "ts": self._ts(t)}
        if args is not None:
            ev["args"] = args
        self.events.append(ev)

    def async_end(self, track: int, name: str, t: float, ident: str,
                  args: Optional[dict] = None,
                  cat: str = "request") -> None:
        """Close the matching async lifeline (chrome ``e``)."""
        pid, tid = self._tracks[track]
        ev = {"ph": "e", "name": name, "cat": cat, "id": ident,
              "pid": pid, "tid": tid, "ts": self._ts(t)}
        if args is not None:
            ev["args"] = args
        self.events.append(ev)

    # ------------------------------------------------------------------
    # host clock: spans with parents, per-request waits, compiles
    # ------------------------------------------------------------------
    def span(self, track: int, name: str, **args):
        """Context manager for one span of host work on ``track``, a
        child of the innermost open span (``args["parent"]``, by its
        ``sid``), also opened as the ``TraceAnnotation``
        ``<lane>:<name>``. A span tied to one request carries ``req``.
        Records nothing on the simulated clock, where host work takes
        no time."""
        if not self.host_clock:
            return NO_SPAN
        self._n_spans += 1
        args["sid"] = self._n_spans
        pid, tid = self._tracks[track]
        ev = {"ph": "X", "name": name, "cat": "span", "pid": pid,
              "tid": tid, "ts": 0.0, "dur": 0.0, "args": args}
        return _Span(self, ev,
                     self._annotation(f"{self._labels[track]}:{name}"))

    def async_open(self, track: int, name: str, req: str) -> None:
        """Start ``req``'s wait ``name`` on ``track`` (chrome ``b``, one
        id per wait), e.g. its ``queue`` wait for a slot. Host clock
        only; a wait already open is left as it is."""
        key = (name, track, req)
        if not self.host_clock or key in self._waits:
            return
        self._n_waits += 1
        ident = f"{req}#{self._n_waits}"
        self._waits[key] = ident
        # the time argument is ignored: the host clock stamps the event
        self.async_begin(track, name, 0.0, ident, {"req": req}, cat=name)

    def async_close(self, track: int, name: str, req: str,
                    args: Optional[dict] = None) -> None:
        """End ``req``'s open wait ``name`` on ``track`` (chrome ``e``);
        a no-op when none is open."""
        ident = self._waits.pop((name, track, req), None)
        if ident is not None:
            self.async_end(track, name, 0.0, ident, args, cat=name)

    def _compiled(self, fun: str, seconds: float, cache_s) -> None:
        """One JAX backend compile that just ended, as a ``compile`` span
        under the innermost open span (clamped to start inside it)."""
        parent = self._stack[-1].ev
        t1 = time.perf_counter() * 1e6
        t0 = max(t1 - seconds * 1e6, parent["ts"])
        self._n_spans += 1
        args = {"sid": self._n_spans, "parent": parent["args"]["sid"],
                "fun": fun}
        if cache_s is not None:
            args["cache_load_s"] = cache_s
        self.events.append({"ph": "X", "name": "compile", "cat": "span",
                            "pid": parent["pid"], "tid": parent["tid"],
                            "ts": t0, "dur": t1 - t0, "args": args})

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def to_chrome(self) -> List[dict]:
        """The trace as a Chrome ``trace_event`` list: naming metadata
        first, then every event stably sorted by timestamp (stable, so
        same-instant events keep their causal emission order — e.g. a
        CPI's TTFT overwrite stays after the PPI timestamp it
        supersedes)."""
        return self._meta + sorted(self.events, key=lambda e: e["ts"])

    def export(self, path: str) -> None:
        """Write Perfetto-loadable JSON (`ui.perfetto.dev` → Open trace
        file, or ``chrome://tracing``)."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.to_chrome(),
                       "displayTimeUnit": "ms"}, f)


# ---------------------------------------------------------------------------
# compiles: one JAX listener per process, feeding the live host-clock
# tracers that have a span open (the compile happened inside it)
# ---------------------------------------------------------------------------

_listening = False
_live: List["weakref.ref[Tracer]"] = []
_pending_cache_s: List[float] = []      # the read inside the next compile


def _watch_compiles(tracer: Tracer) -> None:
    global _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    _live[:] = [r for r in _live if r() is not None]
    _live.append(weakref.ref(tracer))


def _on_duration(event: str, duration: float, fun_name: str = "",
                 **_) -> None:
    if event == CACHE_LOAD_EVENT:
        _pending_cache_s.append(duration)
        return
    if event != COMPILE_EVENT:
        return
    cache_s = _pending_cache_s.pop() if _pending_cache_s else None
    _pending_cache_s.clear()
    for ref in _live:
        tracer = ref()
        if tracer is not None and tracer._stack:
            tracer._compiled(fun_name, duration, cache_s)
