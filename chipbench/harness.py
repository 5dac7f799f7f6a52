"""One benchmark run: build the served path, warm it, drive it open-loop on
the wall clock, and gather what the metrics are read from.

The entry the window drives is the user's: ``ServeSpec(...,
executor="paged").build(model, params)`` gives an ``InferenceService``.
The harness keeps its own clock. It submits each request when its due
time passes, steps the service between sends, and stamps every token on
the host clock when the engine emits it (``on_token``, which fires after
the token's logits have been read back to the host, so the device work
behind it is done). The service's own clock is simulated: each request's
``arrival`` is set to the service's lagging engine clock at submission,
so no admission waits on simulated time, and nothing the service reports
about time is read.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import time
from collections import defaultdict
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

from chipbench import families

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    family: ModuleType      # chipbench/families/<the config's "family">.py
    mix: dict               # the traffic file
    setup: dict             # the cell's own file (deployment, rate, check)

    @property
    def dims(self) -> dict:
        return self.family.dims(self.config)


def read_bench() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: Optional[dict] = None,
              root: Path = REPO) -> Cell:
    """The cell ``name`` of ``bench`` (default: the repo's BENCHMARK.json),
    with its files read from under ``root``, and the architecture family
    its configuration names (from under ``root``, else the repo's)."""
    bench = read_bench() if bench is None else bench
    (wl,) = [w for w in bench["workloads"] if w["name"] == name]
    (cf,) = [c for c in bench["configs"] if c["name"] == wl["config"]]
    path = root / cf["file"]
    config = json.loads(path.read_text())
    family = families.for_config(config, path, root)
    mix = json.loads((root / "chipbench" / "traffic"
                      / f"{wl['traffic']}.json").read_text())
    setup = json.loads((root / "chipbench" / "cells"
                        / f"{name}.json").read_text())
    return Cell(name, wl["chips"], config, family, mix, setup)


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    kind: str               # prefill | decode | extract | inject
    engine: str
    t0: float
    t1: float
    shape: tuple            # prefill (chunk, ctx, completes); decode ctxs;
                            # extract/inject (tokens,)


class Recorder:
    """Host-clock stamps and counts; one per process."""

    def __init__(self):
        self.due: Dict[str, float] = {}
        self.sent: Dict[str, float] = {}
        self.stamps: Dict[str, List[float]] = defaultdict(list)
        self.calls: List[Call] = []
        self.kv_share: List[tuple] = []       # (time, share)
        self.compiles: List[tuple] = []       # (time, event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), "compile"))

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.compiles.append((time.perf_counter(), "cache_load"))

    def clear_traffic(self) -> None:
        self.due.clear()
        self.sent.clear()
        self.stamps.clear()
        self.calls.clear()
        self.kv_share.clear()


def instrument(svc, rec: Recorder) -> None:
    """Stamp tokens on the host clock, and time and annotate each call into
    the model step, without changing what the program does."""
    for eng in svc.engines:
        inner = eng.on_token

        def on_token(req, token, t, inner=inner):
            rec.stamps[req.req_id].append(time.perf_counter())
            if inner is not None:
                inner(req, token, t)
        eng.on_token = on_token
        _wrap_executor(eng.executor, eng.name, rec)


def _wrap_executor(ex, engine: str, rec: Recorder) -> None:
    prefill, decode = ex.prefill_chunk, ex.decode
    extract, inject = ex.extract_kv, ex.inject_kv

    def prefill_chunk(slot, tokens, ctx_len, completes, enc_emb=None):
        t0 = time.perf_counter()
        with TraceAnnotation(f"{engine}.prefill_chunk"):
            out = prefill(slot, tokens, ctx_len, completes, enc_emb=enc_emb)
        rec.calls.append(Call("prefill", engine, t0, time.perf_counter(),
                              (len(tokens), int(ctx_len), bool(completes))))
        return out

    def decode_(slot_tokens, slot_lens):
        t0 = time.perf_counter()
        with TraceAnnotation(f"{engine}.decode"):
            out = decode(slot_tokens, slot_lens)
        rec.calls.append(Call("decode", engine, t0, time.perf_counter(),
                              tuple(int(slot_lens[s]) + 1
                                    for s in slot_tokens)))
        return out

    def extract_kv(slot, upto):
        t0 = time.perf_counter()
        with TraceAnnotation(f"{engine}.extract_kv"):
            out = extract(slot, upto)
        rec.calls.append(Call("extract", engine, t0, time.perf_counter(),
                              (int(upto),)))
        return out

    def inject_kv(slot, payload, upto):
        t0 = time.perf_counter()
        with TraceAnnotation(f"{engine}.inject_kv"):
            out = inject(slot, payload, upto)
        rec.calls.append(Call("inject", engine, t0, time.perf_counter(),
                              (int(upto),)))
        return out

    ex.prefill_chunk, ex.decode = prefill_chunk, decode_
    ex.extract_kv, ex.inject_kv = extract_kv, inject_kv


# ---------------------------------------------------------------------------
# build and warm
# ---------------------------------------------------------------------------

def build_service(cell: Cell, seed: int, device):
    """Weights from the seed on ``device``, then the service as a user
    builds it."""
    from repro.models import build_model
    from repro.serving.api import ServeSpec
    params = cell.family.make_params(seed, cell.dims, device)
    model = build_model(cell.family.model_config(cell.config))
    spec = ServeSpec(arch=cell.config["arch_id"], executor="paged",
                     **cell.mix["deployment"], **cell.setup["serve"])
    return spec.build(model=model, params=params), params


def warm_shapes(svc, cell: Cell, log=lambda msg: None) -> dict:
    """Run every reachable program shape once, as the cell's family warms
    it (``warm_phases``: throwaway inputs that write only the trash page,
    one logits row read back the way the executor does), in the phases
    each engine's role runs: prefill unless decode-only, decode unless
    prefill-only, the first PPI's ``extract_kv`` and a pair's decoding
    engine's ``inject_kv``. ``log`` gets one line per phase; returns the
    shapes warmed per ``<engine>.<phase>``.

    If the program changes what it compiles, the window compiles it:
    ``compiles_in_window`` shows it, and a CPU test asserts that the
    window of a tiny cell compiles nothing."""
    counts = {}
    seen_extract = False
    for eng in svc.engines:
        ecfg = eng.ecfg
        active = {"prefill": not ecfg.decode_only,
                  "decode": not ecfg.prefill_only,
                  "extract_kv": ecfg.prefill_only and not seen_extract,
                  "inject_kv": (not ecfg.prefill_only
                                and _pair_of(svc, eng) is not None)}
        seen_extract = seen_extract or active["extract_kv"]
        phases = cell.family.warm_phases(eng.executor, ecfg, cell.mix)
        for name, on in active.items():
            if not on:
                continue
            items, call = phases[name]
            t = time.perf_counter()
            for it in items:
                call(*it) if isinstance(it, tuple) else call(it)
            key = f"{eng.name}.{name}"
            counts[key] = counts.get(key, 0) + len(items)
            log(f"warm {key}: {len(items)} shapes in "
                f"{time.perf_counter() - t:.1f} s")
    return counts


def _pair_of(svc, eng):
    """The Cronus pair endpoint that holds ``eng``, or None."""
    from repro.cluster.pair import CronusPairEndpoint
    for ep in svc.endpoints:
        if isinstance(ep, CronusPairEndpoint) and eng in ep.engines:
            return ep
    return None


def decode_engine(svc):
    """The engine that decodes (a pair's CPI, or the worker)."""
    return svc.endpoints[0].engines[-1]


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------

def make_requests(planned, prefix: str = "r"):
    from repro.core.request import Request
    return [Request(req_id=f"{prefix}{p.index}", prompt=p.prompt,
                    output_len=p.output_len) for p in planned]


def wait_device(svc, family: ModuleType) -> None:
    """Wait for every program the service has launched: block on each
    executor's device state (``family.device_state``)."""
    for eng in svc.engines:
        for a in family.device_state(eng.executor):
            a.block_until_ready()


def drive(svc, reqs, due_at, rec: Recorder, until: float,
          on_time=None) -> None:
    """Send each request once its due time (host clock) has passed, and
    step the service in between, until ``until``. ``on_time(now)`` is
    called every round (the profiler uses it)."""
    dec = decode_engine(svc)
    i, n = 0, len(reqs)
    while True:
        now = time.perf_counter()
        if now >= until:
            return
        if on_time is not None:
            on_time(now)
        while i < n and due_at[i] <= now:
            r = reqs[i]
            r.arrival = min(e.clock for e in svc.engines)
            r.metrics.arrival = r.arrival
            with TraceAnnotation("submit"):
                svc.submit(r)
            rec.due[r.req_id] = due_at[i]
            rec.sent[r.req_id] = time.perf_counter()
            i += 1
        with TraceAnnotation("service.step"):
            progressed = svc.step()
        alloc = dec.allocator
        rec.kv_share.append((time.perf_counter(),
                             1.0 - alloc.num_free / alloc.num_blocks))
        if not progressed:
            nxt = due_at[i] if i < n else until
            with TraceAnnotation("wait_for_arrival"):
                time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))


def backlog(rec: Recorder, t: float) -> int:
    """Requests sent by ``t`` that had no first token at ``t``."""
    return sum(1 for rid, sent in rec.sent.items() if sent <= t
               and not (rec.stamps.get(rid) and rec.stamps[rid][0] <= t))


def free_service(svc, family: ModuleType, params, reqs) -> None:
    """Free the program's device state (each executor's, as
    ``family.device_state`` lists it, payloads, weights) now, whatever
    still refers to it."""
    for r in reqs:
        r.kv_payload = None
    for eng in svc.engines:
        for a in family.device_state(eng.executor):
            a.delete()
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    gc.collect()


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not report memory)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
