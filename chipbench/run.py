"""Run one benchmark cell once on the chip and print one JSON result line.

Usage, from the root of a checkout on a machine with a TPU:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (configuration, traffic mix, deployment) is found by name from
``BENCHMARK.json``. The run makes the weights and the requests from the
seed, builds the served path, warms every program shape the cell can
reach, lets the cell's own traffic run for a warm span, and then measures
for ``--seconds``. With ``--trace 1`` it also records the program's own
spans from the end of the warm-up (``svc.start_trace()``), profiles a few
seconds inside the window, and reports the per-layer metrics instead of
the end-to-end ones.
Afterwards it checks the served tokens against the float32 reference.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402

from chipbench import costs, harness, reference, tracing  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.harness import Recorder  # noqa: E402

TRACE_SECONDS = 4.0          # profiled span inside a traced window
SAMPLE_STREAM = 0x53414D


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class RunData:
    """What the metric readers read: host-clock records of the window,
    the reduced trace of a traced run, and the run's constants."""

    def __init__(self, cell, rec: Recorder, reqs, t_open: float,
                 t_close: float, setup_s: float, peak: dict):
        self.cell = cell
        self.dims = cell.dims
        self.rec = rec
        self.reqs = {r.req_id: r for r in reqs}
        self.t_open, self.t_close = t_open, t_close
        self.setup_s = setup_s
        self.peak = peak
        self.window_ids = [i for i, t in rec.due.items()
                           if t_open <= t < t_close]
        self.hbm_peak_bytes = 0
        self.trace = None     # {"events", "t0", "t1"} in a traced run
        self.program_events = None   # the program's recorder, traced run
        self.has_pair = False

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def traced_calls(self, kind: str):
        """Calls launched inside the profiled span (each of them ran on the
        device inside it: the span opens and closes on an idle device)."""
        if self.trace is None:
            return []
        return [c for c in self.rec.calls if c.kind == kind
                and self.trace["t0"] <= c.t0 < self.trace["t1"]]


def read_metric(name: str, data: RunData):
    """Call ``chipbench/metrics/<name>.py``'s ``read``; None = nothing to
    read in this run."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(data)


def metric_entries(bench: dict, workload: str, traced: bool):
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def require_chips(chips: int):
    """The first device, if JAX sees a TPU with enough chips; else exit."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {devs[0].platform}")
        sys.exit(1)
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devs)}")
        sys.exit(1)
    return devs[0]


def configure_cache() -> str:
    from repro.launch.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    # tiny eager programs (the handoff's gathers) are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Profiler:
    """Profiles ``TRACE_SECONDS`` in the middle of the window. The span
    opens and closes on an idle device, so every program launched inside
    it ran inside it."""

    def __init__(self, svc, family, start_at: float, seconds: float):
        self.svc, self.family = svc, family
        self.start_at, self.seconds = start_at, seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.t0 = self.t1 = None

    def __call__(self, now: float) -> None:
        if self.t0 is None and now >= self.start_at:
            harness.wait_device(self.svc, self.family)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t0 = time.perf_counter()
        elif self.t0 is not None and self.t1 is None \
                and now >= self.t0 + self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.t0 is not None and self.t1 is None:
            harness.wait_device(self.svc, self.family)
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()

    def events(self):
        path = tracing.find_xplane(self.dir)
        events = tracing.load_events(path)
        shutil.rmtree(self.dir, ignore_errors=True)
        return events


def sample_for_check(reqs, seed: int, vocab: int, tokens: int,
                     max_ref_tokens: int):
    """Finished requests to compare, drawn from the seed, always with the
    longest finished request in the sample."""
    from repro.core.request import ReqState
    done = sorted((r for r in reqs if r.state is ReqState.FINISHED),
                  key=lambda r: int(r.req_id[1:]))
    if not done:
        return []
    longest = max(done, key=lambda r: (r.input_len + len(r.generated)))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([SAMPLE_STREAM, int(seed)]).permutation(
        len(rest))
    picked = [longest]
    served = len(longest.generated)
    total = longest.input_len + served
    for i in order:
        if served >= tokens:
            break
        r = rest[i]
        n = r.input_len + len(r.generated)
        if total + n > max_ref_tokens:
            continue
        picked.append(r)
        served += len(r.generated)
        total += n
    return [{"prompt": np.asarray(r.prompt[:r.metrics.input_len]),
             "served": np.asarray(r.generated, np.int32),
             "split": 0 < r.partial_len < r.input_len}
            for r in picked]


def bad_requests(reqs, vocab: int) -> int:
    """Finished requests whose output has the wrong length or a token
    outside the vocabulary."""
    from repro.core.request import ReqState
    bad = 0
    for r in reqs:
        if r.state is ReqState.FINISHED and (
                len(r.generated) != r.output_len
                or any(not 0 <= t < vocab for t in r.generated)):
            bad += 1
    return bad


def run(workload: str, seed: int, seconds: float, traced: bool,
        bench: Optional[dict] = None, root: Path = REPO, device=None,
        process_start: float = PROCESS_START, cache: bool = True,
        control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``bench`` is BENCHMARK.json as a dict (default: the repo's) and ``root``
    the directory its files are under. ``device`` None means: look for the
    chip, and exit without one. ``cache=False`` leaves JAX's persistent
    compilation cache off (CPU tests). ``control=True`` puts the control
    in the program's place for the comparison: the tokens compared are
    the ones the fp8 reference puts first at the served positions, so a
    sound limit must read the run as not correct."""
    bench = harness.read_bench() if bench is None else bench
    cell = harness.load_cell(workload, bench, root)
    if device is None:
        device = require_chips(cell.chips)
    if cache:
        log(f"compile cache: {configure_cache()}")
    rec = Recorder()
    kind = device.device_kind
    peak = costs.peaks(kind) if device.platform == "tpu" else None

    t = time.perf_counter()
    svc, params = harness.build_service(cell, seed, device)
    jax.block_until_ready(params)
    log(f"weights and service: {time.perf_counter() - t:.1f} s")
    harness.instrument(svc, rec)
    t = time.perf_counter()
    warmed = harness.warm_shapes(svc, cell, log)
    log(f"warm-up of program shapes {warmed}: "
        f"{time.perf_counter() - t:.1f} s, {len(rec.compiles)} compiles "
        f"or cache loads so far")
    # a traced run records the program's own spans, from after the warm-up
    # (its compiles stay out of the record) to the close
    tracer = svc.start_trace() if traced else None

    s = cell.setup
    warm_s = float(s["warm_seconds"])
    planned = traffic.plan(cell.mix, float(s["rate_per_s"]), warm_s, seconds,
                           seed, cell.dims["vocab_size"])
    reqs = harness.make_requests(planned)
    t_start = time.perf_counter()
    due_at = [t_start + p.due_s for p in planned]
    t_open = t_start + warm_s
    t_close = t_open + seconds
    setup_s = t_open - process_start
    profiler = (Profiler(svc, cell.family,
                         t_open + max(0.0, (seconds - TRACE_SECONDS) / 2),
                         min(TRACE_SECONDS, seconds))
                if traced else None)
    harness.drive(svc, reqs, due_at, rec, t_close, on_time=profiler)
    if profiler is not None:
        profiler.stop()
    data = RunData(cell, rec, reqs, t_open, t_close, setup_s, peak)
    data.has_pair = harness._pair_of(svc, svc.engines[0]) is not None
    data.hbm_peak_bytes = harness.peak_bytes()
    log(f"window: {len(data.window_ids)} requests sent, "
        f"{sum(len(v) for v in rec.stamps.values())} tokens stamped; "
        f"backlog (sent, no first token) {harness.backlog(rec, t_open)} at "
        f"the open, {harness.backlog(rec, t_close)} at the close")

    if profiler is not None:
        events = profiler.events()
        data.trace = {"events": events, "t0": profiler.t0, "t1": profiler.t1}
    if tracer is not None:
        data.program_events = tracer.events
    harness.free_service(svc, cell.family, params, reqs)
    del svc, params, profiler, tracer

    metrics = {}
    for m in metric_entries(bench, workload, traced):
        value = read_metric(m["name"], data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": False, "attempted": len(data.window_ids),
              "failed": 0, "metrics": metrics,
              "device": {"platform": device.platform, "kind": kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": data.hbm_peak_bytes}}
    if traced:
        ev = data.trace["events"]
        result["device"]["busy_s"] = tracing.busy_seconds(ev)
        result["device"]["window_s"] = data.trace["t1"] - data.trace["t0"]
        result["breakdown"] = {"device_ops": tracing.top_ops(ev),
                               "idle_gaps": tracing.idle_gaps(ev)}

    # correctness: the served tokens against the float32 reference
    vocab = cell.dims["vocab_size"]
    chk = s["check"]
    window_reqs = [data.reqs[i] for i in data.window_ids]
    result["failed"] = bad_requests(window_reqs, vocab)
    t = time.perf_counter()
    samples = sample_for_check(reqs, seed, vocab, chk["tokens"],
                               chk["max_reference_tokens"])
    check = {"failed_requests": {"value": result["failed"], "limit": 0}}
    if samples:
        cmp = reference.compare(seed, cell.dims, samples, control=control,
                                device=device)
        who = "fp8 control" if control else "program"
        log(f"reference against the {who}: {cmp} over {len(samples)} requests "
            f"({sum(x['split'] for x in samples)} split PPI->CPI), "
            f"{time.perf_counter() - t:.1f} s")
        if control:
            result["control"] = {"served_max_gap": cmp["served_max_gap"]}
        check["max_logit_gap"] = {"value": cmp["max_gap"],
                                  "limit": chk["max_logit_gap"]}
        check["tokens_compared"] = {"value": cmp["n_tokens"],
                                    "limit": chk["min_tokens"],
                                    "at_least": True}
    else:
        check["tokens_compared"] = {"value": 0, "limit": chk["min_tokens"],
                                    "at_least": True}
    result["correct"] = all(
        (c["value"] >= c["limit"]) if c.get("at_least")
        else (c["value"] <= c["limit"]) for c in check.values())
    result["check"] = check
    for name, c in check.items():
        rule = ">=" if c.get("at_least") else "<="
        log(f"check {name}: {c['value']} (limit {rule} {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
