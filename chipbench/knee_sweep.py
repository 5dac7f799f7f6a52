"""Find a cell's knee once, by a sweep of fixed arrival rates on the chip.

Usage, from the checkout root on a machine with a TPU:

    python3 chipbench/knee_sweep.py --workload <name> --seed <n> \\
        --rates 0.2,1,2,3 --seconds 40 --ttft-s 2 --tbt-ms 500

One process builds and warms the cell's served path once, then offers
each rate for ``--seconds`` (open loop, the cell's own traffic mix), stops
sending, and lets every request sent finish before the next rate. For
each rate it prints one JSON line: requests sent, TTFT and TBT
percentiles on the host clock, output tokens per second over the sending
span, the share of requests that met both limits
(``--ttft-s``, ``--tbt-ms``; a request still unfinished after the drain
misses), and the backlog (requests sent but without a first token) at the
middle and at the end of the sending span. The knee is the highest rate
with at least 90% meeting both limits and a backlog that does not grow.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import jax  # noqa: E402

from chipbench import harness, run, traffic  # noqa: E402
from chipbench.stats import meets_slo, percentile  # noqa: E402

DRAIN_LIMIT_S = 90.0


def offer(svc, cell, rec, rate, seconds, seed, prefix, lim):
    """Offer ``rate`` for ``seconds``, then drain; returns one line."""
    planned = traffic.plan(cell.mix, rate, 0.0, seconds, seed,
                           cell.dims["vocab_size"])
    reqs = harness.make_requests(planned, prefix=prefix)
    rec.clear_traffic()
    t0 = time.perf_counter()
    due = [t0 + p.due_s for p in planned]
    backlog = []

    def watch(now):
        if len(backlog) < 1 and now >= t0 + seconds / 2:
            backlog.append(sum(1 for r in rec.sent if r not in rec.stamps))
    harness.drive(svc, reqs, due, rec, t0 + seconds, on_time=watch)
    t_end = time.perf_counter()
    backlog.append(sum(1 for r in rec.sent if r not in rec.stamps))
    sent = [r for r in reqs if r.req_id in rec.sent]
    tokens = sum(1 for s in rec.stamps.values() for t in s if t < t_end)
    deadline = t_end + DRAIN_LIMIT_S
    while svc.n_active > 0 and time.perf_counter() < deadline:
        if not svc.step():
            time.sleep(0.001)
    ttft, gaps, ok = [], [], 0
    for r in sent:
        st = rec.stamps.get(r.req_id, [])
        t_first = st[0] if st else time.perf_counter()
        ttft.append(t_first - rec.due[r.req_id])
        g = [b - a for a, b in zip(st, st[1:])]
        gaps += g
        if len(st) == r.output_len and meets_slo(
                ttft[-1], g, lim["ttft_s"], lim["tbt_ms"] / 1e3):
            ok += 1
    return {"rate": rate, "sent": len(sent), "seconds": seconds,
            "ttft_p50_s": percentile(ttft, 50), "ttft_p90_s": percentile(ttft, 90),
            "tbt_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
            "tbt_p99_ms": 1e3 * percentile(gaps, 99) if gaps else None,
            "output_tok_per_s": tokens / seconds,
            "met_both": ok / max(len(sent), 1),
            "backlog_mid_end": backlog, "limits": lim,
            "unfinished_after_drain": svc.n_active}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--ttft-s", type=float, required=True)
    ap.add_argument("--tbt-ms", type=float, required=True)
    args = ap.parse_args(argv)
    lim = {"ttft_s": args.ttft_s, "tbt_ms": args.tbt_ms}
    cell = harness.load_cell(args.workload)
    device = run.require_chips(cell.chips)
    run.configure_cache()
    rec = harness.Recorder()
    svc, _ = harness.build_service(cell, args.seed, device)
    harness.instrument(svc, rec)
    harness.warm_shapes(svc, cell, run.log)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        line = offer(svc, cell, rec, rate, args.seconds, args.seed + i,
                     prefix=f"k{i}-", lim=lim)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
