"""Benchmark harness — one entry per paper table/figure (+ roofline,
balancer ablation, kernel numerics). Prints ``name,us_per_call,derived``
CSV rows. Run: ``PYTHONPATH=src python -m benchmarks.run [--quick]``."""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller request counts (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args, _ = ap.parse_known_args()

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    from benchmarks import (bench_balancer_ablation, bench_cluster_scaling,
                            bench_fig3_predictor_fit, bench_fig4_latency,
                            bench_kernels, bench_offload_limitation,
                            bench_roofline, bench_scheduler_ablation,
                            bench_table2_throughput,
                            bench_table3_utilization)

    n2 = 250 if args.quick else 600
    n4 = 200 if args.quick else 400
    benches = {
        "table2": lambda: bench_table2_throughput.run(n_requests=n2),
        "fig3": bench_fig3_predictor_fit.run,
        "fig4": lambda: bench_fig4_latency.run(n_requests=n4),
        "table3": lambda: bench_table3_utilization.run(n_requests=n4),
        "balancer_ablation": lambda: bench_balancer_ablation.run(
            n_requests=n4),
        "offload_limitation": lambda: bench_offload_limitation.run(
            n_requests=n4),
        "cluster_scaling": lambda: bench_cluster_scaling.run(
            n_requests=150 if args.quick else 300),
        "scheduler_ablation": lambda: bench_scheduler_ablation.run(
            n_requests=80 if args.quick else 300,
            out_path="BENCH_scheduler_ablation.json"),
        "kernels": bench_kernels.run,
        "roofline": bench_roofline.run,
    }
    only = set(args.only.split(",")) if args.only else None
    failures = 0
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc(limit=2)!r}")
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
