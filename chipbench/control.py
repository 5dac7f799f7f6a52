"""Run the cell with the lower-precision control in the program's place.

Usage, from the checkout root on a machine with a TPU:

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \\
        --seconds 51

For each seed, one process runs the cell as a benchmark run does (weights
and traffic from the seed, warm-up, warm span, window), and then decides
``correct`` with the control put in the program's place: the tokens
compared with the float32 reference are the ones that the fp8 reference
puts first at each served position. It prints one JSON line per seed:
``correct`` (false where the limit separates the control from the
program), the compared numbers with their limits, and the widest gap of
the served bf16 tokens of the same run. It exits 1 if any seed reads
``correct`` true. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run(args.workload, seed, args.seconds, False,
                      process_start=time.perf_counter(), control=True)
        passed.append(res["correct"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "served_max_gap":
                              res.get("control", {}).get("served_max_gap"),
                          "check": res["check"]}), flush=True)
    return 1 if any(passed) else 0


if __name__ == "__main__":
    sys.exit(main())
