"""Seconds of compiling (or loading from the persistent cache) that the
program did inside the window: the sum of its ``compile`` spans, one per
JAX backend compile, that started in the window. Prints the seconds by
the span each compile ran under to stderr. Should read 0. Needs the
program's spans."""
import sys
from collections import defaultdict

from chipbench import program_spans


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    spans = prog.named("compile", run.t_open, run.t_close)
    by_parent = defaultdict(float)
    for s in spans:
        by_parent[prog.by_sid[s.parent].name] += s.dur
    print("compile_s_in_window by parent span: "
          + (", ".join(f"{k} {v:.3f} s" for k, v in
                       sorted(by_parent.items(), key=lambda kv: -kv[1]))
             or "none"), file=sys.stderr)
    return sum(s.dur for s in spans)
