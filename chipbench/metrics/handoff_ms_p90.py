"""Host time of the PPI->CPI KV handoff, 90th percentile over the handoffs
whose ``extract_kv`` started in the window: the program's ``extract_kv``
span on the PPI plus its ``inject_kv`` span on the CPI. Prints the
payload bytes and their rate to stderr. Cronus deployments only; needs
the program's spans."""
import sys

from chipbench import program_spans
from chipbench.stats import percentile


def read(run):
    prog = program_spans.of(run)
    if prog is None or not prog.is_pair:
        return None
    out = [h for s in prog.named("extract_kv", run.t_open, run.t_close)
           if (h := prog.handoff(s.args["req"])) is not None]
    if not out:
        return None
    secs = sum(t for t, _ in out)
    nbytes = sum(b for _, b in out)
    print(f"handoff_ms_p90: {len(out)} handoffs, {nbytes / 1e6:.1f} MB, "
          f"{nbytes / len(out) / 1e6:.2f} MB each, "
          f"{nbytes / secs / 1e9:.3f} GB/s over their host time",
          file=sys.stderr)
    return 1e3 * percentile([t for t, _ in out], 90)
