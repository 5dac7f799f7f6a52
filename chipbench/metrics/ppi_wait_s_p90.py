"""Time a request waits for the partial prefill instance, 90th percentile
over the requests due in the window: from the program's ``submit`` to the
PPI's slot admission (the end of its ``queue`` wait), on the host clock.
It holds the wait to be routed, since the pair takes at most two prompts
at a time, and the PPI's own queue. A request not admitted when the
window closes counts at its wait so far. Cronus deployments only; needs
the program's spans."""
from chipbench import program_spans
from chipbench.stats import percentile


def read(run):
    prog = program_spans.of(run)
    if prog is None or not prog.is_pair:
        return None
    waits = [w for rid in run.window_ids
             if (w := prog.ppi_wait(rid, run.t_close)) is not None]
    return percentile(waits, 90) if waits else None
