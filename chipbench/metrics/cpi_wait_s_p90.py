"""Time a handed-off request waits for the chunked prefill instance, 90th
percentile over the requests due in the window whose ``extract_kv`` ended
before the close: from that end to the CPI's slot admission, i.e. the
payload's ``kv_in_flight`` and the CPI's ``queue`` wait (host clock). A
request not admitted when the window closes counts at its wait so far.
Cronus deployments only; needs the program's spans."""
from chipbench import program_spans
from chipbench.stats import percentile


def read(run):
    prog = program_spans.of(run)
    if prog is None or not prog.is_pair:
        return None
    waits = [w for rid in run.window_ids
             if (w := prog.cpi_wait(rid, run.t_close)) is not None]
    return percentile(waits, 90) if waits else None
