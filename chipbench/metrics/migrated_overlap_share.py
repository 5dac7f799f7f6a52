"""Share of the CPI's host time on migrated prefill that it also spent
decoding: over the ``iter`` spans that started in the window and ran
chunks of a prefill whose head the PPI ran (``migrated_prefill_tokens``
> 0), the time of those that also decoded (``n_decode`` > 0) over the
time of all of them. The paper's overlap of the remaining prefill with
decode, on the host clock (``tools/trace_report.py`` reads the same on
either clock). Cronus deployments only; needs the program's spans."""
from chipbench import program_spans


def read(run):
    prog = program_spans.of(run)
    if prog is None or not prog.is_pair:
        return None
    migrated = [s for s in prog.named("iter", run.t_open, run.t_close)
                if s.args.get("migrated_prefill_tokens", 0) > 0]
    total = sum(s.dur for s in migrated)
    if total <= 0.0:
        return None
    return 100.0 * sum(s.dur for s in migrated
                       if s.args.get("n_decode", 0) > 0) / total
