"""Host time of reading a decode step's tokens back, mean over the decode
steps that started in the window: the program's ``readback`` span, the
per-row logits reads and greedy picks after the step's logits are ready
(``decode.wait``). Needs the program's spans."""
from chipbench import program_spans


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    spans = prog.named("readback", run.t_open, run.t_close)
    return 1e3 * sum(s.dur for s in spans) / len(spans) if spans else None
