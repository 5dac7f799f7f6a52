"""Host time per round of the service's loop spent outside the model
step: mean over the ``tick`` spans that started in the window of the
tick's duration less the executor calls under it (``prefill_chunk``,
``decode``, ``extract_kv``, ``inject_kv``). That is routing, the
Balancer, the handoff pump, scheduling and bookkeeping. Needs the
program's spans."""
from chipbench import program_spans


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    ticks = prog.named("tick", run.t_open, run.t_close)
    if not ticks:
        return None
    return 1e3 * sum(t.dur - prog.executor_time(t)
                     for t in ticks) / len(ticks)
