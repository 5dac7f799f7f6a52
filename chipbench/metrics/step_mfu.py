"""FLOPs the served tokens need (live shapes, ``chipbench/costs.py``) over
the device time of the prefill and decode step programs in the profiled
span, as a share of the chip's peak bf16 rate."""
from chipbench import costs, tracing


def read(run):
    if run.trace is None:
        return None
    ev = run.trace["events"]
    t = (tracing.program_seconds(ev, tracing.PREFILL_PROGRAM)
         + tracing.program_seconds(ev, tracing.DECODE_PROGRAM))
    if t <= 0.0:
        return None
    flops = sum(costs.prefill_step_flops(run.dims, *c.shape)
                for c in run.traced_calls("prefill"))
    flops += sum(costs.decode_step_flops(run.dims, c.shape)
                 for c in run.traced_calls("decode"))
    return 100.0 * flops / t / run.peak["bf16_flops_per_s"]
