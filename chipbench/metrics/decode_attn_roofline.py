"""The paged decode attention kernel's share of its roofline: the least
time its live work needs (K/V bytes read or FLOPs, whichever bounds each
call) over the kernel's device time in the profiled span."""
import sys

from chipbench import costs, families, tracing


def read(run):
    if run.trace is None:
        return None
    t = tracing.kernel_seconds(run.trace["events"], tracing.DECODE_PROGRAM)
    if t <= 0.0:
        return None
    fam = families.of(run.dims)
    least, bounds = 0.0, set()
    for c in run.traced_calls("decode"):
        for ctx in c.shape:
            s, b = costs.least_time(fam.decode_attn_flops(run.dims, ctx),
                                    fam.decode_attn_bytes(run.dims, ctx),
                                    run.peak)
            least += s
            bounds.add(b)
    print(f"decode_attn_roofline bound: {sorted(bounds)}", file=sys.stderr)
    return 100.0 * least / t
