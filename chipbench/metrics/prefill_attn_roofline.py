"""The chunked prefill attention kernel's share of its roofline: the least
time its live work needs (FLOPs over the causal keys, or K/V bytes,
whichever bounds each chunk) over the kernel's device time in the
profiled span."""
import sys

from chipbench import costs, families, tracing


def read(run):
    if run.trace is None:
        return None
    t = tracing.kernel_seconds(run.trace["events"], tracing.PREFILL_PROGRAM)
    if t <= 0.0:
        return None
    fam = families.of(run.dims)
    least, bounds = 0.0, set()
    for c in run.traced_calls("prefill"):
        chunk, ctx, _ = c.shape
        s, b = costs.least_time(fam.prefill_attn_flops(run.dims, chunk, ctx),
                                fam.prefill_attn_bytes(run.dims, chunk, ctx),
                                run.peak)
        least += s
        bounds.add(b)
    print(f"prefill_attn_roofline bound: {sorted(bounds)}", file=sys.stderr)
    return 100.0 * least / t
