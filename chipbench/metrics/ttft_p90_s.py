"""Time to first token, 90th percentile over the requests due in the
window: from the due time to the host-clock stamp of the first token. A
request with no first token when the window closes counts at its wait so
far."""


def read(run):
    from chipbench.stats import percentile
    ttft = []
    for rid in run.window_ids:
        stamps = run.rec.stamps.get(rid)
        first = stamps[0] if stamps else run.t_close
        ttft.append(first - run.rec.due[rid])
    return percentile(ttft, 90) if ttft else None
