"""How late the generator sent: send time minus due time, 99th
percentile over the requests due in the window (host clock)."""


def read(run):
    from chipbench.stats import percentile
    lag = [run.rec.sent[r] - run.rec.due[r] for r in run.window_ids]
    return 1e3 * percentile(lag, 99) if lag else None
