"""Highest share of the decoding engine's KV blocks in use, sampled after
every step of the window."""


def read(run):
    xs = [s for t, s in run.rec.kv_share if run.in_window(t)]
    return 100.0 * max(xs) if xs else None
