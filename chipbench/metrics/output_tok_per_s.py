"""Output tokens delivered in the window (host-clock stamps at emission),
over the window's length."""


def read(run):
    n = sum(1 for stamps in run.rec.stamps.values() for t in stamps
            if run.in_window(t))
    return n / run.seconds
