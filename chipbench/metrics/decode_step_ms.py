"""Host-clock time of ``executor.decode`` (it ends reading the tokens back
to the host), mean over the calls of the window."""


def read(run):
    xs = [c.t1 - c.t0 for c in run.rec.calls
          if c.kind == "decode" and run.in_window(c.t0)]
    return 1e3 * sum(xs) / len(xs) if xs else None
