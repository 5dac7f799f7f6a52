"""1 minus the union of device-op intervals over the profiled span."""
from chipbench import tracing


def read(run):
    if run.trace is None:
        return None
    window = run.trace["t1"] - run.trace["t0"]
    busy = tracing.busy_seconds(run.trace["events"])
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / window)
