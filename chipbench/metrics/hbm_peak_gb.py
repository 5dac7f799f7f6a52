"""Peak device memory in use (``peak_bytes_in_use``), highest over the
devices, read when the window closes."""


def read(run):
    return run.hbm_peak_bytes / 1e9 if run.hbm_peak_bytes else None
