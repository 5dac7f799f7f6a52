"""Programs compiled, or loaded from the persistent cache, inside the
window (JAX's backend-compile and cache-hit events). Should read 0."""


def read(run):
    return sum(1 for t, _ in run.rec.compiles if run.in_window(t))
