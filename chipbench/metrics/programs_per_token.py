"""Device programs (``XLA Modules`` events) that start in the window per
output token served in it."""
from chipbench import trace as tr


def read(run):
    if run.events is None or run.tokens_in_window <= 0:
        return None
    lo, hi = run.window
    n = sum(lo <= e.t0 < hi for e in tr.modules(run.events))
    return n / run.tokens_in_window if n else None
