"""Device: share of the traced window in which no operation ran on the
chip (1 - union of ``XLA Ops`` intervals / traced seconds)."""
from bench import trace_reduce as tr


def read(run):
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    busy = tr.busy_seconds(t["prof"], t["n_devices"])
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / t["window_s"])
