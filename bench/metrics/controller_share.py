"""Controller (``_interval_plan``: observe, Algorithm 1, payback filter):
host seconds of the window's intervals (``migration_log[i]["plan_s"]``)
over the window's seconds.  The decode loop waits for all of it."""


def read(run):
    p = run.counters.get("plan_s")
    if not p:
        return None
    return 100.0 * sum(p) / run.seconds
