"""90th percentile of time to first token over every request whose first
token falls in the window, from its scheduled arrival (open loop: the
wait behind a stall counts)."""
import numpy as np


def read(run):
    ttft = [run.times[rid][0] - t for rid, t in run.arrival.items()
            if run.times.get(rid) and run.in_window(run.times[rid][0])]
    return float(np.percentile(ttft, 90)) if ttft else None
