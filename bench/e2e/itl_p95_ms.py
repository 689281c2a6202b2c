"""95th percentile of every gap between consecutive tokens of one request,
both inside the window, in ms.  Controller stalls and prefills that hold
the decode loop land here."""
import numpy as np


def read(run):
    gaps = [b - a for ts in run.times.values() for a, b in zip(ts, ts[1:])
            if run.in_window(a) and run.in_window(b)]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
