"""Paged KV (``serving/paging.py``): mean over the window's decode steps of
pages holding tokens over pages in the pools (``live_pages`` /
``n_pages`` of every allocator), read as each step is dispatched."""
import numpy as np


def read(run):
    s = run.counters.get("live_share")
    return 100.0 * float(np.mean(s)) if s else None
