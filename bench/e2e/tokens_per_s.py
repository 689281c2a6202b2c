"""Every output token emitted in the window, over the window's seconds."""


def read(run):
    n = sum(1 for ts in run.times.values() for t in ts if run.in_window(t))
    return n / run.seconds
