"""Controller (``IntervalController.step_interval``): Algorithm 1's share
of the controller's time -- the program's ``ctl.assign`` span seconds
over its ``ctl.interval`` span seconds, both within the traced seconds.
The rest of each interval is observe, the payback filter, the
permutations and the delay estimates."""
from bench import program_spans as ps


def read(run):
    if not run.trace:
        return None
    split = ps.controller_split(ps.spans(run.trace["prof"]))
    if not split.get("ctl.interval"):
        return None
    return 100.0 * split.get("ctl.assign", 0.0) / split["ctl.interval"]
