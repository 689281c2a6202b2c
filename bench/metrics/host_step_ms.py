"""Scheduler (``ServingEngine.step``): the host's own work per decode step
-- page mounts, upload, dispatch, sample readback, emit -- from the
program's spans in the trace: each ``sched.step`` that decoded, less its
``model.decode_wait``, ``ctl.interval``, ``mig.apply`` and
``sched.admit``; the mean over the traced steps, in ms."""
import numpy as np

from bench import program_spans as ps


def read(run):
    if not run.trace:
        return None
    s = ps.host_step_seconds(ps.spans(run.trace["prof"]))
    return 1e3 * float(np.mean(s)) if s else None
