"""Scheduler (``ServingEngine.step``): share of slot-steps that decoded a
live request over the window, from the engine's ``slot_busy_steps`` and
``decode_steps`` counters."""


def read(run):
    c = run.counters
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["slot_busy_steps"] / (c["decode_steps"] * c["n_slots"])
