"""Set-up: from process start to the window's first second (imports,
weights, compiles or cache loads, slots filled, first plan applied)."""


def read(run):
    return run.setup_s
