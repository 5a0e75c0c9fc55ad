"""Seconds from process start to the first timed request: imports, the
chip, compiling or reading back every program, and the warm-up."""


def read(run):
    return run.setup_s
