"""Seconds from process start to the window's first step: weights,
engine build and calibration, compiles or cache loads, warm-up and the
fill that the traffic needs."""
UNIT = "s"


def read(run):
    return run.t0 - run.t_start
